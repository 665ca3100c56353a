"""The one-chip PUT route (ISSUE 35): on ``backend="tpu"`` the full
blocks of ``Erasure.encode_framed``'s body cross the link once — data
stripes up, parity and the k+m digests down from ONE fused program per
stripe (``rs_fused.launch_encode_bitrot``, program ``mt_encode_bitrot``)
— through the combiner's ``encode-bitrot`` bucket (ops/codec.py); a tail
block keeps the two-dispatch route (ISSUE 35's fallback, taken by the
rule it gives: the shape decides).  Here on
XLA:CPU the route runs in its XLA forms (tests/conftest.py); the Pallas
program is run interpreted where a case says so.  Pinned: the rows on
disk against the host one-copy route and the plain reference, who
shares a dispatch with whom, that a batch goes out one stripe per
dispatch, and what the dispatch counts.
"""

import threading

import numpy as np
import pytest

from minio_tpu.admin import metrics as _metrics
from minio_tpu.hashing import bitrot
from minio_tpu.hashing.highwayhash import hh256
from minio_tpu.obs import trace
from minio_tpu.ops import device, gf8, gf8_ref, rs_fused
from minio_tpu.ops.codec import Erasure
from minio_tpu.parallel import batcher

from . import shard_files

MIB = 1 << 20
BS = 10 * MIB
GEOMETRIES = [(12, 4), (2, 2)]
# (object size, callers that meet in the combiner).  Sizes under a
# 10 MiB block: shard widths under one 32-byte packet (1, 100, 372 B
# over k = 12: 1, 9, 31), the small-zipf palette
# (benchmarks/traffic/small-zipf.json; none of its widths is a packet
# multiple), one whole block, a block + a 1-byte and a 100,001-byte
# tail, two blocks and a half
CASES = [(1, 1), (100, 1), (372, 1), (384, 1),
         (3000, 1), (12000, 1), (50000, 1), (100000, 1), (200000, 1),
         (400000, 1), (1000000, 1),
         (BS, 1), (BS + 1, 1), (BS + 100001, 1), (25 * MIB, 1),
         (3000, 2), (BS, 2), (100000, 3), (BS + 1, 3), (12000, 5),
         (1000000, 5)]


def _body(n: int, seed: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture
def combining():
    """The batcher on, with a window long enough that callers released
    together meet in one batch."""
    cfg = batcher.CONFIG
    saved = (cfg.enable, cfg.window_s, cfg._loaded)
    cfg.enable, cfg.window_s, cfg._loaded = True, 0.25, True
    yield cfg
    cfg.enable, cfg.window_s, cfg._loaded = saved


def _together(n: int, fn) -> list:
    """``fn(i)`` on n threads released by one barrier; their results."""
    got: list = [None] * n
    gate = threading.Barrier(n)

    def run(i):
        gate.wait(10)
        got[i] = fn(i)

    ths = [threading.Thread(target=run, args=(i,), name=f"mt-test-put{i}",
                            daemon=True) for i in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(120)
        assert not t.is_alive()
    return got


@pytest.mark.parametrize("total,callers", CASES)
@pytest.mark.parametrize("k,m", GEOMETRIES, ids=["12+4", "2+2"])
def test_rows_are_the_host_routes_and_the_plain_references(
        combining, k, m, total, callers):
    """Bit for bit the k+m framed rows of the host one-copy route and of
    the plain reference (reedsolomon split + gf8_ref parity + the host
    HighwayHash), alone and when 2 / 3 / 5 callers of one width share a
    combined dispatch (each gets the rows of its own body)."""
    codec = batcher.codec_for(k, m, BS, "tpu")
    host = Erasure(k, m, BS, backend="numpy")
    bodies = [_body(total, total + 31 * k + i) for i in range(callers)]
    if callers > 1:
        # the width's program is built: the callers meet in the window,
        # not behind a compile
        codec.encode_framed(bodies[0], bitrot.HIGHWAYHASH256S)
    before = batcher.GLOBAL.snapshot()
    got = _together(callers, lambda i: codec.encode_framed(
        bodies[i], bitrot.HIGHWAYHASH256S))
    after = batcher.GLOBAL.snapshot()
    submissions = (total >= BS) + (total % BS > 0)
    assert after["requests"] - before["requests"] == callers * submissions
    if callers > 1:
        assert after["dispatches"] - before["dispatches"] \
            < callers * submissions
    for i, body in enumerate(bodies):
        rows = [bytes(r) for r in got[i]]
        assert len(rows) == k + m
        assert rows == [bytes(r) for r in host.encode_framed(
            body, bitrot.HIGHWAYHASH256S)], f"caller {i}: host route"
        assert rows == shard_files.reference_framed(body, BS, k, m), \
            f"caller {i}: plain reference"


def _stripes(B: int, k: int, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (B, k, n), dtype=np.uint8)


def test_a_batch_goes_out_one_stripe_per_dispatch_all_up_before_any_down(
        monkeypatch):
    """Whatever batch the combiner formed, the program sees one stripe:
    one program per shard width serves any batch, and every stripe is
    uploaded and launched before any result is fetched."""
    k, m, n = 4, 2, 1000
    rows = np.asarray(gf8.rs_matrix(k, k + m))[k:]
    events = []
    upload, fetch = device.upload, device.fetch

    def up(op, x):
        events.append(("up", x.shape[0]))
        return upload(op, x)

    def down(op, x, rows=None):
        events.append(("down", None))
        return fetch(op, x, rows)

    monkeypatch.setattr(device, "upload", up)
    monkeypatch.setattr(device, "fetch", down)
    blocks = _stripes(3, k, n, 2)
    parity, digests = rs_fused.launch_encode_bitrot(rows, blocks, n)()
    assert events == [("up", 1)] * 3 + [("down", None)] * 6
    assert [p.shape for p in parity] == [(m, n)] * 3
    assert digests.shape == (3, k + m, 32)
    for b in range(3):
        want = gf8_ref.encode_parity(blocks[b], m)
        assert np.array_equal(parity[b], want), b
        for s, row in enumerate(np.concatenate([blocks[b], want])):
            assert digests[b, s].tobytes() == hh256(row.tobytes()), (b, s)


def _counters() -> dict:
    snap = _metrics.GLOBAL.snapshot()
    out = {d: snap.get(("mt_tpu_link_bytes_total",
                        (("dir", d), ("op", "encode"))), 0)
           for d in ("h2d", "d2h")}
    out.update({kind: snap.get(
        ("mt_tpu_hash_rows_total", (("kind", kind),)), 0)
        for kind in ("real", "hashed")})
    for op in ("encode", "hash"):
        out["ops." + op] = snap.get(
            ("mt_tpu_ops_total", (("backend", "tpu"), ("op", op))), 0)
    out["bytes"] = snap.get(
        ("mt_tpu_bytes_total", (("backend", "tpu"), ("op", "encode"))), 0)
    return out


@pytest.mark.parametrize("tail", [0, 70], ids=["whole-blocks", "blocks+tail"])
@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_a_put_is_one_encode_dispatch_that_counts_what_it_moved(
        pallas, tail, monkeypatch):
    """One ``encode`` dispatch of the body's bytes; for the full blocks
    the link carries the staged (padded) data up and parity + digests
    down, once each, with no ``hash`` dispatch; the hash lanes come from
    the plan of the form in force; the legs are the ones the benchmark
    reads.  A tail block adds its own RS upload and ONE ``hash``
    dispatch (the two-dispatch route it keeps)."""
    monkeypatch.setattr(device, "use_pallas", lambda: pallas)
    k, m, bs, ss = 2, 2, 2 * 96, 96
    codec = Erasure(k, m, bs, backend="tpu")
    data = _body(2 * bs + tail, 34)
    before = _counters()
    with trace.HTTP_TRACE.subscribe() as sub:
        rows = codec.encode_framed(data, bitrot.HIGHWAYHASH256S)
        spans = list(sub.drain(80, timeout=2.0))
    assert [bytes(r) for r in rows] == \
        shard_files.reference_framed(data, bs, k, m)
    moved = {key: v - before[key] for key, v in _counters().items()}
    # ONE fused submission of the two full blocks, one stripe each
    w_full = rs_fused.staged_width(k, m, ss)
    if pallas:
        lanes = 2 * rs_fused.hashed_lanes(rs_fused.plan(1, k, m, ss))
        assert (w_full, lanes) == (256, 2 * 128)
    else:
        lanes = 2 * (k + m)
        assert w_full == ss
    up = 2 * k * w_full
    down = 2 * m * w_full + 2 * (k + m) * 32
    if tail:
        # the tail's RS dispatch at its lane-padded width (35 -> 128),
        # and the rows its bitrot leg hashes (4, or one 128-row tile)
        up, down = up + k * 128, down + m * 128
        lanes += 128 if pallas else k + m
    assert moved == {
        "h2d": up, "d2h": down,
        "real": (2 + bool(tail)) * (k + m), "hashed": lanes,
        "ops.encode": 1, "ops.hash": int(bool(tail)), "bytes": len(data)}
    legs = [s.get("funcName") or "" for s in spans]
    for leg in ("encode.prep", "encode.upload", "encode.launch",
                "encode.fetch", "hash.frame", "encode.dispatch",
                "encode-bitrot.batch"):
        assert leg in legs, (leg, legs)
    assert bool([leg for leg in legs if leg.startswith("hash.")
                 and leg != "hash.frame"]) == bool(tail)


def test_a_new_block_width_is_one_compiled_program():
    """Kernel call (here its XLA forms), remainder packet and
    finalization are traced together: a full-block width the process has
    not seen costs ONE backend compile and one build, under the
    program's name, and a second PUT of it none."""
    k, m = 4, 2

    def rows():
        return {name: (row["compiles"], row["builds"]) for name, row in
                device.compile_stats()["by_function"].items()}

    Erasure(k, m, 4096, backend="tpu").encode_framed(
        _body(4096, 1), bitrot.HIGHWAYHASH256S)            # matrix up
    codec = Erasure(k, m, 5332, backend="tpu")             # width 1333
    before = rows()
    codec.encode_framed(_body(5332, 2), bitrot.HIGHWAYHASH256S)
    after = rows()
    new = {name: (v[0] - before.get(name, (0, 0))[0],
                  v[1] - before.get(name, (0, 0))[1])
           for name, v in after.items() if v != before.get(name, (0, 0))}
    assert new == {"mt_encode_bitrot": (1, 1)}
    codec.encode_framed(_body(2 * 5332, 3), bitrot.HIGHWAYHASH256S)
    assert rows() == after

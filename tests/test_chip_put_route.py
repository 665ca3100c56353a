"""The one-chip PUT route (ISSUE 35): on ``backend="tpu"`` the full
blocks of ``Erasure.encode_framed``'s body cross the link once — data
stripes up, parity and the k+m digests down from fused programs
(program ``mt_encode_bitrot``): ONE per stripe for a body of one block
(``rs_fused.launch_encode_bitrot``, the combiner's ``encode-bitrot``
bucket), ONE per stripe group for a body of several
(``rs_fused.launch_encode_bitrot_groups``, the ``encode-bitrot-group``
bucket; ops/codec.py); a tail block keeps the two-dispatch route (the
shape decides; ``Erasure.encode_framed`` says why).  Here on
XLA:CPU the route runs in its XLA forms (tests/conftest.py); the Pallas
program is run interpreted where a case says so.  Pinned: the rows on
disk against the host one-copy route and the plain reference, who
shares a dispatch with whom, that a batch of one-block bodies goes out
one stripe per dispatch, how a body of several goes out in groups, and
what the dispatches count.
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
    for form in ("stripe", "group"):
        for what in ("programs", "stripes"):
            out[f"{what}.{form}"] = snap.get(
                (f"mt_tpu_fused_{what}_total", (("form", form),)), 0)
    return out


@pytest.mark.parametrize("tail", [0, 70], ids=["whole-blocks", "blocks+tail"])
@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_a_put_is_one_encode_dispatch_that_counts_what_it_moved(
        pallas, tail, monkeypatch):
    """One ``encode`` dispatch of the body's bytes; for the full blocks
    the link carries the staged (padded) data up and parity + digests
    down, once each, with no ``hash`` dispatch; the hash lanes come from
    the plan of the form in force; the legs are the ones the benchmark
    reads.  Two full blocks are one partial stripe group: one program,
    its zero stripes made on the device and never on the link.  A tail
    block adds its own RS upload and ONE ``hash`` dispatch (the
    two-dispatch route it keeps)."""
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
    # ONE fused submission of the two full blocks: one group program of
    # G = 32 stripes at 2+2, two of them real
    w_full = rs_fused.staged_width(k, m, ss)
    p = rs_fused.group_plan(k, m, ss)
    assert p["bs"] == 32
    if pallas:
        lanes = rs_fused.hashed_lanes(p)
        assert (w_full, lanes) == (256, 128)
    else:
        lanes = 32 * (k + m)
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
        "ops.encode": 1, "ops.hash": int(bool(tail)), "bytes": len(data),
        "programs.stripe": 0, "stripes.stripe": 0,
        "programs.group": 1, "stripes.group": 2}
    legs = [s.get("funcName") or "" for s in spans]
    for leg in ("encode.prep", "encode.upload", "encode.launch",
                "encode.fetch", "hash.frame", "encode.dispatch",
                "encode-bitrot-group.batch"):
        assert leg in legs, (leg, legs)
    assert bool([leg for leg in legs if leg.startswith("hash.")
                 and leg != "hash.frame"]) == bool(tail)


def _spy(monkeypatch) -> list:
    """Record every upload (its stripes), fetch and program call of the
    one-chip route, in order."""
    events: list = []
    upload, fetch = device.upload, device.fetch

    def up(op, x):
        events.append(("up", x.shape[0]))
        return upload(op, x)

    def down(op, x, rows=None):
        events.append(("down", None))
        return fetch(op, x, rows)

    monkeypatch.setattr(device, "upload", up)
    monkeypatch.setattr(device, "fetch", down)
    for name in ("_encode_bitrot", "_encode_bitrot_xla", "_group_stage",
                 "_group_split"):
        def call(*a, _fn=getattr(rs_fused, name), _name=name, **kw):
            events.append((_name, a[-1].shape[0]
                           if _name.startswith("_encode") else None))
            return _fn(*a, **kw)
        monkeypatch.setattr(rs_fused, name, call)
    return events


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_one_block_takes_the_one_stripe_program_as_before(
        pallas, monkeypatch):
    """A body of ONE full block (every body of the 10 MiB cells) makes
    what it made before stripe groups existed: its stripe up as one
    array, ONE call of the one-stripe program, parity then digests down,
    the ``encode-bitrot`` bucket, and the stripe form's counters; no
    group program, stage or split runs."""
    monkeypatch.setattr(device, "use_pallas", lambda: pallas)
    k, m, ss = 8, 4, 96
    codec = Erasure(k, m, k * ss, backend="tpu")
    data = _body(k * ss, 35)
    before = _counters()
    events = _spy(monkeypatch)
    with trace.HTTP_TRACE.subscribe() as sub:
        rows = codec.encode_framed(data, bitrot.HIGHWAYHASH256S)
        spans = list(sub.drain(80, timeout=2.0))
    assert [bytes(r) for r in rows] == \
        shard_files.reference_framed(data, k * ss, k, m)
    program = "_encode_bitrot" if pallas else "_encode_bitrot_xla"
    assert events == [("up", 1), (program, 1), ("down", None),
                      ("down", None)]
    w = rs_fused.staged_width(k, m, ss)
    moved = {key: v - before[key] for key, v in _counters().items()}
    assert moved == {
        "h2d": k * w, "d2h": m * w + (k + m) * 32,
        "real": k + m, "hashed": 128 if pallas else k + m,
        "ops.encode": 1, "ops.hash": 0, "bytes": len(data),
        "programs.stripe": 1, "stripes.stripe": 1,
        "programs.group": 0, "stripes.group": 0}
    legs = [s.get("funcName") or "" for s in spans]
    assert legs.count("encode.launch") == 1
    assert "encode-bitrot.batch" in legs
    assert not [leg for leg in legs if "group" in leg]


# stripe groups: k+m = 12 shards lay 10 stripes into one 128-lane row
GK, GM, GSS = 8, 4, 96
G = 10


def _group_body(nfull: int, seed: int) -> bytes:
    return _body(nfull * GK * GSS, seed)


@pytest.mark.parametrize("nfull", [2, G - 1, G, G + 1, 2 * G + 3])
@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_a_body_of_several_blocks_goes_out_in_stripe_groups(
        pallas, nfull, monkeypatch):
    """nfull full blocks are ceil(nfull / G) programs of the ONE group
    shape: a whole group up as one array and down in two fetches; a
    last partial group of r up as its r stripes, completed and split on
    the device, its r stripes' results down, so the link carries no
    padding either way; the rows are the plain reference's."""
    monkeypatch.setattr(device, "use_pallas", lambda: pallas)
    codec = Erasure(GK, GM, GK * GSS, backend="tpu")
    p = rs_fused.group_plan(GK, GM, GSS)
    assert (p["bs"], p["gs"], p["S"]) == (G, 2, 1)
    data = _group_body(nfull, nfull)
    before = _counters()
    events = _spy(monkeypatch)
    rows = codec.encode_framed(data, bitrot.HIGHWAYHASH256S)
    assert [bytes(r) for r in rows] == \
        shard_files.reference_framed(data, GK * GSS, GK, GM)
    whole, r = divmod(nfull, G)
    program = "_encode_bitrot" if pallas else "_encode_bitrot_xla"
    want = [("up", G), (program, G)] * whole
    if r:
        want += [("up", 1)] * r + [("_group_stage", None), (program, G),
                                   ("_group_split", None)]
    want += [("down", None)] * (2 * whole + 2 * r)
    assert events == want
    w = rs_fused.staged_width(GK, GM, GSS)
    programs = whole + bool(r)
    moved = {key: v - before[key] for key, v in _counters().items()}
    assert moved == {
        "h2d": nfull * GK * w, "d2h": nfull * (GM * w + (GK + GM) * 32),
        "real": nfull * (GK + GM),
        "hashed": programs * (128 if pallas else G * (GK + GM)),
        "ops.encode": 1, "ops.hash": 0, "bytes": len(data),
        "programs.stripe": 0, "stripes.stripe": 0,
        "programs.group": programs, "stripes.group": nfull}


def test_bodies_that_meet_fill_stripe_groups_together(combining):
    """Three callers with bodies of four blocks each meet in the
    ``encode-bitrot-group`` bucket: their twelve stripes fill groups
    together and each caller gets the rows of its own body."""
    codec = batcher.codec_for(GK, GM, GK * GSS, "tpu")
    bodies = [_group_body(4, 40 + i) for i in range(3)]
    codec.encode_framed(bodies[0], bitrot.HIGHWAYHASH256S)   # built
    before, c0 = batcher.GLOBAL.snapshot(), _counters()
    got = _together(3, lambda i: codec.encode_framed(
        bodies[i], bitrot.HIGHWAYHASH256S))
    after, c1 = batcher.GLOBAL.snapshot(), _counters()
    assert after["requests"] - before["requests"] == 3
    assert after["dispatches"] - before["dispatches"] < 3
    assert c1["stripes.group"] - c0["stripes.group"] == 12
    assert c1["programs.group"] - c0["programs.group"] < 3
    assert c1["programs.stripe"] == c0["programs.stripe"]
    for i, body in enumerate(bodies):
        assert [bytes(r) for r in got[i]] == shard_files.reference_framed(
            body, GK * GSS, GK, GM), f"caller {i}"


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
    codec.encode_framed(_body(5332, 3), bitrot.HIGHWAYHASH256S)
    assert rows() == after
    # bodies of several blocks: ONE group program of the width, its
    # stage and split, built by the first; none for other block counts
    codec.encode_framed(_body(2 * 5332, 4), bitrot.HIGHWAYHASH256S)
    grouped = rows()
    assert {name: (v[0] - after.get(name, (0, 0))[0],
                   v[1] - after.get(name, (0, 0))[1])
            for name, v in grouped.items()
            if v != after.get(name, (0, 0)) and v[1]} == {
        "mt_encode_bitrot": (1, 1), "mt_group_stage": (1, 1),
        "mt_group_split": (1, 1)}
    for nfull in (3, 21, 45):
        codec.encode_framed(_body(nfull * 5332, nfull),
                            bitrot.HIGHWAYHASH256S)
    assert rows() == grouped

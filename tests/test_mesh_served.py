"""``server --backend mesh`` as a served deployment: the benchmark's
``mesh4-ec12p4`` configuration (16 drives, one 12+4 set, k sharded 3
per chip over a 1x4 mesh) at a small block size, on the virtual CPU
mesh tests/conftest.py pins (the XLA forms: routes, bytes and counters
are what a tier-1 pass proves, not the chip's kernels).

An ``S3Server`` over ``ErasureSets(backend="mesh")`` takes seeded PUTs
over HTTP; the shard files on the 16 drives must equal, byte for byte,
the plain reference (gf8_ref parity + host HighwayHash frames) AND what
``backend="tpu"`` writes for the same bodies; and the ``mt_tpu_*``
families the benchmark reads must rise by what the dispatches account
for — the mesh route is counted and spanned like the one-chip route.
"""

import threading

import jax
import numpy as np
import pytest

from minio_tpu.admin import metrics as _metrics
from minio_tpu.objectlayer.sets import ErasureSets
from minio_tpu.hashing import highwayhash
from minio_tpu.ops import gf8, gf8_ref, hh_pallas, rs_fused, rs_mesh
from minio_tpu.parallel import batcher
from minio_tpu.parallel import mesh as pmesh
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.writers import close_write_planes

from . import shard_files

K, M, CHIPS = 12, 4, 4
BS = 40000          # not divisible by k: per-block zero padding, as 10 MiB
BUCKET = "meshb"
SIZES = {"tail-only": 25000, "one-block": BS,
         "blocks+tail": 3 * BS + 12345}


def _body(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng([n, seed]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _dirs(root, n=K + M):
    out = []
    for i in range(n):
        d = root / f"d{i:02d}"
        d.mkdir()
        out.append(str(d))
    return out


@pytest.fixture(scope="module")
def mesh_1x4():
    prev = pmesh._ACTIVE
    pmesh.set_active_mesh(pmesh.make_mesh(jax.devices()[:CHIPS], stripe=1))
    cfg = batcher.CONFIG
    saved = (cfg.enable, cfg.window_s, cfg.max_blocks, cfg.queue_depth,
             cfg._loaded)
    cfg.enable, cfg.window_s, cfg.max_blocks, cfg.queue_depth, \
        cfg._loaded = True, 200e-6, 256, 1024, True
    yield
    (cfg.enable, cfg.window_s, cfg.max_blocks, cfg.queue_depth,
     cfg._loaded) = saved
    pmesh.set_active_mesh(prev)


@pytest.fixture(scope="module")
def served(mesh_1x4, tmp_path_factory):
    """(client, layer) of one mesh server over 16 drive directories."""
    layer = ErasureSets.from_dirs(
        _dirs(tmp_path_factory.mktemp("mesh16")), 1, K + M,
        backend="mesh", block_size=BS)
    assert layer.sets[0]._codec.dispatch_devices() == CHIPS
    srv = S3Server(layer, access_key="ck", secret_key="cs")
    srv.start()
    cli = S3Client(srv.endpoint, "ck", "cs")
    cli.make_bucket(BUCKET)
    yield cli, layer
    srv.stop()
    close_write_planes(layer)


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    """The same deployment on the one-chip device route."""
    layer = ErasureSets.from_dirs(
        _dirs(tmp_path_factory.mktemp("tpu16")), 1, K + M,
        backend="tpu", block_size=BS)
    layer.make_bucket(BUCKET)
    yield layer
    close_write_planes(layer)


def _on_disk(layer, key: str) -> dict[int, bytes]:
    """shard index -> framed shard bytes, from every drive that holds
    the object (inline, packed or a part file)."""
    out = {}
    for disk in layer.sets[0].disks:
        fi = disk.read_version(BUCKET, key)
        out[fi.erasure.index - 1] = bytes(fi.inline_data) \
            if fi.inline_data is not None \
            else shard_files.read_shard(disk, BUCKET, key)
    return out


def _counters() -> dict:
    snap = _metrics.GLOBAL.snapshot()

    def c(name, **labels):
        return snap.get((name, tuple(sorted(labels.items()))), 0.0)

    out = {
        "ops": c("mt_tpu_ops_total", op="encode", backend="mesh"),
        "bytes": c("mt_tpu_bytes_total", op="encode", backend="mesh"),
        "h2d": c("mt_tpu_link_bytes_total", op="encode", dir="h2d"),
        "d2h": c("mt_tpu_link_bytes_total", op="encode", dir="d2h"),
        "real": c("mt_tpu_hash_rows_total", kind="real"),
        "hashed": c("mt_tpu_hash_rows_total", kind="hashed"),
        "dispatches": c("mt_codec_batch_dispatches_total",
                        op="encode-bitrot"),
    }
    for (name, labels, _), h in _metrics.GLOBAL.hist_snapshot().items():
        lb = dict(labels)
        if name == "mt_tpu_leg_seconds":
            out[f"{lb['op']}.{lb['leg']}"] = h[-2]          # _count
        elif name == "mt_tpu_kernel_seconds" and lb == {
                "op": "encode", "backend": "mesh"}:
            out["kernel"] = h[-2]
        elif name == "mt_tpu_batch_blocks" and lb == {"op": "encode"}:
            out["batch_blocks"] = h[-1]                     # _sum
    return out


def _delta(before: dict, after: dict) -> dict:
    return {k: after.get(k, 0) - before.get(k, 0) for k in after}


def _stripes(total: int) -> list[tuple[int, int]]:
    """(stripes, shard width) of each sharded dispatch one PUT of
    ``total`` bytes makes alone: its full blocks one stripe per
    dispatch (one program per shard width, whatever the batch), then
    its tail."""
    nfull, tail = divmod(total, BS)
    out = [(1, gf8.shard_size(BS, K))] * nfull
    return out + ([(1, gf8.ceil_frac(tail, K))] if tail else [])


def _accounted(totals: list[int]) -> dict:
    """What the PUTs' dispatches move and hash on a 1x4 mesh in the XLA
    form (no batch or lane padding there): every data byte up once,
    parity and 32-byte digests down, k+m digests asked per stripe, and
    hashed per stripe the 3 data rows of each chip plus the m parity
    rows on EVERY chip after the fan-in."""
    stripes = [s for t in totals for s in _stripes(t)]
    return {
        "ops": len(totals), "kernel": len(totals), "bytes": sum(totals),
        "batch_blocks": sum(-(-t // BS) for t in totals),
        "h2d": sum(b * K * n for b, n in stripes),
        "d2h": sum(b * (M * n + (K + M) * 32) for b, n in stripes),
        "real": sum(b * (K + M) for b, _ in stripes),
        "hashed": sum(b * CHIPS * (K // CHIPS + M) for b, _ in stripes),
    }


@pytest.mark.parametrize("case", list(SIZES))
def test_served_put_equals_reference_and_one_chip(served, one_chip, case):
    cli, layer = served
    body = _body(SIZES[case])
    key = f"obj-{case}"
    cli.put_object(BUCKET, key, body)
    assert cli.get_object(BUCKET, key).body == body
    got = _on_disk(layer, key)
    assert sorted(got) == list(range(K + M))        # all 16 drives
    want = shard_files.reference_framed(body, BS, K, M)
    one_chip.put_object(BUCKET, key, body)
    tpu = _on_disk(one_chip, key)
    for i in range(K + M):
        assert got[i] == want[i], (case, i)
        assert got[i] == tpu[i], (case, i)


@pytest.mark.parametrize("case", list(SIZES))
def test_served_put_is_counted_and_spanned(served, case):
    """One PUT alone: exact deltas, the legs' counts included."""
    cli, _ = served
    total = SIZES[case]
    n_disp = len(_stripes(total))
    before = _counters()
    cli.put_object(BUCKET, f"cnt-{case}", _body(total, 1))
    d = _delta(before, _counters())
    want = _accounted([total])
    assert {k: d[k] for k in want} == want
    # through the batcher: the full blocks together, the tail
    assert d["dispatches"] == (1 if total >= BS else 0) + \
        (1 if total % BS else 0)
    # the body (with its full blocks), the tail block, each dispatch
    assert d["encode.prep"] == 1 + (1 if total % BS else 0) + n_disp
    assert d["encode.upload"] == d["encode.launch"] == n_disp
    assert d["encode.fetch"] == 2 * n_disp          # parity, digests
    assert d["encode.dispatch"] == 1
    assert d["hash.frame"] == 1
    assert d.get("hash.launch", 0) == 0             # no second program


@pytest.mark.parametrize("case", ["one-block", "blocks+tail"])
def test_concurrent_puts_ride_the_batcher(served, case):
    """8 PUTs at once: whatever batches the combiner forms, the files
    are the reference's and the sums are the dispatches' (stripes are
    batch-axis independent and this form pads none)."""
    cli, layer = served
    total = SIZES[case]
    bodies = [_body(total, 10 + i) for i in range(8)]
    errs = []

    def put(i):
        try:
            S3Client(cli.endpoint, "ck", "cs").put_object(
                BUCKET, f"par-{case}-{i}", bodies[i])
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(e)

    before = _counters()
    threads = [threading.Thread(target=put, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    d = _delta(before, _counters())
    want = _accounted([total] * 8)
    assert {k: d[k] for k in want} == want
    assert 1 <= d["dispatches"] <= 8 * len(_stripes(total))
    assert d["encode.upload"] == d["encode.launch"] >= d["dispatches"]
    assert d["encode.fetch"] == 2 * d["encode.upload"]
    assert d["hash.frame"] == 8
    for i, body in enumerate(bodies):
        got = _on_disk(layer, f"par-{case}-{i}")
        want_files = shard_files.reference_framed(body, BS, K, M)
        assert [got[j] for j in range(K + M)] == want_files, i


@pytest.mark.parametrize("engine", ["single", "two-kernel"])
def test_chip_form_counts_its_padding(mesh_1x4, monkeypatch, engine):
    """The forms the chip runs (here through the Pallas interpreter):
    the link counts the PADDED operand and results, and the hashed
    lanes come from the functions that pad — the fused kernel's whole
    (S, 128) row-block per chip, or hh_pallas's 128-row tiles, plus the
    parity rows hashed again on every chip of the shard axis."""
    monkeypatch.setenv("MT_PALLAS", "1")
    monkeypatch.setenv("MT_FUSED_SINGLE",
                       "1" if engine == "single" else "0")
    n, kl = 1000, K // CHIPS
    blocks = np.random.default_rng(5).integers(
        0, 256, (1, K, n), dtype=np.uint8)
    if engine == "single":
        p = rs_fused.plan(1, kl, M, n, hash_parity=False)
        b_pad, n_pad = p["B_pad"], p["n_pad"]
        lanes = rs_fused.hashed_lanes(p)
    else:
        b_pad, n_pad = 4, 1024                  # _GS stripes, 256-lane tile
        lanes = hh_pallas.hashed_rows(b_pad * kl, n)
    lanes += hh_pallas.hashed_rows(b_pad * M, n)
    assert lanes == 256                         # 2 x 128 for 3 + 4 real rows
    before = _counters()
    parity, digs = rs_mesh.encode_with_bitrot(K, M, blocks)
    d = _delta(before, _counters())
    assert np.array_equal(parity[0], gf8_ref.encode_parity(blocks[0], M))
    rows = np.concatenate([blocks[0], parity[0]])
    assert [bytes(x) for x in digs[0]] == \
        [highwayhash.hh256(r.tobytes()) for r in rows]
    assert (d["real"], d["hashed"]) == (K + M, CHIPS * lanes)
    assert d["h2d"] == b_pad * K * n_pad
    assert d["d2h"] == b_pad * (M * n_pad + (K + M) * 32)
    assert d["encode.prep"] == d["encode.upload"] == d["encode.launch"] == 1


@pytest.mark.parametrize("T", [1, 2])
def test_a_batch_goes_out_one_stripe_per_device(mesh_1x4, T):
    """Whatever batch the combiner forms, every dispatch carries one
    stripe per device of the stripe axis: one program per shard width,
    none first met (traced and compiled, its callers parked)
    mid-traffic.  The pieces add up to the batch, bit for bit."""
    prev = pmesh._ACTIVE
    pmesh.set_active_mesh(pmesh.make_mesh(jax.devices()[:CHIPS], stripe=T))
    try:
        blocks = np.random.default_rng(T).integers(
            0, 256, (5, K, 777), dtype=np.uint8)
        before = _counters()
        parity, digs = rs_mesh.encode_with_bitrot(K, M, blocks)
        d = _delta(before, _counters())
    finally:
        pmesh.set_active_mesh(prev)
    assert d["encode.upload"] == d["encode.launch"] == -(-5 // T)
    assert d["real"] == 5 * (K + M)
    for b in range(5):
        want = gf8_ref.encode_parity(blocks[b], M)
        assert np.array_equal(parity[b], want)
        assert [bytes(x) for x in digs[b]] == [
            highwayhash.hh256(r.tobytes())
            for r in np.concatenate([blocks[b], want])]

"""Cross-request batching codec service (parallel/batcher.py): batched
outputs are pinned bit-identical to the serial reference across ragged
geometry mixes and padding boundaries; concurrent waiters coalesce into
fewer dispatches; callers that die mid-queue cancel cleanly (no leaked
``mt-codec-*`` threads); the ``codec`` kvconfig knobs reload live.
"""

import threading
import time

import numpy as np
import pytest

from minio_tpu.admin.metrics import GLOBAL as METRICS
from minio_tpu.ops.codec import Erasure
from minio_tpu.parallel import batcher

RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _restore_config():
    """Every test runs against the process-global CONFIG/GLOBAL: pin a
    known state going in and restore the defaults going out so test
    order never matters."""
    cfg = batcher.CONFIG
    saved = (cfg.enable, cfg.window_s, cfg.max_blocks, cfg.queue_depth,
             cfg._loaded)
    cfg.enable = True
    cfg.window_s = 200e-6
    cfg.max_blocks = 256
    cfg.queue_depth = 1024
    cfg._loaded = True
    yield
    (cfg.enable, cfg.window_s, cfg.max_blocks, cfg.queue_depth,
     cfg._loaded) = saved
    assert not batcher.GLOBAL._buckets, "batcher bucket leaked"


def _body(size, seed):
    return RNG.__class__(np.random.PCG64(seed)).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _serial(codec_args, data):
    """The reference output: the same geometry with batching OFF."""
    cfg = batcher.CONFIG
    prev = cfg.enable
    cfg.enable = False
    try:
        return Erasure(*codec_args).encode_object(data)
    finally:
        cfg.enable = prev


# -- bit-identity -----------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_ragged_geometry_mix_bit_identical(backend):
    """Concurrent encodes across a ragged geometry mix — every
    (k, m, blockSize) lands in its own bucket, all coalescing at once —
    stay bit-identical to the serial per-request reference."""
    geos = [(4, 2, 64 * 1024), (6, 3, 128 * 1024), (8, 4, 32 * 1024),
            (2, 2, 4096)]
    jobs = []
    for gi, geo in enumerate(geos):
        bs = geo[2]
        for size in (1, bs - 1, bs, 3 * bs + 17):
            jobs.append((geo, _body(size, 100 * gi + size % 97)))
    want = [_serial((k, m, bs, backend), data)
            for (k, m, bs), data in jobs]
    batcher.CONFIG.window_s = 0.02          # wide window: force overlap
    got = [None] * len(jobs)
    start = threading.Barrier(len(jobs))

    def run(i):
        (k, m, bs), data = jobs[i]
        start.wait()
        got[i] = Erasure(k, m, bs, backend).encode_object(data)

    ths = [threading.Thread(target=run, args=(i,),
                            name=f"mt-codec-rg{i}")
           for i in range(len(jobs))]
    for t in ths:
        t.start()
    for t in ths:
        t.join(30)
    for i, (w, g) in enumerate(zip(want, got)):
        assert g is not None, jobs[i][0]
        for a, b in zip(w, g):
            assert np.array_equal(a, b), jobs[i][0]


def test_padding_boundaries_bit_identical():
    """1 block, exactly max_batch_blocks, and max+1 (the dispatch-split
    boundary) all produce the serial bytes."""
    batcher.CONFIG.max_blocks = 4
    k, m, bs = 4, 2, 4096
    for nblocks in (1, 4, 5):
        data = _body(nblocks * bs, 40 + nblocks)
        want = _serial((k, m, bs, "tpu"), data)
        got = Erasure(k, m, bs, "tpu").encode_object(data)
        for a, b in zip(want, got):
            assert np.array_equal(a, b), nblocks


@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_decode_and_reconstruct_bit_identical(backend):
    """The decode path (survivor solve + batched matmul) and the public
    apply_matrix reconstruct path match the serial reference."""
    k, m, bs = 4, 2, 64 * 1024
    data = _body(2 * bs + 999, 9)
    full = _serial((k, m, bs, backend), data)
    degraded = [s.copy() for s in full]
    degraded[0] = None
    degraded[5] = np.zeros(0, np.uint8)
    cfg = batcher.CONFIG
    cfg.enable = False
    ref = Erasure(k, m, bs, backend).decode_data_and_parity_blocks(
        [None if s is None or len(s) == 0 else s.copy()
         for s in degraded])
    cfg.enable = True
    out = Erasure(k, m, bs, backend).decode_data_and_parity_blocks(
        [None if s is None or len(s) == 0 else s.copy()
         for s in degraded])
    for i in range(k + m):
        assert np.array_equal(out[i], ref[i]), i
        assert np.array_equal(out[i], full[i]), i
    # decode_data_blocks (the GET path's early-outs included)
    lost = [s.copy() for s in full]
    lost[1] = None
    out2 = Erasure(k, m, bs, backend).decode_data_blocks(lost)
    for i in range(k):
        assert np.array_equal(out2[i], full[i]), i


# -- coalescing -------------------------------------------------------------

def test_concurrent_waiters_coalesce_and_count():
    """N concurrent same-geometry encodes fuse into fewer dispatches
    than requests; occupancy/blocks land in the mt_codec_batch_*
    counters."""
    batcher.CONFIG.window_s = 0.05
    k, m, bs = 4, 2, 4096
    body = _body(8 * bs, 3)
    want = _serial((k, m, bs, "tpu"), body)
    c = Erasure(k, m, bs, "tpu")
    n = 8
    res = [None] * n
    start = threading.Barrier(n)

    def run(i):
        start.wait()
        res[i] = c.encode_object(body)

    before = batcher.GLOBAL.snapshot()
    d0 = METRICS.snapshot().get(
        ("mt_codec_batch_dispatches_total", (("op", "encode"),)), 0.0)
    ths = [threading.Thread(target=run, args=(i,),
                            name=f"mt-codec-cw{i}") for i in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(30)
    after = batcher.GLOBAL.snapshot()
    for r in res:
        for a, b in zip(want, r):
            assert np.array_equal(a, b)
    served = after["requests"] - before["requests"]
    fused = after["dispatches"] - before["dispatches"]
    assert served == n
    assert fused < served, (fused, served)
    d1 = METRICS.snapshot().get(
        ("mt_codec_batch_dispatches_total", (("op", "encode"),)), 0.0)
    assert d1 - d0 == fused


def test_single_caller_takes_serial_fallback():
    """A window that finds one caller dispatches exactly the caller's
    own stripes (occupancy 1) — the strict serial reference path."""
    before = batcher.GLOBAL.snapshot()
    k, m, bs = 4, 2, 4096
    body = _body(3 * bs, 5)
    want = _serial((k, m, bs, "tpu"), body)
    got = Erasure(k, m, bs, "tpu").encode_object(body)
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    after = batcher.GLOBAL.snapshot()
    delta_d = after["dispatches"] - before["dispatches"]
    delta_r = after["requests"] - before["requests"]
    assert delta_d == delta_r  # nothing coalesced: every dispatch solo


def test_queue_bound_sheds_to_serial():
    """Arrivals past codec.queue_depth blocks take the serial path
    immediately (bounded queue, correct bytes, counted)."""
    cfg = batcher.CONFIG
    cfg.window_s = 0.05
    cfg.max_blocks = 2
    cfg.queue_depth = 2
    k, m, bs = 4, 2, 4096
    body = _body(bs, 11)                    # one block: B=1 queues
    want = _serial((k, m, bs, "tpu"), body)
    c = Erasure(k, m, bs, "tpu")
    n = 6
    res = [None] * n
    start = threading.Barrier(n)

    def run(i):
        start.wait()
        res[i] = c.encode_object(body)

    before = batcher.GLOBAL.snapshot()
    ths = [threading.Thread(target=run, args=(i,),
                            name=f"mt-codec-sh{i}") for i in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(30)
    for r in res:
        for a, b in zip(want, r):
            assert np.array_equal(a, b)
    after = batcher.GLOBAL.snapshot()
    assert after["shed"] >= before["shed"]  # sheds are load-dependent;
    # the hard contract is correctness + the bound, asserted above


# -- cancellation -----------------------------------------------------------

def test_caller_death_mid_queue_cancels_cleanly():
    """A waiter whose caller gives up mid-queue cancels out, computes
    its own result on the serial path, and the combiner never touches
    it; nothing mt-codec-shaped survives."""
    cfg = batcher.CONFIG
    cfg.window_s = 1.5                      # long window: the combiner
    k, m, bs = 5, 2, 10240                  # parks followers behind it
    body = _body(2 * bs, 21)
    want = _serial((k, m, bs, "tpu"), body)
    leader_out = [None]
    leading = threading.Event()

    def lead():
        leading.set()
        leader_out[0] = Erasure(k, m, bs, "tpu").encode_object(body)

    tl = threading.Thread(target=lead, name="mt-codec-lead",
                          daemon=True)
    tl.start()
    assert leading.wait(10)
    time.sleep(0.05)                        # leader is window-waiting
    # the doomed follower: enqueues behind the combiner's open window,
    # then its deadline expires — it must cancel OUT of the queue and
    # serve itself serially, well before the window closes
    caller = Erasure(k, m, bs, "tpu")
    rows = np.asarray(caller.matrix)[k:]
    ssize = caller.shard_size()
    blocks = np.frombuffer(body, np.uint8).reshape(2, k, ssize)
    before = batcher.GLOBAL.snapshot()
    t0 = time.monotonic()
    out = batcher.GLOBAL.apply(caller, "encode", rows, blocks,
                               timeout=0.2)
    waited = time.monotonic() - t0
    after = batcher.GLOBAL.snapshot()
    assert after["cancelled"] >= before["cancelled"] + 1
    assert waited < 1.0, waited             # did not ride out the window
    for j in range(m):
        assert np.array_equal(out[:, j].reshape(-1), want[k + j])
    tl.join(20)
    assert not tl.is_alive()
    for a, b in zip(want, leader_out[0]):
        assert np.array_equal(a, b)
    # the mt-codec-* naming discipline: no batcher-related thread
    # outlives its caller (the batcher itself owns none)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and any(
            t.name.startswith("mt-codec") for t in threading.enumerate()):
        time.sleep(0.02)
    leftover = [t.name for t in threading.enumerate()
                if t.name.startswith("mt-codec")]
    assert not leftover, leftover


def test_numpy_backend_never_routes_through_batcher():
    """The host path has no dispatch-launch cost to amortize, and its
    GIL-releasing native matmuls already run in parallel across caller
    threads — batching would serialize them for nothing, so the numpy
    backend must bypass the batcher entirely."""
    before = batcher.GLOBAL.snapshot()
    c = Erasure(4, 2, 4096, "numpy")
    body = _body(3 * 4096, 2)
    c.encode_object(body)
    full = c.encode_object(body)
    lost = [s.copy() for s in full]
    lost[0] = None
    c.decode_data_and_parity_blocks(lost)
    assert batcher.GLOBAL.snapshot() == before


def test_mesh_fused_framed_path_rides_batcher_bit_identical():
    """The production mesh PUT path (encode_object_framed_fused:
    fused parity + bitrot digests) coalesces through the batcher's
    tuple-result buckets and stays bit-identical to the unbatched
    fused pipeline."""
    from minio_tpu.ops import rs_mesh
    from minio_tpu.parallel import mesh as pmesh
    prev = pmesh._ACTIVE
    pmesh.set_active_mesh(pmesh.make_mesh(stripe=2))
    cfg = batcher.CONFIG
    try:
        data = _body(3 * 65536 + 17, 31)
        cfg.enable = False
        want = rs_mesh.encode_object_framed_fused(4, 2, 65536, data)
        cfg.enable = True
        s0 = batcher.GLOBAL.snapshot()
        got = rs_mesh.encode_object_framed_fused(4, 2, 65536, data)
        s1 = batcher.GLOBAL.snapshot()
        assert s1["dispatches"] > s0["dispatches"]   # it rode the queue
        assert np.array_equal(want, got)
    finally:
        pmesh.set_active_mesh(prev)


# -- shared geometry registry ----------------------------------------------

def test_callers_share_one_codec_per_geometry():
    a = batcher.codec_for(4, 2, 64 * 1024, "numpy")
    b = batcher.codec_for(4, 2, 64 * 1024, "numpy")
    assert a is b
    assert batcher.codec_for(4, 2, 32 * 1024, "numpy") is not a
    # 'auto' is resolved before keying: one instance per geometry under
    # the name it resolves to, not a second one under 'auto'
    auto = batcher.codec_for(4, 2, 64 * 1024, "auto")
    assert auto is batcher.codec_for(4, 2, 64 * 1024, auto.backend)


# -- live reload ------------------------------------------------------------

def test_codec_config_env_and_load(monkeypatch):
    monkeypatch.setenv("MT_CODEC_BATCH_WINDOW_US", "5000")
    monkeypatch.setenv("MT_CODEC_MAX_BATCH_BLOCKS", "32")
    monkeypatch.setenv("MT_CODEC_QUEUE_DEPTH", "64")
    monkeypatch.setenv("MT_CODEC_ENABLE", "off")
    cfg = batcher.CodecConfig()
    assert cfg.on() is False
    assert cfg.window_s == pytest.approx(0.005)
    assert cfg.max_blocks == 32
    assert cfg.queue_depth == 64


def test_admin_set_config_kv_reloads_window(tmp_path):
    """PUT config/codec/batch_window_us through the real admin route
    retunes the live process-wide batcher."""
    from minio_tpu.admin.client import AdminClient
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl_storage import XLStorage
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="ck", secret_key="cs")
    srv.start()
    try:
        adm = AdminClient(srv.endpoint, "ck", "cs")
        adm.set_config_kv("codec", "batch_window_us", "4321")
        assert batcher.CONFIG.window_s == pytest.approx(4321e-6)
        adm.set_config_kv("codec", "enable", "off")
        assert batcher.CONFIG.on() is False
        adm.set_config_kv("codec", "enable", "on")
        assert batcher.CONFIG.on() is True
    finally:
        srv.stop()
        from minio_tpu.storage.writers import close_write_planes
        close_write_planes(layer)

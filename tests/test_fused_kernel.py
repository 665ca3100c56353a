"""Single-kernel fused encode+HH256 (ops/rs_fused.py) and the device
multi-buffer MD5 (hashing/md5_device.py): bit-identity is the whole
contract.

* the fused kernel's parity must match the GF(2^8) reference and its
  digests the host HighwayHash-256, across ragged geometries (the
  BASELINE-config k/m matrix), tail blocks (widths not multiples of
  the 32-byte packet or the lane tile), batch padding boundaries, and
  the data-only ``hash_parity=False`` mesh form;
* the mesh data plane's single-kernel path must agree with the proven
  two-kernel pipeline byte for byte, and the production framed path
  must still ride the batcher's ``encode-bitrot`` bucket;
* the device MD5 must agree with hashlib at the md5fast boundary
  lengths (0/1/55/56/63/64/65/4MiB±1) and any update split, through
  the ``md5`` combining bucket included, and the backend ladder must
  degrade with a NAMED reason when no device is usable.
"""

import hashlib
import os
import threading

import numpy as np
import pytest

from minio_tpu.hashing import md5_device, md5fast
from minio_tpu.hashing.highwayhash import MAGIC_KEY, HighwayHash256
from minio_tpu.ops import gf8, gf8_ref, rs_fused
from minio_tpu.parallel import batcher

RNG = np.random.default_rng(12)


def _hh(row) -> bytes:
    h = HighwayHash256(MAGIC_KEY)
    h.update(bytes(row))
    return h.digest()


def _check(blocks, par, dig, k, m):
    B = blocks.shape[0]
    ref_par = np.stack([gf8_ref.encode_parity(blocks[b], m)
                        for b in range(B)])
    assert np.array_equal(np.asarray(par), ref_par)
    dig = np.asarray(dig)
    for b in range(B):
        for s in range(k):
            assert dig[b, s].tobytes() == _hh(blocks[b, s]), (b, s)
        for s in range(m):
            assert dig[b, k + s].tobytes() == _hh(ref_par[b, s]), (b, s)


class TestFusedKernel:
    # the BASELINE-config k/m matrix: config 1 (4+2), config 2 (8+4),
    # the 12+4 headline, plus odd non-dividing geometries
    @pytest.mark.parametrize("k,m", [
        (4, 2), (3, 2), (5, 1),
        # the wide configs compile ~15s each on CPU interpret mode;
        # the slow tier keeps them, tier-1 keeps the 4+2 baseline, the
        # odd non-dividing geometry, and the m=1 floor that catch
        # tiling bugs — 6+3 re-proves the dividing case 4+2 already
        # covers (~9s of compile)
        pytest.param(6, 3, marks=pytest.mark.slow),
        pytest.param(8, 4, marks=pytest.mark.slow),
        pytest.param(12, 4, marks=pytest.mark.slow),
    ])
    def test_bit_identity_ragged_geometry(self, k, m):
        blocks = RNG.integers(0, 256, (3, k, 997), dtype=np.uint8)
        par, dig = rs_fused.encode_with_bitrot_fused(k, m, blocks)
        _check(blocks, par, dig, k, m)

    @pytest.mark.parametrize("n", [31, 32, 33, 256, 2048, 2079, 2080])
    def test_tail_blocks_across_lane_tiles(self, n):
        """Widths below one packet, exactly on packet/lane-tile edges,
        and crossing the 2048-byte tile — the digest must cover
        exactly n bytes, never the kernel's padding."""
        k, m = 4, 2
        blocks = RNG.integers(0, 256, (2, k, n), dtype=np.uint8)
        par, dig = rs_fused.encode_with_bitrot_fused(k, m, blocks)
        _check(blocks, par, dig, k, m)

    @pytest.mark.parametrize("B", [
        1, 2,
        # B=5/9 re-prove the same pad-to-batch rule at larger sizes
        # (~10s each); slow tier keeps them
        pytest.param(5, marks=pytest.mark.slow),
        pytest.param(9, marks=pytest.mark.slow),
    ])
    def test_batch_padding_boundaries(self, B):
        blocks = RNG.integers(0, 256, (B, 6, 300), dtype=np.uint8)
        par, dig = rs_fused.encode_with_bitrot_fused(6, 2, blocks)
        _check(blocks, par, dig, 6, 2)

    def test_hash_parity_false_hashes_data_only(self):
        """The mesh form: per-device parity is partial before the ring
        XOR, so the kernel hashes only the data lanes."""
        k, m, B, n = 6, 2, 4, 500
        blocks = RNG.integers(0, 256, (B, k, n), dtype=np.uint8)
        rows = np.asarray(gf8.rs_matrix(k, k + m))[k:]
        par, dig = rs_fused.encode_hash_device(rows, blocks,
                                               hash_parity=False)
        par, dig = np.asarray(par), np.asarray(dig)
        assert dig.shape == (B, k, 32)
        ref_par = np.stack([gf8_ref.encode_parity(blocks[b], m)
                            for b in range(B)])
        assert np.array_equal(par, ref_par)
        for b in range(B):
            for s in range(k):
                assert dig[b, s].tobytes() == _hh(blocks[b, s])

    def test_plan_rejects_oversized_stripe(self):
        with pytest.raises(ValueError):
            rs_fused.plan(4, 1000, 100, 4096)

    @pytest.mark.slow    # ~108s of interpret-mode mesh compiles;
    # test_mesh.py keeps the fast-tier mesh data-plane coverage and
    # the slow tier still runs this full single-vs-two-kernel proof
    def test_mesh_single_kernel_matches_two_kernel(self, monkeypatch):
        """The mesh data plane's single-kernel path vs the proven
        two-kernel pipeline: byte-identical parity AND digests on a
        sharded mesh (partial-parity ring form) and a stripe-only
        mesh (full in-kernel hash form)."""
        from minio_tpu.ops import rs_mesh
        from minio_tpu.parallel import mesh as pmesh
        monkeypatch.setenv("MT_PALLAS", "1")
        prev = pmesh._ACTIVE
        try:
            for stripe, shard in ((2, 4), (8, 1)):
                pmesh.set_active_mesh(
                    pmesh.make_mesh(stripe=stripe, shard=shard))
                blocks = RNG.integers(0, 256, (3, 12, 1000),
                                      dtype=np.uint8)
                monkeypatch.setenv("MT_FUSED_SINGLE", "0")
                par0, dig0 = rs_mesh.encode_with_bitrot(12, 4, blocks)
                monkeypatch.setenv("MT_FUSED_SINGLE", "1")
                # no fallback between the two engines (a failing
                # single kernel raises), so this really ran it
                par1, dig1 = rs_mesh.encode_with_bitrot(12, 4, blocks)
                assert np.array_equal(par0, par1), (stripe, shard)
                assert np.array_equal(dig0, dig1), (stripe, shard)
                _check(blocks, par1, dig1, 12, 4)
        finally:
            pmesh.set_active_mesh(prev)

    @pytest.mark.slow    # ~77s mesh compile; the batcher-engagement
    # contract stays covered fast-tier by test_batcher.py, and the
    # slow tier runs this full framed production path
    def test_framed_fused_rides_encode_bitrot_bucket(self, monkeypatch):
        """The production mesh PUT path through the batcher's
        ``encode-bitrot`` bucket, single-kernel engine on: coalesced
        AND bit-identical to the unbatched unfused reference."""
        from minio_tpu.ops import rs_mesh
        from minio_tpu.parallel import mesh as pmesh
        monkeypatch.setenv("MT_PALLAS", "1")
        prev = pmesh._ACTIVE
        cfg = batcher.CONFIG
        saved = (cfg.enable, cfg._loaded)
        pmesh.set_active_mesh(pmesh.make_mesh(stripe=2))
        try:
            cfg._loaded = True
            data = bytes(RNG.integers(0, 256, 3 * 65536 + 17,
                                      dtype=np.uint8))
            monkeypatch.setenv("MT_FUSED_SINGLE", "0")
            cfg.enable = False
            want = rs_mesh.encode_object_framed_fused(4, 2, 65536,
                                                      data)
            monkeypatch.setenv("MT_FUSED_SINGLE", "1")
            cfg.enable = True
            s0 = batcher.GLOBAL.snapshot()
            got = rs_mesh.encode_object_framed_fused(4, 2, 65536,
                                                     data)
            s1 = batcher.GLOBAL.snapshot()
            assert s1["dispatches"] > s0["dispatches"]
            assert np.array_equal(want, got)
        finally:
            (cfg.enable, cfg._loaded) = saved
            pmesh.set_active_mesh(prev)


# -- device MD5 conformance -------------------------------------------------

pytestmark_device = pytest.mark.skipif(
    not md5_device.available(),
    reason=md5_device.unavailable_reason() or "device md5 available")

_4MIB = 4 * (1 << 20)
BOUNDARY_LENGTHS = [0, 1, 55, 56, 63, 64, 65,
                    _4MIB - 1, _4MIB, _4MIB + 1]


def _direct(h, words):
    """Bucket-free dispatch: the raw batched compress."""
    return md5_device.advance(h[None], words[None],
                              np.asarray([words.shape[0]]))[0]


@pytestmark_device
class TestDeviceMD5Conformance:
    @pytest.mark.parametrize("n", BOUNDARY_LENGTHS)
    def test_oneshot_matches_hashlib(self, n):
        data = os.urandom(n)
        h = md5_device.MD5Device(dispatch=_direct)
        h.update(data)
        assert h.hexdigest() == hashlib.md5(data).hexdigest()

    @pytest.mark.parametrize("split", [1, 63, 64, 65, 4096])
    def test_split_updates_match(self, split):
        data = os.urandom(3 * 4096 + 7)
        h = md5_device.MD5Device(dispatch=_direct)
        for off in range(0, len(data), split):
            h.update(data[off:off + split])
        assert h.hexdigest() == hashlib.md5(data).hexdigest()

    def test_digest_keeps_stream_usable_and_copy_forks(self):
        h = md5_device.MD5Device(b"abc", dispatch=_direct)
        assert h.hexdigest() == hashlib.md5(b"abc").hexdigest()
        h.update(b"def")
        c = h.copy()
        c.update(b"x")
        h.update(b"y")
        assert c.hexdigest() == hashlib.md5(b"abcdefx").hexdigest()
        assert h.hexdigest() == hashlib.md5(b"abcdefy").hexdigest()

    def test_ragged_batch_through_advance(self):
        """One dispatch, lanes advancing by DIFFERENT block counts —
        the masked-lane contract."""
        bufs = [os.urandom(64 * nb) for nb in (5, 2, 9, 1)]
        nb_max = 9
        states = np.tile(np.asarray(md5_device._INIT, np.uint32),
                         (len(bufs), 1))
        words = np.zeros((len(bufs), nb_max, 16), np.uint32)
        for i, b in enumerate(bufs):
            words[i, :len(b) // 64] = np.frombuffer(
                b, "<u4").reshape(-1, 16)
        out = md5_device.advance(
            states, words,
            np.asarray([len(b) // 64 for b in bufs], np.int32))
        for i, b in enumerate(bufs):
            h = md5_device.MD5Device(dispatch=_direct)
            h._h = [int(x) for x in out[i]]
            h._n = len(b)
            assert h.hexdigest() == hashlib.md5(b).hexdigest(), i

    def test_concurrent_streams_coalesce_through_md5_bucket(self):
        """Concurrent MD5Device streams through the production ``md5``
        bucket: digests bit-identical, requests coalesced into fewer
        dispatches, and the bucket drains to idle."""
        datas = [os.urandom(200_000 + 13 * i) for i in range(6)]
        outs: list = [None] * len(datas)

        def run(i):
            h = md5_device.MD5Device()       # default: MD5_GLOBAL
            mv = memoryview(datas[i])
            for off in range(0, len(mv), 65536):
                h.update(mv[off:off + 65536])
            outs[i] = h.hexdigest()

        s0 = batcher.MD5_GLOBAL.snapshot()
        ts = [threading.Thread(target=run, args=(i,), daemon=True,
                               name=f"mt-md5dev-{i}")
              for i in range(len(datas))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s1 = batcher.MD5_GLOBAL.snapshot()
        for i, d in enumerate(datas):
            assert outs[i] == hashlib.md5(d).hexdigest(), i
        assert s1["requests"] - s0["requests"] >= len(datas)
        assert s1["dispatches"] > s0["dispatches"]
        assert batcher.MD5_GLOBAL.idle()

    def test_md5_factory_device_backend(self):
        md5fast.set_backend("device")
        try:
            h = md5fast.md5(b"hello")
            assert isinstance(h, md5_device.MD5Device)
            assert h.hexdigest() == hashlib.md5(b"hello").hexdigest()
        finally:
            md5fast.set_backend("auto")


class TestBackendLadder:
    def test_unavailable_reason_is_named(self, monkeypatch):
        """No usable device must degrade with a NAMED reason (the
        skip/telemetry contract), never a bare False."""
        monkeypatch.setattr(md5_device, "_AVAIL", False)
        monkeypatch.setattr(md5_device, "_REASON",
                            "device MD5 unavailable: RuntimeError: "
                            "jax reports zero devices")
        assert not md5_device.available()
        assert "device MD5 unavailable" in \
            md5_device.unavailable_reason()

    def test_device_backend_falls_back_and_counts(self, monkeypatch):
        """pipeline.md5_backend=device with no device lands on the
        next rung and bumps mt_md5_device_fallback_total."""
        from minio_tpu.admin.metrics import GLOBAL as mtr
        monkeypatch.setattr(md5_device, "_AVAIL", False)
        monkeypatch.setattr(md5_device, "_REASON", "device MD5 "
                            "unavailable: forced by test")
        key = ("mt_md5_device_fallback_total", ())
        md5fast.set_backend("device")
        try:
            before = mtr.snapshot().get(key, 0)
            h = md5fast.md5(b"xyz")
            assert not isinstance(h, md5_device.MD5Device)
            assert h.hexdigest() == hashlib.md5(b"xyz").hexdigest()
            assert mtr.snapshot().get(key, 0) == before + 1
        finally:
            md5fast.set_backend("auto")

    def test_mt_md5_hashlib_outranks_knob(self, monkeypatch):
        monkeypatch.setenv("MT_MD5", "hashlib")
        md5fast.set_backend("device")
        try:
            h = md5fast.md5(b"k")
            assert h.__class__.__module__ == "_hashlib" or \
                not isinstance(h, (md5fast.MD5Fast,
                                   md5_device.MD5Device))
        finally:
            md5fast.set_backend("auto")

    def test_auto_choice_is_cached_and_valid(self):
        md5fast.set_backend("auto")
        choice = md5fast._resolve_backend()
        assert choice in ("device", "native", "hashlib")
        assert md5fast._resolve_backend() == choice

    def test_live_reload_changes_backend(self):
        """reload_pipeline_config -> set_backend: the knob lands on a
        live layer (the SetConfigKV path)."""
        from minio_tpu.utils.kvconfig import Config
        cfg = Config()
        cfg.set("pipeline", "md5_backend", "hashlib")
        try:
            md5fast.set_backend(cfg.get("pipeline", "md5_backend"))
            assert md5fast._resolve_backend() == "hashlib"
        finally:
            md5fast.set_backend("auto")

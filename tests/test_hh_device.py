"""Device HighwayHash-256 conformance (minio_tpu/ops/hh_kernels.py)
against the native/reference implementation (cmd/bitrot.go bit-identical
requirement).
"""

import numpy as np
import pytest

from minio_tpu.hashing import highwayhash as hh
from minio_tpu.ops import hh_kernels as hk


@pytest.mark.parametrize("n", [
    # tier-1 keeps the boundary representatives: 1 (minimum), 32/33
    # (the 32-byte packet edge), 87424 (multi-tile production size);
    # the interior sizes re-walk the same padding rule (~5-7s each)
    # and ride the slow tier
    1, 32, 33, 87424,
    pytest.param(17, marks=pytest.mark.slow),
    pytest.param(31, marks=pytest.mark.slow),
    pytest.param(64, marks=pytest.mark.slow),
    pytest.param(96, marks=pytest.mark.slow),
    pytest.param(1024, marks=pytest.mark.slow),
    pytest.param(4096, marks=pytest.mark.slow),
    pytest.param(87382, marks=pytest.mark.slow),
])
def test_batch_matches_reference(n):
    rng = np.random.default_rng(n)
    blocks = rng.integers(0, 256, (7, n), dtype=np.uint8)
    got = np.asarray(hk.hh256_batch(blocks))
    for i in range(blocks.shape[0]):
        want = np.frombuffer(hh.hh256(blocks[i].tobytes()), np.uint8)
        assert np.array_equal(got[i], want), f"block {i} size {n}"


def test_custom_key():
    key = bytes(range(32))
    blocks = np.arange(3 * 128, dtype=np.uint8).reshape(3, 128)
    got = np.asarray(hk.hh256_batch(blocks, key=key))
    for i in range(3):
        want = np.frombuffer(hh.hh256(blocks[i].tobytes(), key=key),
                             np.uint8)
        assert np.array_equal(got[i], want)


def test_single_block_and_identical_blocks():
    b = np.full((4, 320), 0xAB, dtype=np.uint8)
    got = np.asarray(hk.hh256_batch(b))
    assert all(np.array_equal(got[0], got[i]) for i in range(4))
    assert np.array_equal(
        got[0], np.frombuffer(hh.hh256(b[0].tobytes()), np.uint8))


def test_streaming_encode_batch_device_matches_host():
    """The fused stripe-framing path must produce byte-identical shard
    files to the host C path (shard sizes are NOT 32-aligned)."""
    from minio_tpu.hashing import bitrot
    from minio_tpu.ops import codec
    rng = np.random.default_rng(99)
    shard_size = 1387                  # deliberately ragged
    shards = [rng.integers(0, 256, 4500, dtype=np.uint8).tobytes()
              for _ in range(6)]
    host = [bitrot.streaming_encode(s, shard_size) for s in shards]
    dev = codec._streaming_encode_batch_device(shards, shard_size)
    assert dev == host


def test_zero_length_blocks():
    got = np.asarray(hk.hh256_batch(np.zeros((2, 0), dtype=np.uint8)))
    want = np.frombuffer(hh.hh256(b""), np.uint8)
    assert np.array_equal(got[0], want)
    assert np.array_equal(got[1], want)


def _host_digests(blocks):
    return np.stack([np.frombuffer(hh.hh256(row.tobytes()), np.uint8)
                     for row in blocks])


@pytest.mark.parametrize("B,n", [
    # tier-1 keeps the single-packet floor; the multi-chunk ragged
    # shapes ride the slow tier (~7-9s each) because the multi-chunk
    # grid-carry test below stays fast-tier and owns that coverage
    (1, 32),
    pytest.param(5, 1000, marks=pytest.mark.slow),
    pytest.param(2, 96, marks=pytest.mark.slow),
    pytest.param(3, 87, marks=pytest.mark.slow),
])
def test_pallas_kernel_matches_reference(B, n):
    """The single-kernel pallas formulation (ops/hh_pallas.py) must be
    bit-identical to the host C HighwayHash-256; on CPU it runs in the
    pallas interpreter (same program, no Mosaic)."""
    from minio_tpu.ops import hh_pallas
    rng = np.random.default_rng(17)
    blocks = rng.integers(0, 256, (B, n), dtype=np.uint8)
    got = np.asarray(hh_pallas.hh256_batch(blocks))
    assert np.array_equal(got, _host_digests(blocks))


def test_pallas_kernel_multi_chunk_grid_carry():
    """Production shapes span MANY packet chunks (ssize ~87 KiB -> ~2732
    packets vs _PC=128): the VMEM state carried across the packet-chunk
    grid dimension, S>1 shard tiling, and the masked tail chunk must all
    agree with the reference — a bug there corrupts every stored shard's
    digests.  B=256 -> S=2 tiles; n=8808 -> 275 packets -> 3 chunks with
    19 valid packets in the last, plus an 8-byte remainder."""
    from minio_tpu.ops import hh_pallas
    rng = np.random.default_rng(23)
    B, n = 256, 8808
    blocks = rng.integers(0, 256, (B, n), dtype=np.uint8)
    got = np.asarray(hh_pallas.hh256_batch(blocks))
    idx = [0, 1, 127, 128, 255]          # spot-check across both tiles
    for i in idx:
        want = np.frombuffer(hh.hh256(blocks[i].tobytes()), np.uint8)
        assert np.array_equal(got[i], want), i


def _compiles_by_function():
    from minio_tpu.ops import device
    return {name: row["compiles"] for name, row in
            device.compile_stats()["by_function"].items()}


def _new_compiles(before):
    after = _compiles_by_function()
    return {name: n - before.get(name, 0) for name, n in after.items()
            if n != before.get(name, 0)}


@pytest.mark.parametrize("B,n", [
    # shapes no other test of this file hashes: the first call must be
    # the one that compiles
    (8, 171),       # rem 11: the three-byte remainder packet
    (8, 160),       # rem 0: no remainder update in the program
    (5, 95),        # B not a multiple of 8; rem 31 (the `rem & 16` form)
    (3, 2101),      # P = 65 > _PC_NAT: two packet chunks, one valid
                    # packet in the second
], ids=["rem", "aligned", "ragged-batch", "multi-chunk"])
def test_pallas_batch_is_one_program_per_shape(B, n):
    """``hh_pallas.hh256_batch`` is ONE compiled program per (B, n) —
    ``mt_hh256_batch``: slice, pad, kernel, limb reassembly, remainder
    and finalization dispatched once, and nothing under a jnp
    primitive's name (op by op they are ~840 dispatches per call)."""
    from minio_tpu.ops import hh_pallas
    rng = np.random.default_rng(B * 100003 + n)
    blocks = rng.integers(0, 256, (B, n), dtype=np.uint8)
    before = _compiles_by_function()
    got = np.asarray(hh_pallas.hh256_batch(blocks))
    assert _new_compiles(before) == {"mt_hh256_batch": 1}
    before = _compiles_by_function()
    again = np.asarray(hh_pallas.hh256_batch(blocks))
    assert _new_compiles(before) == {}
    want = _host_digests(blocks)
    assert np.array_equal(got, want)
    assert np.array_equal(again, want)


def test_pallas_batch_under_an_outer_trace():
    """rs_mesh calls ``hh256_batch`` inside ``jit(shard_map(...))``: under
    an outer trace it inlines and gives the direct call's digests."""
    import jax
    from minio_tpu.ops import hh_pallas
    rng = np.random.default_rng(29)
    # a shape the test above has compiled: only the outer program is new
    blocks = rng.integers(0, 256, (8, 171), dtype=np.uint8)
    direct = np.asarray(hh_pallas.hh256_batch(blocks))
    traced = np.asarray(jax.jit(lambda x: hh_pallas.hh256_batch(x))(blocks))
    assert np.array_equal(traced, direct)
    assert np.array_equal(direct, _host_digests(blocks))


# -- rows hashed: what mt_tpu_hash_rows_total counts (ISSUE 31) ---------------

@pytest.mark.parametrize("B,rows", [(1, 128), (4, 128), (16, 128),
                                    (128, 128), (129, 256), (1024, 1024),
                                    (1025, 2048)])
def test_hashed_rows_is_the_pad_the_program_applies(B, rows, monkeypatch):
    """``hh_pallas.hashed_rows`` and the operand ``_hh256_batch`` hands
    the kernel come from one function of B: a 2+2 stripe's 4 rows and a
    12+4 stripe's 16 both hash a 128-row tile.  The XLA form and the
    shapes ``hh256_batch`` hands to it pad none."""
    import jax
    import jax.numpy as jnp
    from minio_tpu.ops import hh_pallas
    seen = []

    def kernel_stub(x, n_packets, S):
        seen.append((x.shape[0], S))
        return jnp.zeros((x.shape[0] // (S * 128), 32, S, 128), jnp.uint32)

    monkeypatch.setattr(hh_pallas, "_run_nat", kernel_stub)
    # under a function of this test's own: a trace of ``_hh256_batch``
    # itself would be cached, stub and all, for every later caller
    out = jax.eval_shape(lambda x: hh_pallas._hh256_batch.__wrapped__(x),
                         jax.ShapeDtypeStruct((B, 64), jnp.uint8))
    assert out.shape == (B, 32)
    (padded, S), = seen
    assert padded == rows == hh_pallas.hashed_rows(B, 64)
    assert padded % (S * 128) == 0 and 0 <= padded - B < S * 128
    assert hh_pallas.hashed_rows(B, 31) == B
    assert hk.hashed_rows(B, 64) == B


def _hash_rows():
    from minio_tpu.admin.metrics import GLOBAL
    snap = GLOBAL.snapshot()
    return {kind: snap.get(("mt_tpu_hash_rows_total", (("kind", kind),)), 0)
            for kind in ("real", "hashed")}


@pytest.mark.parametrize("pallas,hashed", [(False, 4), (True, 128)],
                         ids=["xla", "pallas"])
def test_device_hash_dispatch_counts_its_rows(pallas, hashed, monkeypatch):
    """One dispatch of the device bitrot hash raises
    ``mt_tpu_hash_rows_total`` by its B rows (``real``) and by the rows
    the form in force hashes (``hashed``), and says both in
    ``hash.launch``'s span detail."""
    from minio_tpu.obs import trace
    from minio_tpu.ops import codec, device
    monkeypatch.setattr(device, "use_pallas", lambda: pallas)
    blocks = np.random.default_rng(31).integers(
        0, 256, (4, 64), dtype=np.uint8)
    before = _hash_rows()
    with trace.HTTP_TRACE.subscribe() as sub:
        got = codec._device_hh256_batch(blocks)
        spans = list(sub.drain(20, timeout=2.0))
    assert np.array_equal(got, _host_digests(blocks))
    after = _hash_rows()
    assert {k: after[k] - before[k] for k in after} == \
        {"real": 4, "hashed": hashed}
    launch, = [s for s in spans if s.get("funcName") == "hash.launch"]
    assert launch["tpu"] == {"op": "hash", "rows": 4, "rowsHashed": hashed}

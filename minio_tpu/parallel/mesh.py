"""Multi-chip erasure coding over a jax.sharding.Mesh.

MinIO's parallelism axes (SURVEY.md §2.3) mapped onto a TPU device mesh:

  * ``stripe`` axis — object/stripe batch parallelism (the DP analog; the
    reference hashes objects across erasure sets, cmd/erasure-sets.go:629)
  * ``shard`` axis  — shard parallelism (the TP analog; the reference writes
    k+m shards concurrently, goroutine-per-drive, cmd/erasure-encode.go:36)

Within the ``shard`` axis each device holds a contiguous slice of the k data
shards and the matching columns of the GF(2) coefficient matrix.  It computes
a partial integer matmul; a ``psum`` over the shard axis then XOR-combines
partials (sum mod 2 == XOR for bit operands), so the collective rides ICI as
one int32 all-reduce.  This is the device-native equivalent of the
reference's fan-out/fan-in over drive goroutines.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from minio_tpu.ops import device  # noqa: F401 — compile cache set before the first jit
from minio_tpu.ops import gf8


def make_mesh(devices=None, stripe: int | None = None,
              shard: int | None = None) -> Mesh:
    """Build a ('stripe', 'shard') mesh over the given (or all) devices."""
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if shard is None:
        shard = 1 if stripe is None else n // stripe
    if stripe is None:
        stripe = n // shard
    assert stripe * shard == n, (stripe, shard, n)
    dev = np.array(devices).reshape(stripe, shard)
    return Mesh(dev, axis_names=("stripe", "shard"))


# -- active mesh (the data plane's handle onto the chips) -------------------
#
# The object layer reaches the ICI collectives through here: an
# ErasureObjects built with backend="mesh" routes encode/reconstruct/
# heal matmuls through the active mesh (ops/rs_mesh.py), the way the
# reference's erasureObjects fans shards over drive goroutines
# (cmd/erasure-encode.go:36-70).  A 1-device mesh is the degenerate
# single-chip case, so the same code path serves both.

_ACTIVE: Mesh | None = None


def set_active_mesh(mesh: Mesh | None) -> None:
    """Install (or with None, reset) the process-wide data-plane mesh."""
    global _ACTIVE
    _ACTIVE = mesh


def get_active_mesh() -> Mesh:
    """The data-plane mesh; defaults to shard-axis parallelism over all
    visible devices (the TP analog — shard blocks split across chips,
    XOR fan-in rides one ICI psum)."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = make_mesh(stripe=1)
    return _ACTIVE


def _local_gf2_kernel(n_rows: int, reduce_fn):
    """Per-device GF(2) bitplane kernel shared by the psum and ring
    paths; `reduce_fn` folds the (8r, B/T, n) int32 partial products
    across the ``shard`` axis."""

    def local(mat, data):
        # mat: (8r, 8k/S) int8;  data: (B/T, k/S, n) uint8
        b, kl, n = data.shape
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((data[:, :, None, :] >> shifts[None, None, :, None]) & 1)
        bits = bits.reshape(b, 8 * kl, n).astype(jnp.int8)
        acc = jax.lax.dot_general(
            mat, bits, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)          # (8r, B/T, n)
        acc = reduce_fn(acc)
        par = (acc & 1).astype(jnp.uint8)
        par = par.reshape(n_rows // 8, 8, b, n)
        weights = (jnp.uint8(1) << shifts)[None, :, None, None]
        packed = (par * weights).sum(axis=1, dtype=jnp.uint8)
        return packed.transpose(1, 0, 2)               # (B/T, r, n)

    return local


_SPECS = dict(in_specs=(P(None, "shard"), P("stripe", "shard", None)),
              out_specs=P("stripe", None, None))


@functools.lru_cache(maxsize=32)
def _sharded_apply(mesh: Mesh, n_rows: int, k: int):
    """Compiled sharded kernel: (8r, 8k) matrix x (B, k, n) shards.

    Matrix columns and data shards are split over the ``shard`` mesh axis,
    stripes over ``stripe``; partial products XOR-reduce via psum."""
    local = _local_gf2_kernel(
        n_rows, lambda acc: jax.lax.psum(acc, "shard"))
    return jax.jit(jax.shard_map(local, mesh=mesh, **_SPECS))


def distributed_apply(mesh: Mesh, M: np.ndarray,
                      shards: np.ndarray) -> jax.Array:
    """out[b] = M (GF) @ shards[b], sharded over the mesh.

    M: (r, k) GF coefficients;  shards: (B, k, n) uint8 with B divisible
    by the stripe axis.  k NEED NOT divide the shard axis: zero shards
    (and matching zero matrix columns) pad k up to the next multiple —
    a zero operand contributes nothing to the XOR fan-in, so the padded
    kernel is bit-identical (the k=12-over-4 exactness of the headline
    geometry is not load-bearing).
    """
    M = np.asarray(M, dtype=np.uint8)
    shards = np.asarray(shards, dtype=np.uint8)
    S = mesh.shape["shard"]
    k = shards.shape[1]
    pad = (-k) % S
    if pad:
        shards = np.concatenate(
            [shards, np.zeros((shards.shape[0], pad, shards.shape[2]),
                              np.uint8)], axis=1)
        M = np.concatenate(
            [M, np.zeros((M.shape[0], pad), np.uint8)], axis=1)
    M2 = jnp.asarray(gf8.gf2_expand(M), jnp.int8)
    fn = _sharded_apply(mesh, M2.shape[0], shards.shape[1])
    return fn(M2, jnp.asarray(shards))


def distributed_encode(mesh: Mesh, data_blocks: int, parity_blocks: int,
                       shards: np.ndarray) -> jax.Array:
    """Parity for a batch of stripes, sharded over ('stripe', 'shard')."""
    M = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)
    return distributed_apply(mesh, np.asarray(M)[data_blocks:], shards)


def distributed_reconstruct(mesh: Mesh, data_blocks: int, parity_blocks: int,
                            surviving: np.ndarray, present: list[int],
                            wanted: list[int]) -> jax.Array:
    """Rebuild ``wanted`` shards from k survivors, sharded over the mesh.

    surviving: (B, k, n) rows ordered by ``present``.  The tiny GF solve runs
    on host (gf8.gf_mat_inv); the heavy matmul is device-sharded.
    """
    rows = _reconstruct_rows(data_blocks, parity_blocks, present, wanted)
    return distributed_apply(mesh, rows, surviving)


# -- ring formulation (neighbor-hop ICI) ------------------------------------

@functools.lru_cache(maxsize=32)
def _ring_apply(mesh: Mesh, n_rows: int, k: int):
    """Same XOR fan-in as _sharded_apply but as an explicit ppermute
    ring all-reduce over the ``shard`` axis: each step passes the
    accumulator to the next neighbor and folds the local partial in —
    S-1 single-hop ICI transfers instead of one tree all-reduce.  This
    is the ring layout SURVEY.md §5 maps long-sequence reconstruction
    onto: neighbors stream partial XOR state around the ring, which
    composes with compute overlap when stripes pipeline."""
    S = mesh.shape["shard"]
    perm = [(j, (j + 1) % S) for j in range(S)]

    def ring_reduce(partial):
        def step(_, acc):
            acc = jax.lax.ppermute(acc, "shard", perm)
            return acc + partial

        # after S-1 hops every device holds the full ring-reduced sum
        return jax.lax.fori_loop(0, S - 1, step, partial)

    local = _local_gf2_kernel(n_rows, ring_reduce)
    # ring replication over 'shard' is real (every device ends with the
    # full sum) but not statically inferable through ppermute/fori_loop,
    # so replication checking is disabled for this kernel
    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                                 **_SPECS))


def _reconstruct_rows(data_blocks: int, parity_blocks: int,
                      present: list[int], wanted: list[int]) -> np.ndarray:
    """Host-side GF solve shared by the psum and ring reconstructs."""
    M = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)
    return gf8.decode_rows(M, data_blocks, list(present), list(wanted))


def ring_reconstruct(mesh: Mesh, data_blocks: int, parity_blocks: int,
                     surviving: np.ndarray, present: list[int],
                     wanted: list[int]) -> jax.Array:
    """distributed_reconstruct via the ppermute ring instead of psum."""
    rows = _reconstruct_rows(data_blocks, parity_blocks, present, wanted)
    M2 = jnp.asarray(gf8.gf2_expand(np.asarray(rows, dtype=np.uint8)),
                     jnp.int8)
    fn = _ring_apply(mesh, M2.shape[0], surviving.shape[1])
    return fn(M2, jnp.asarray(surviving, dtype=jnp.uint8))


# -- per-device-different survivor patterns ---------------------------------

@functools.lru_cache(maxsize=32)
def _grouped_apply(mesh: Mesh, n_rows: int, k: int):
    """Like _sharded_apply but the decode matrix VARIES along the
    stripe axis: each stripe group (one row of devices) applies its own
    matrix.  This is the real degraded-cluster shape — different erasure
    sets lose different drives, so each device group reconstructs with
    its own survivor pattern in the SAME sharded step
    (cmd/erasure-healing.go heals per-set patterns independently)."""
    inner = _local_gf2_kernel(
        n_rows, lambda acc: jax.lax.psum(acc, "shard"))

    def local(mats, data):
        # mats: (1, 8r, 8k/S) — this stripe group's matrix slice
        return inner(mats[0], data)

    specs = dict(in_specs=(P("stripe", None, "shard"),
                           P("stripe", "shard", None)),
                 out_specs=P("stripe", None, None))
    return jax.jit(jax.shard_map(local, mesh=mesh, **specs))


def distributed_reconstruct_mixed(
        mesh: Mesh, data_blocks: int, parity_blocks: int,
        surviving: np.ndarray,
        patterns: list[tuple[list[int], list[int]]]) -> jax.Array:
    """Rebuild shards where EACH stripe group has its own survivor
    pattern.

    surviving: (B, k, n) with B divisible by the stripe axis; stripe
    group g's rows are ordered by ``patterns[g][0]`` (its present
    list).  patterns: one (present, wanted) per stripe-axis group; all
    groups must want the same COUNT of shards (their identities may
    differ freely).  Returns (B, r, n): group g's rows are its own
    ``patterns[g][1]`` reconstruction.
    """
    T = mesh.shape["stripe"]
    if len(patterns) != T:
        raise ValueError(f"need {T} patterns, got {len(patterns)}")
    r = len(patterns[0][1])
    if any(len(w) != r for _, w in patterns):
        raise ValueError("all groups must reconstruct the same count")
    mats = np.stack([
        gf8.gf2_expand(np.asarray(_reconstruct_rows(
            data_blocks, parity_blocks, list(p), list(w)), np.uint8))
        for p, w in patterns]).astype(np.int8)         # (T, 8r, 8k)
    fn = _grouped_apply(mesh, mats.shape[1], surviving.shape[1])
    return fn(jnp.asarray(mats),
              jnp.asarray(surviving, dtype=jnp.uint8))


# -- fused encode + bitrot hash (BASELINE config 5, multi-chip form) --------

@functools.lru_cache(maxsize=32)
def _fused_encode_hash(mesh: Mesh, n_rows: int, k: int):
    """Parity AND per-shard HighwayHash-256 digests from one sharded
    pipeline: each device encodes its partial parity (psum XOR fan-in
    over ICI), hashes its OWN k/S data-shard slice locally, and the data
    digests ride an all_gather over the shard axis — the multi-chip form
    of the fused single-chip path (ops/hh_pallas.py).  Parity is
    replicated post-psum, so its digests are computed in place."""
    from minio_tpu.ops import hh_kernels

    def local(mat, data):
        # data: (B/T, k/S, n) uint8 — this device's shard slice
        b, kl, n = data.shape
        encode = _local_gf2_kernel(
            n_rows, lambda acc: jax.lax.psum(acc, "shard"))
        parity = encode(mat, data)                   # (B/T, r, n) replicated
        d_dig = hh_kernels.hh256_batch(
            data.reshape(b * kl, n)).reshape(b, kl, 32)
        d_dig = jax.lax.all_gather(
            d_dig, "shard", axis=1, tiled=True)      # (B/T, k, 32)
        r = parity.shape[1]
        p_dig = hh_kernels.hh256_batch(
            parity.reshape(b * r, n)).reshape(b, r, 32)
        return parity, jnp.concatenate([d_dig, p_dig], axis=1)

    specs = dict(in_specs=(P(None, "shard"), P("stripe", "shard", None)),
                 out_specs=(P("stripe", None, None),
                            P("stripe", None, None)))
    return jax.jit(jax.shard_map(local, mesh=mesh, check_vma=False,
                                 **specs))


def distributed_encode_with_bitrot(mesh: Mesh, data_blocks: int,
                                   parity_blocks: int,
                                   shards: np.ndarray):
    """(parity, digests) for a stripe batch, sharded over the mesh.

    shards: (B, k, n) uint8.  Returns parity (B, m, n) and digests
    (B, k+m, 32) — data-shard digests first, parity digests after,
    bit-identical to the host HighwayHash-256 with the bitrot key.
    """
    M = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)
    M2 = jnp.asarray(
        gf8.gf2_expand(np.asarray(M)[data_blocks:]), jnp.int8)
    fn = _fused_encode_hash(mesh, M2.shape[0], shards.shape[1])
    return fn(M2, jnp.asarray(shards, dtype=jnp.uint8))

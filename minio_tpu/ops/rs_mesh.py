"""Mesh codec backend — erasure matmuls sharded over the active device
mesh (parallel/mesh.py) behind the same impl surface as rs_kernels /
gf8_ref, so ``Erasure(backend="mesh")`` drops into the object layer's
existing PUT/GET/heal paths unchanged.

This is the multi-chip data plane the blueprint contracts (SURVEY.md
§2.3): encode fans the k shard blocks and GF(2) matrix columns across
the mesh's ``shard`` axis, partial products XOR-combine via one ICI
psum, stripes batch over the ``stripe`` axis — the device-native form
of the reference's goroutine-per-drive fan-out
(cmd/erasure-encode.go:36-70).  A 1-device mesh is the degenerate
single-chip case, so the backend is valid on any topology.

Shard math is bit-identical to the other backends: distributed_apply
zero-pads k up to the shard axis (a zero operand adds nothing to an
XOR fan-in) and this module zero-pads the stripe batch the same way.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from minio_tpu.parallel import mesh as mesh_mod
from . import device, gf8, hh_pallas, rs_fused, rs_kernels, rs_pallas

# Two per-device engines behind every entry point here, chosen by
# ops/device.py (use_pallas): on a TPU the fused pallas bitplane kernel
# (ops/rs_pallas.py) with a ppermute ring XOR-combining the PACKED
# parity bytes — (S-1) x r x n bytes of ICI traffic, ring-allreduce
# optimal; off it the XLA psum formulation (parallel/mesh.py) on the
# virtual CPU mesh.


@functools.lru_cache(maxsize=64)
def _sharded_apply_pallas(mesh, r: int, kl: int, gs: int, tn: int):
    """shard_map'd per-device pallas matmul + packed-byte ring XOR.

    GF(2) addition of packed parity bytes IS XOR, so partial parities
    combine bitwise after each single-hop ppermute — no int32
    accumulator ever crosses ICI (a psum of the pre-packed accumulator
    would carry 32x the bytes and erase the kernel's HBM advantage).
    """
    S = mesh.shape["shard"]
    perm = [(j, (j + 1) % S) for j in range(S)]

    def local(mats, data):
        # mats: (1, gs*8r, gs*8kl) int8 — this device's column slice;
        # data: (B/T, kl, n) uint8
        part = rs_pallas._gf2_apply_bm(mats[0], data, gs=gs, tn=tn)
        if S == 1:
            return part

        def step(_, acc):
            return jax.lax.ppermute(acc, "shard", perm) ^ part

        return jax.lax.fori_loop(0, S - 1, step, part)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P("shard", None, None), P("stripe", "shard", None)),
        out_specs=P("stripe", None, None)))


def _apply_pallas(m, rows: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """Mesh apply with the pallas per-device engine; pads B to the
    stripe x gs grid, k to the shard axis, n to the lane tile."""
    T, S = m.shape["stripe"], m.shape["shard"]
    B, k, n = shards.shape
    r = rows.shape[0]
    padK = (-k) % S
    if padK:
        shards = np.concatenate(
            [shards, np.zeros((B, padK, n), np.uint8)], axis=1)
        rows = np.concatenate(
            [rows, np.zeros((r, padK), np.uint8)], axis=1)
    kl = (k + padK) // S
    gs = rs_pallas._GS
    padB = (-B) % (T * gs)
    if padB:
        shards = np.concatenate(
            [shards, np.zeros((padB, k + padK, n), np.uint8)])
    # same lane-tile heuristic as rs_pallas.apply_matrix
    q = max(n // 4, 1)
    tn = rs_pallas._LANES
    while tn * 2 <= q and tn < rs_pallas._TN:
        tn *= 2
    padN = (-n) % tn
    if padN:
        shards = np.pad(shards, ((0, 0), (0, 0), (0, padN)))
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    mats = jnp.stack([
        rs_pallas._device_matrix_bd(
            np.ascontiguousarray(rows[:, j * kl:(j + 1) * kl])
            .tobytes(), r, kl, gs)
        for j in range(S)])
    fn = _sharded_apply_pallas(m, r, kl, gs, tn)
    out = np.asarray(fn(mats, jnp.asarray(shards)))
    return out[:B, :, :n]


def apply_matrix(rows: np.ndarray, shards) -> np.ndarray:
    """out[b] = rows (GF) @ shards[b] over the active mesh.

    shards: (B, k, n) or (k, n) uint8.  B is zero-padded up to the
    stripe axis (zero stripes produce zero rows we slice off), so any
    batch size is valid on any mesh shape.
    """
    shards = np.asarray(shards, dtype=np.uint8)
    squeeze = shards.ndim == 2
    if squeeze:
        shards = shards[None]
    m = mesh_mod.get_active_mesh()
    if device.use_pallas():
        rows8 = np.asarray(rows, dtype=np.uint8)
        out = _apply_pallas(m, rows8, shards)
        return out[0] if squeeze else out
    T = m.shape["stripe"]
    B = shards.shape[0]
    pad = (-B) % T
    if pad:
        shards = np.concatenate(
            [shards, np.zeros((pad,) + shards.shape[1:], np.uint8)])
    out = np.asarray(mesh_mod.distributed_apply(m, rows, shards))[:B]
    return out[0] if squeeze else out


def encode_parity(data_shards: np.ndarray, parity: int,
                  matrix: np.ndarray | None = None) -> np.ndarray:
    """(B, k, n) or (k, n) data -> (B, m, n) / (m, n) parity, sharded."""
    data_shards = np.asarray(data_shards, dtype=np.uint8)
    k = data_shards.shape[-2]
    if matrix is None:
        matrix = gf8.rs_matrix(k, k + parity)
    return apply_matrix(np.asarray(matrix)[k:], data_shards)


def reconstruct(shards, data_blocks: int, parity_blocks: int,
                data_only: bool = False,
                matrix: np.ndarray | None = None):
    """Single-stripe reconstruct; survivor/solve logic is shared with
    rs_kernels, only the matmul engine is mesh-sharded."""
    return rs_kernels.reconstruct(shards, data_blocks, parity_blocks,
                                  data_only=data_only, matrix=matrix,
                                  apply=apply_matrix)


def reconstruct_batch(shards: np.ndarray, present: list[int],
                      wanted: list[int], data_blocks: int,
                      parity_blocks: int,
                      matrix: np.ndarray | None = None) -> np.ndarray:
    """Batched same-pattern reconstruction over the mesh."""
    if matrix is None:
        matrix = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)
    rows = gf8.decode_rows(matrix, data_blocks, list(present),
                           list(wanted))
    return apply_matrix(rows, shards)


@functools.lru_cache(maxsize=64)
def _fused_pallas_single(mesh, r: int, kl: int, gs: int, bs: int,
                         S_h: int, pc: int, n_real: int, hp: bool):
    """Fused encode+bitrot through the SINGLE-kernel formulation
    (ops/rs_fused.py): per device the data tile crosses HBM once —
    parity is computed and hashed from the VMEM-resident tiles.  When
    k is sharded (S>1) the per-device parity is PARTIAL before the
    ring XOR, so the kernel hashes only the data lanes (hp=False) and
    the parity digests run post-ring on the small parity rows; a
    1-wide shard axis hashes everything in-kernel (hp=True)."""
    S = mesh.shape["shard"]
    perm = [(j, (j + 1) % S) for j in range(S)]

    def local(mats, data):
        b = data.shape[0]
        part, planes = rs_fused._fused_call(
            mats[0], data, k=kl, ro=r, gs=gs, bs=bs, S=S_h, pc=pc,
            n_packets=n_real // 32, hash_parity=hp)
        if S > 1:
            def step(_, acc):
                return jax.lax.ppermute(acc, "shard", perm) ^ part
            parity = jax.lax.fori_loop(0, S - 1, step, part)
        else:
            parity = part
        digs = rs_fused._digests_from_planes(
            planes, data, part, k=kl, ro=r, bs=bs, S=S_h, B=b,
            n_real=n_real, hash_parity=hp)
        if hp:
            d_dig, p_dig = digs[:, :kl], digs[:, kl:]
        else:
            d_dig = digs
            rr = parity.shape[1]
            p_dig = hh_pallas.hh256_batch(
                parity[:, :, :n_real].reshape(b * rr, n_real)
            ).reshape(b, rr, 32)
        if S > 1:
            d_dig = jax.lax.all_gather(d_dig, "shard", axis=1,
                                       tiled=True)
        return parity, jnp.concatenate([d_dig, p_dig], axis=1)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P("shard", None, None), P("stripe", "shard", None)),
        out_specs=(P("stripe", None, None), P("stripe", None, None))))


@functools.lru_cache(maxsize=64)
def _fused_pallas(mesh, r: int, kl: int, gs: int, tn: int,
                  n_real: int):
    """Fused encode+bitrot, pallas per-chip form: local pallas matmul
    on this device's k-slice, packed-byte ring XOR for the parity, and
    the pallas HighwayHash kernel over the UNPADDED shard widths
    (digests must never cover lane-tile padding); data digests ride an
    all_gather, parity digests compute post-ring on the replicated
    parity."""
    S = mesh.shape["shard"]
    perm = [(j, (j + 1) % S) for j in range(S)]

    def local(mats, data):
        b = data.shape[0]
        part = rs_pallas._gf2_apply_bm(mats[0], data, gs=gs, tn=tn)
        if S > 1:
            def step(_, acc):
                return jax.lax.ppermute(acc, "shard", perm) ^ part
            parity = jax.lax.fori_loop(0, S - 1, step, part)
        else:
            parity = part
        d_dig = hh_pallas.hh256_batch(
            data[:, :, :n_real].reshape(b * kl, n_real)
        ).reshape(b, kl, 32)
        if S > 1:
            d_dig = jax.lax.all_gather(d_dig, "shard", axis=1,
                                       tiled=True)
        rr = parity.shape[1]
        p_dig = hh_pallas.hh256_batch(
            parity[:, :, :n_real].reshape(b * rr, n_real)
        ).reshape(b, rr, 32)
        return parity, jnp.concatenate([d_dig, p_dig], axis=1)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P("shard", None, None), P("stripe", "shard", None)),
        out_specs=(P("stripe", None, None), P("stripe", None, None))))


def _use_single() -> bool:
    """Single fused kernel (ops/rs_fused.py) unless MT_FUSED_SINGLE=0
    picks the two-kernel pipeline.  Whichever is chosen runs or raises:
    neither is the other's fallback."""
    return os.environ.get("MT_FUSED_SINGLE", "") != "0"


def _encode_with_bitrot_single(m, data_blocks: int, parity_blocks: int,
                               blocks: np.ndarray):
    """encode_with_bitrot through ops/rs_fused.py: ONE kernel per
    device reads the data tile from HBM once and emits parity AND
    hash-state planes; padding mirrors _encode_with_bitrot_pallas
    (k up to the shard axis, B up to stripe x row-block, n up to the
    plan's lane tile)."""
    T, S = m.shape["stripe"], m.shape["shard"]
    B, k, n = blocks.shape
    r = parity_blocks
    M = np.asarray(gf8.rs_matrix(data_blocks,
                                 data_blocks + parity_blocks))[k:]
    padK = (-k) % S
    if padK:
        blocks = np.concatenate(
            [blocks, np.zeros((B, padK, n), np.uint8)], axis=1)
        M = np.concatenate([M, np.zeros((r, padK), np.uint8)], axis=1)
    kl = (k + padK) // S
    hp = S == 1                     # full parity only without k-sharding
    p = rs_fused.plan(-(-B // T), kl, r, n, hash_parity=hp)
    B_pad = T * p["B_pad"]
    if B_pad != B:
        blocks = np.concatenate(
            [blocks, np.zeros((B_pad - B, k + padK, n), np.uint8)])
    if p["n_pad"] != n:
        blocks = np.pad(blocks, ((0, 0), (0, 0), (0, p["n_pad"] - n)))
    M = np.ascontiguousarray(M, dtype=np.uint8)
    mats = jnp.stack([
        rs_pallas._device_matrix_bd(
            np.ascontiguousarray(M[:, j * kl:(j + 1) * kl]).tobytes(),
            r, kl, p["gs"])
        for j in range(S)])
    fn = _fused_pallas_single(m, r, kl, p["gs"], p["bs"], p["S"],
                              p["pc"], n, hp)
    parity, digests = fn(mats, jnp.asarray(blocks))
    parity = np.asarray(parity)[:B, :, :n]
    digests = np.asarray(digests)
    # digest rows: [k+padK data slots][r parity slots] — drop the pads
    digests = np.concatenate(
        [digests[:B, :k], digests[:B, k + padK:]], axis=1)
    return parity, digests


def _encode_with_bitrot_pallas(m, data_blocks: int, parity_blocks: int,
                               blocks: np.ndarray):
    T, S = m.shape["stripe"], m.shape["shard"]
    B, k, n = blocks.shape
    r = parity_blocks
    M = np.asarray(gf8.rs_matrix(data_blocks,
                                 data_blocks + parity_blocks))[k:]
    padK = (-k) % S
    if padK:
        blocks = np.concatenate(
            [blocks, np.zeros((B, padK, n), np.uint8)], axis=1)
        M = np.concatenate([M, np.zeros((r, padK), np.uint8)], axis=1)
    kl = (k + padK) // S
    gs = rs_pallas._GS
    padB = (-B) % (T * gs)
    if padB:
        blocks = np.concatenate(
            [blocks, np.zeros((padB, k + padK, n), np.uint8)])
    q = max(n // 4, 1)
    tn = rs_pallas._LANES
    while tn * 2 <= q and tn < rs_pallas._TN:
        tn *= 2
    padN = (-n) % tn
    if padN:
        blocks = np.pad(blocks, ((0, 0), (0, 0), (0, padN)))
    M = np.ascontiguousarray(M, dtype=np.uint8)
    mats = jnp.stack([
        rs_pallas._device_matrix_bd(
            np.ascontiguousarray(M[:, j * kl:(j + 1) * kl]).tobytes(),
            r, kl, gs)
        for j in range(S)])
    fn = _fused_pallas(m, r, kl, gs, tn, n)
    parity, digests = fn(mats, jnp.asarray(blocks))
    parity = np.asarray(parity)[:B, :, :n]
    digests = np.asarray(digests)
    # digest rows: [k+padK data slots][r parity slots] — drop the pads
    digests = np.concatenate(
        [digests[:B, :k], digests[:B, k + padK:]], axis=1)
    return parity, digests


def encode_with_bitrot(data_blocks: int, parity_blocks: int,
                       blocks: np.ndarray):
    """(parity, digests) for a (B, k, n) stripe batch through the FUSED
    sharded pipeline: each device encodes its partial parity and hashes
    its own shard slice; digests ride an all_gather.

    Two engines, same contract as apply_matrix: on a TPU the
    per-device compute is the pallas matmul + pallas HighwayHash with a
    packed-byte ppermute-ring XOR; elsewhere the XLA psum formulation
    (mesh.distributed_encode_with_bitrot).

    Pads B up to the stripe axis and k up to the shard axis (padded
    shards are zero; their digests are computed but sliced off).
    Returns (parity (B, m, n) uint8, digests (B, k+m, 32) uint8).
    """
    m = mesh_mod.get_active_mesh()
    blocks = np.asarray(blocks, dtype=np.uint8)
    if device.use_pallas():
        engine = _encode_with_bitrot_single if _use_single() \
            else _encode_with_bitrot_pallas
        return engine(m, data_blocks, parity_blocks, blocks)
    T, S = m.shape["stripe"], m.shape["shard"]
    B, k, n = blocks.shape
    padB, padK = (-B) % T, (-k) % S
    if padB or padK:
        padded = np.zeros((B + padB, k + padK, n), np.uint8)
        padded[:B, :k] = blocks
        blocks = padded
    M = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)
    Mp = np.asarray(M)[data_blocks:]              # (m, k)
    if padK:
        Mp = np.concatenate(
            [Mp, np.zeros((Mp.shape[0], padK), np.uint8)], axis=1)
    M2 = jnp.asarray(gf8.gf2_expand(Mp), jnp.int8)
    fn = mesh_mod._fused_encode_hash(m, M2.shape[0], blocks.shape[1])
    parity, digests = fn(M2, jnp.asarray(blocks))
    parity = np.asarray(parity)[:B]
    digests = np.asarray(digests)
    # digest rows: [k+padK data slots][m parity slots] — drop the pads
    digests = np.concatenate([digests[:B, :k], digests[:B, k + padK:]],
                             axis=1)
    return parity, digests


def _encode_with_bitrot_batched(data_blocks: int, parity_blocks: int,
                                block_size: int,
                                blocks: np.ndarray):
    """encode_with_bitrot through the cross-request batcher when it is
    enabled: concurrent PUT streams' fused encode+digest dispatches
    coalesce into one padded shard_map dispatch over the shared mesh
    (the production mesh PUT path's ride onto parallel/batcher.py).
    The executor is per-stripe independent along the batch axis —
    parity rows and per-shard digests each depend only on their own
    stripe — so concatenation is bit-identical to dispatching apart."""
    try:
        from minio_tpu.parallel import batcher as _bt
        enabled = _bt.CONFIG.on()
    except Exception:  # pragma: no cover — parallel plane unavailable
        enabled = False
    if not enabled:
        return encode_with_bitrot(data_blocks, parity_blocks, blocks)
    codec = _bt.codec_for(data_blocks, parity_blocks, block_size,
                          "mesh")
    rows = np.asarray(gf8.rs_matrix(
        data_blocks, data_blocks + parity_blocks))[data_blocks:]
    return _bt.GLOBAL.submit(
        codec, "encode-bitrot", rows, blocks,
        fn=lambda _rows, cat: encode_with_bitrot(
            data_blocks, parity_blocks, cat))


def encode_object_framed_fused(data_blocks: int, parity_blocks: int,
                               block_size: int, data,
                               digest: int = 32) -> np.ndarray:
    """Whole object -> bitrot-framed shard files with parity AND digests
    from the fused mesh pipeline (the multi-chip route of
    Erasure.encode_framed, its one caller).

    Returns (k+m, framed_len) uint8: per erasure block a
    [32B HighwayHash-256 digest][shard payload] frame, bit-identical to
    the host streaming-bitrot layout (cmd/bitrot-streaming.go framing
    around cmd/erasure-encode.go blocks).
    """
    k, m_par = data_blocks, parity_blocks
    buf = np.frombuffer(bytes(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) \
        else np.asarray(data, np.uint8).ravel()
    total = buf.size
    bs = block_size
    ssize = gf8.shard_size(bs, k)
    nfull, tail_len, tail_ss, flen = gf8.framed_layout(bs, k, total,
                                                       digest)
    F = digest + ssize
    out = np.zeros((k + m_par, flen), dtype=np.uint8)
    if nfull:
        blocks = np.zeros((nfull, k, ssize), dtype=np.uint8)
        blocks.reshape(nfull, k * ssize)[:, :bs] = \
            buf[:nfull * bs].reshape(nfull, bs)
        parity, digs = _encode_with_bitrot_batched(k, m_par, block_size,
                                                   blocks)
        fview = out[:, :nfull * F].reshape(k + m_par, nfull, F)
        fview[:k, :, digest:] = blocks.transpose(1, 0, 2)
        fview[k:, :, digest:] = parity.transpose(1, 0, 2)
        fview[:, :, :digest] = digs.transpose(1, 0, 2)
    if tail_len:
        tblock = np.zeros((1, k, tail_ss), dtype=np.uint8)
        tblock.reshape(1, k * tail_ss)[0, :tail_len] = buf[nfull * bs:]
        parity_t, digs_t = _encode_with_bitrot_batched(
            k, m_par, block_size, tblock)
        base = nfull * F
        out[:k, base + digest:] = tblock[0]
        out[k:, base + digest:] = parity_t[0]
        out[:, base:base + digest] = digs_t[0]
    return out

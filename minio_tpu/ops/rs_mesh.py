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

Shard math is bit-identical to the other backends: k is zero-padded up
to the shard axis (a zero operand adds nothing to an XOR fan-in) and
the stripe batch up to the stripe axis the same way.

Every dispatch is traced like the one-chip form's (rs_kernels): legs
``<op>.prep`` (host pads, per-device matrices), ``<op>.upload`` /
``<op>.fetch`` through ops/device.py (link bytes counted, padding
included), ``<op>.launch`` (the call of the sharded program until its
handles are held); the fused encode also counts the lanes it hashes
into ``mt_tpu_hash_rows_total{kind}`` and frames under ``hash.frame``.
No sync or copy separates a leg.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from minio_tpu.admin.metrics import GLOBAL as _metrics
from minio_tpu.obs import trace as _trace
from minio_tpu.parallel import mesh as mesh_mod
from . import (device, gf8, hh_kernels, hh_pallas, rs_fused, rs_kernels,
               rs_pallas)

# Two per-device engines behind every entry point here, chosen by
# ops/device.py (use_pallas): on a TPU the fused pallas bitplane kernel
# (ops/rs_pallas.py) with a ppermute ring XOR-combining the PACKED
# parity bytes — (S-1) x r x n bytes of ICI traffic, ring-allreduce
# optimal; off it the XLA psum formulation (parallel/mesh.py) on the
# virtual CPU mesh.


_RING_SPECS = dict(
    in_specs=(P("shard", None, None), P("stripe", "shard", None)),
    out_specs=P("stripe", None, None))


def _named_shard_map(name: str, local, mesh, in_specs, out_specs):
    """``local`` shard_map'd over ``mesh`` and jitted under a stable
    program name (``jit_<name>`` in a trace's ``XLA Modules`` line and
    in ``compile_stats()``), whatever the per-device function is
    called."""
    return device.named_jit(name)(jax.shard_map(
        local, mesh=mesh, check_vma=False, in_specs=in_specs,
        out_specs=out_specs))


def _ring_xor(part, S: int):
    """XOR fan-in of packed partial parities over the ``shard`` axis:
    S-1 single-hop ppermutes, each folded in bitwise."""
    if S == 1:
        return part
    perm = [(j, (j + 1) % S) for j in range(S)]

    def step(_, acc):
        return jax.lax.ppermute(acc, "shard", perm) ^ part

    return jax.lax.fori_loop(0, S - 1, step, part)


@device.once_cache(64)
def _sharded_apply_pallas(mesh, r: int, kl: int, gs: int, tn: int):
    """shard_map'd per-device pallas matmul + packed-byte ring XOR.

    GF(2) addition of packed parity bytes IS XOR, so partial parities
    combine bitwise after each single-hop ppermute — no int32
    accumulator ever crosses ICI (a psum of the pre-packed accumulator
    would carry 32x the bytes and erase the kernel's HBM advantage).
    """
    S = mesh.shape["shard"]

    def local(mats, data):
        # mats: (1, gs*8r, gs*8kl) int8 — this device's column slice;
        # data: (B/T, kl, n) uint8
        return _ring_xor(
            rs_pallas._gf2_apply_bm(mats[0], data, gs=gs, tn=tn), S)

    return _named_shard_map("mt_rs_mesh_apply", local, mesh,
                            **_RING_SPECS)


def _pad3(x: np.ndarray, B: int, k: int, n: int) -> np.ndarray:
    """``x`` zero-padded to (B, k, n) in one copy; ``x`` itself when it
    already has that shape."""
    if x.shape == (B, k, n):
        return x
    out = np.zeros((B, k, n), np.uint8)
    out[:x.shape[0], :x.shape[1], :x.shape[2]] = x
    return out


def _pad_cols(rows: np.ndarray, S: int) -> tuple[np.ndarray, int]:
    """(r, k) GF rows with zero columns up to a multiple of the shard
    axis, and the columns each device then holds."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    r, k = rows.shape
    padK = (-k) % S
    if padK:
        rows = np.concatenate(
            [rows, np.zeros((r, padK), np.uint8)], axis=1)
    return rows, (k + padK) // S


@functools.lru_cache(maxsize=64)
def _ring_matrices(key: bytes, r: int, S: int, kl: int,
                   gs: int) -> jax.Array:
    """The S per-device block-diagonal matrices of (r, S*kl) GF rows
    (device j holds columns [j*kl, (j+1)*kl)), stacked on the axis the
    mesh shards; cached on device by content, bounded like
    rs_pallas._device_matrix_bd."""
    rows = np.frombuffer(key, dtype=np.uint8).reshape(r, S * kl)
    return jnp.stack([
        rs_pallas._device_matrix_bd(
            np.ascontiguousarray(rows[:, j * kl:(j + 1) * kl]).tobytes(),
            r, kl, gs)
        for j in range(S)])


def _apply_pallas(m, rows: np.ndarray, shards: np.ndarray,
                  op: str = "decode") -> np.ndarray:
    """Mesh apply with the pallas per-device engine; pads B to the
    stripe x gs grid, k to the shard axis, n to the lane tile."""
    T, S = m.shape["stripe"], m.shape["shard"]
    B, k, n = shards.shape
    with _trace.span("tpu", op + ".prep", nbytes=shards.nbytes):
        rows, kl = _pad_cols(rows, S)
        r = rows.shape[0]
        gs = rs_pallas._GS
        tn = rs_pallas.lane_tile(n)
        mats = _ring_matrices(rows.tobytes(), r, S, kl, gs)
        fn = _sharded_apply_pallas(m, r, kl, gs, tn)
        shards = _pad3(shards, B + (-B) % (T * gs), S * kl,
                       n + (-n) % tn)
    dev = device.upload(op, shards)
    with _trace.span("tpu", op + ".launch", nbytes=dev.nbytes):
        out = fn(mats, dev)
    return device.fetch(op, out)[:B, :, :n]


def apply_matrix(rows: np.ndarray, shards,
                 op: str = "decode") -> np.ndarray:
    """out[b] = rows (GF) @ shards[b] over the active mesh.

    shards: (B, k, n) or (k, n) uint8.  B is zero-padded up to the
    stripe axis (zero stripes produce zero rows we slice off), so any
    batch size is valid on any mesh shape.  ``op`` names the dispatch's
    legs, as rs_kernels.apply_matrix's does.
    """
    shards = np.asarray(shards, dtype=np.uint8)
    squeeze = shards.ndim == 2
    if squeeze:
        shards = shards[None]
    m = mesh_mod.get_active_mesh()
    if device.use_pallas():
        out = _apply_pallas(m, np.asarray(rows, dtype=np.uint8), shards,
                            op)
        return out[0] if squeeze else out
    T, S = m.shape["stripe"], m.shape["shard"]
    B, k, n = shards.shape
    with _trace.span("tpu", op + ".prep", nbytes=shards.nbytes):
        rows, kl = _pad_cols(rows, S)
        M2 = rs_kernels._put_matrix(rows)
        fn = mesh_mod._sharded_apply(m, M2.shape[0], S * kl)
        shards = _pad3(shards, B + (-B) % T, S * kl, n)
    dev = device.upload(op, shards)
    with _trace.span("tpu", op + ".launch", nbytes=dev.nbytes):
        out = fn(M2, dev)
    out = device.fetch(op, out)[:B]
    return out[0] if squeeze else out


def encode_parity(data_shards: np.ndarray, parity: int,
                  matrix: np.ndarray | None = None) -> np.ndarray:
    """(B, k, n) or (k, n) data -> (B, m, n) / (m, n) parity, sharded."""
    data_shards = np.asarray(data_shards, dtype=np.uint8)
    k = data_shards.shape[-2]
    if matrix is None:
        matrix = gf8.rs_matrix(k, k + parity)
    return apply_matrix(np.asarray(matrix)[k:], data_shards, op="encode")


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


_FUSED_SPECS = dict(
    in_specs=_RING_SPECS["in_specs"],
    out_specs=(P("stripe", None, None), P("stripe", None, None)))


def _ring_digests(d_dig, parity, n_real: int, S: int):
    """(b, k+r, 32) digests on every device: this device's data digests
    all_gathered over the shard axis, the parity digests computed from
    the post-ring (replicated) parity rows over their UNPADDED width."""
    b, rr = parity.shape[:2]
    p_dig = hh_pallas.hh256_batch(
        parity[:, :, :n_real].reshape(b * rr, n_real)).reshape(b, rr, 32)
    if S > 1:
        d_dig = jax.lax.all_gather(d_dig, "shard", axis=1, tiled=True)
    return jnp.concatenate([d_dig, p_dig], axis=1)


@device.once_cache(64)
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

    def local(mats, data):
        b = data.shape[0]
        part, planes = rs_fused._fused_call(
            mats[0], data, k=kl, ro=r, gs=gs, bs=bs, S=S_h, pc=pc,
            n_packets=n_real // 32, hash_parity=hp)
        parity = _ring_xor(part, S)
        digs = rs_fused._digests_from_planes(
            planes, data, part, k=kl, ro=r, bs=bs, S=S_h, B=b,
            n_real=n_real, hash_parity=hp)
        if hp:
            return parity, digs
        return parity, _ring_digests(digs, parity, n_real, S)

    return _named_shard_map("mt_rs_fused_mesh", local, mesh,
                            **_FUSED_SPECS)


@device.once_cache(64)
def _fused_pallas(mesh, r: int, kl: int, gs: int, tn: int,
                  n_real: int):
    """Fused encode+bitrot, pallas per-chip form: local pallas matmul
    on this device's k-slice, packed-byte ring XOR for the parity, and
    the pallas HighwayHash kernel over the UNPADDED shard widths
    (digests must never cover lane-tile padding); data digests ride an
    all_gather, parity digests compute post-ring on the replicated
    parity."""
    S = mesh.shape["shard"]

    def local(mats, data):
        b = data.shape[0]
        parity = _ring_xor(
            rs_pallas._gf2_apply_bm(mats[0], data, gs=gs, tn=tn), S)
        d_dig = hh_pallas.hh256_batch(
            data[:, :, :n_real].reshape(b * kl, n_real)
        ).reshape(b, kl, 32)
        return parity, _ring_digests(d_dig, parity, n_real, S)

    return _named_shard_map("mt_rs_hh_mesh", local, mesh, **_FUSED_SPECS)


def _use_single() -> bool:
    """Single fused kernel (ops/rs_fused.py) unless MT_FUSED_SINGLE=0
    picks the two-kernel pipeline.  Whichever is chosen runs or raises:
    neither is the other's fallback."""
    return os.environ.get("MT_FUSED_SINGLE", "") != "0"


def _launch_fused(fn, mats, padded: np.ndarray, shape: tuple,
                  hashed: int):
    """One fused encode+bitrot dispatch once its operands are ready:
    ``encode.upload`` of the padded stripes, ``encode.launch`` of the
    sharded program until both handles are held, the hash lanes it
    runs (``hashed``, over all devices) against the k+r digests per
    stripe it is asked for.  Returns the call that lands the results:
    ``encode.fetch`` of parity and digests, pads stripped on the host."""
    B, k, n = shape
    dev = device.upload("encode", padded)
    with _trace.span("tpu", "encode.launch", nbytes=dev.nbytes):
        parity, digests = fn(mats, dev)
    _metrics.inc("mt_tpu_hash_rows_total", {"kind": "real"},
                 float(B * (k + parity.shape[1])))
    _metrics.inc("mt_tpu_hash_rows_total", {"kind": "hashed"},
                 float(hashed))
    kp = padded.shape[1]

    def land():
        par = device.fetch("encode", parity)[:B, :, :n]
        dig = device.fetch("encode", digests)
        # digest rows: [k+padK data slots][r parity slots] — drop the pads
        if kp != k or dig.shape[0] != B:
            dig = np.concatenate([dig[:B, :k], dig[:B, kp:]], axis=1)
        return par, dig

    return land


def _parity_rows(data_blocks: int, parity_blocks: int, S: int):
    """The parity rows of the geometry's matrix, columns zero-padded
    to the shard axis, and the columns per device."""
    return _pad_cols(np.asarray(gf8.rs_matrix(
        data_blocks, data_blocks + parity_blocks))[data_blocks:], S)


def _encode_with_bitrot_single(m, data_blocks: int, parity_blocks: int,
                               blocks: np.ndarray):
    """One dispatch of encode_with_bitrot through ops/rs_fused.py: ONE
    kernel per device reads the data tile from HBM once and emits
    parity AND hash-state planes; k is padded up to the shard axis, B
    up to stripe x row-block, n up to the plan's lane tile.  Launched
    on return; the result is ``_launch_fused``'s landing call."""
    T, S = m.shape["stripe"], m.shape["shard"]
    B, _, n = blocks.shape
    r = parity_blocks
    with _trace.span("tpu", "encode.prep", nbytes=blocks.nbytes):
        rows, kl = _parity_rows(data_blocks, r, S)
        hp = S == 1                 # full parity only without k-sharding
        p = rs_fused.plan(-(-B // T), kl, r, n, hash_parity=hp)
        mats = _ring_matrices(rows.tobytes(), r, S, kl, p["gs"])
        fn = _fused_pallas_single(m, r, kl, p["gs"], p["bs"], p["S"],
                                  p["pc"], n, hp)
        padded = _pad3(blocks, T * p["B_pad"], S * kl, p["n_pad"])
    # per device: the kernel's lanes, plus the post-ring parity rows
    # that every chip of the shard axis hashes again
    lanes = rs_fused.hashed_lanes(p) + (
        0 if hp else hh_pallas.hashed_rows(p["B_pad"] * r, n))
    return _launch_fused(fn, mats, padded, blocks.shape, T * S * lanes)


def _encode_with_bitrot_pallas(m, data_blocks: int, parity_blocks: int,
                               blocks: np.ndarray):
    """One dispatch of encode_with_bitrot through the two-kernel pipeline
    (rs_pallas matmul, then hh_pallas over data and post-ring
    parity)."""
    T, S = m.shape["stripe"], m.shape["shard"]
    B, _, n = blocks.shape
    r = parity_blocks
    with _trace.span("tpu", "encode.prep", nbytes=blocks.nbytes):
        rows, kl = _parity_rows(data_blocks, r, S)
        gs = rs_pallas._GS
        tn = rs_pallas.lane_tile(n)
        mats = _ring_matrices(rows.tobytes(), r, S, kl, gs)
        fn = _fused_pallas(m, r, kl, gs, tn, n)
        padded = _pad3(blocks, B + (-B) % (T * gs), S * kl,
                       n + (-n) % tn)
    b = padded.shape[0] // T
    lanes = hh_pallas.hashed_rows(b * kl, n) \
        + hh_pallas.hashed_rows(b * r, n)
    return _launch_fused(fn, mats, padded, blocks.shape, T * S * lanes)


def _encode_with_bitrot_xla(m, data_blocks: int, parity_blocks: int,
                            blocks: np.ndarray):
    """One dispatch of encode_with_bitrot through the XLA psum
    formulation (mesh._fused_encode_hash): the off-TPU form."""
    T, S = m.shape["stripe"], m.shape["shard"]
    B, _, n = blocks.shape
    r = parity_blocks
    with _trace.span("tpu", "encode.prep", nbytes=blocks.nbytes):
        rows, kl = _parity_rows(data_blocks, r, S)
        M2 = rs_kernels._put_matrix(rows)
        fn = mesh_mod._fused_encode_hash(m, M2.shape[0], S * kl)
        padded = _pad3(blocks, B + (-B) % T, S * kl, n)
    b = padded.shape[0] // T
    lanes = hh_kernels.hashed_rows(b * kl, n) \
        + hh_kernels.hashed_rows(b * r, n)
    return _launch_fused(fn, M2, padded, blocks.shape, T * S * lanes)


def encode_with_bitrot(data_blocks: int, parity_blocks: int,
                       blocks: np.ndarray):
    """(parity, digests) for a (B, k, n) stripe batch through the FUSED
    sharded pipeline: each device encodes its partial parity and hashes
    its own shard slice; digests ride an all_gather.

    Two engines, same contract as apply_matrix: on a TPU the
    per-device compute is the pallas form (the single fused kernel, or
    matmul + HighwayHash kernels) with a packed-byte ppermute-ring XOR;
    elsewhere the XLA psum formulation.  Each pads B up to the stripe
    axis and k up to the shard axis (padded shards are zero; their
    digests are computed but sliced off).

    A batch goes out one stripe per device and dispatch, every one
    uploaded and launched before any result is fetched (the device
    works on one while the next goes up).  The batch SIZE never
    reaches the program: its row-block is static, so each size would
    be a program of its own that a server traces and compiles for
    ~10 s on four chips the first time the combiner happens to form
    it, mid-traffic, with every caller of that batch parked behind it
    (PERF.md section 6, PR 33).  One program per shard width serves
    any batch; wider dispatches wait for shapes that are built ahead of
    the traffic (ROADMAP S1b).
    Returns (parity (B, m, n) uint8, digests (B, k+m, 32) uint8).
    """
    m = mesh_mod.get_active_mesh()
    blocks = np.asarray(blocks, dtype=np.uint8)
    if not device.use_pallas():
        engine = _encode_with_bitrot_xla
    elif _use_single():
        engine = _encode_with_bitrot_single
    else:
        engine = _encode_with_bitrot_pallas
    T = m.shape["stripe"]
    landings = [
        engine(m, data_blocks, parity_blocks, blocks[off:off + T])
        for off in range(0, blocks.shape[0], T)]
    outs = [land() for land in landings]
    if len(outs) == 1:
        return outs[0]
    return (np.concatenate([o[0] for o in outs]),
            np.concatenate([o[1] for o in outs]))


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
    bs = block_size
    ssize = gf8.shard_size(bs, k)
    # ``encode.prep``: the body and each zero-padded block batch, the
    # host copies this function makes before a dispatch
    with _trace.span("tpu", "encode.prep") as sp:
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) \
            else np.asarray(data, np.uint8).ravel()
        total = sp.nbytes = buf.size
        nfull, tail_len, tail_ss, flen = gf8.framed_layout(bs, k, total,
                                                           digest)
        if nfull:
            blocks = np.zeros((nfull, k, ssize), dtype=np.uint8)
            blocks.reshape(nfull, k * ssize)[:, :bs] = \
                buf[:nfull * bs].reshape(nfull, bs)
    if nfull:
        parity, digs = _encode_with_bitrot_batched(k, m_par, block_size,
                                                   blocks)
    if tail_len:
        with _trace.span("tpu", "encode.prep", nbytes=tail_len):
            tblock = np.zeros((1, k, tail_ss), dtype=np.uint8)
            tblock.reshape(1, k * tail_ss)[0, :tail_len] = \
                buf[nfull * bs:]
        parity_t, digs_t = _encode_with_bitrot_batched(
            k, m_par, block_size, tblock)
    # ``hash.frame``: digests and payloads land in the on-disk layout
    # through views, one copy each (the leg the one-chip route's
    # per-digest interleave is timed under)
    F = digest + ssize
    with _trace.span("tpu", "hash.frame", nbytes=(k + m_par) * flen):
        out = np.zeros((k + m_par, flen), dtype=np.uint8)
        if nfull:
            fview = out[:, :nfull * F].reshape(k + m_par, nfull, F)
            fview[:k, :, digest:] = blocks.transpose(1, 0, 2)
            fview[k:, :, digest:] = parity.transpose(1, 0, 2)
            fview[:, :, :digest] = digs.transpose(1, 0, 2)
        if tail_len:
            base = nfull * F
            out[:k, base + digest:] = tblock[0]
            out[k:, base + digest:] = parity_t[0]
            out[:, base:base + digest] = digs_t[0]
    return out

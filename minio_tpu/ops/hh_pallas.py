"""HighwayHash-256 as ONE fused Pallas TPU kernel.

The lax.scan formulation (hh_kernels.py) pays per-op dispatch latency
2732 times per shard batch — honest chained measurement puts it at
~2-7 GiB/s no matter the batch width, because each of the ~80 u32 ops
per packet runs as its own tiny VPU dispatch inside the while loop.

This kernel runs the ENTIRE packet loop inside a single Mosaic kernel:

* state lives in VMEM scratch as 32 (S, 128)-tile u32 limb planes
  (4 vars x 4 u64 lanes x hi/lo), carried across a packet-chunk grid
  dimension (the standard revisiting-accumulator pattern);
* the shard batch rides the VPU lane dimension: every op processes a
  full (S, 128) tile of independent shards, so the sequential packet
  chain costs VLIW-issue slots, not kernel dispatches;
* the lane dimension of the hash (4 u64 lanes) is fully unrolled in
  the kernel body — the zipper-merge permutation becomes explicit
  variable wiring, reusing hh_kernels' shape-generic u64-pair helpers;
* tail packets in the final chunk are masked with selects (the packet
  count is rarely a multiple of the chunk size);
* slice, pad, the kernel, the limb reassembly, the remainder packet
  and finalization (10 permutes, modular reduction; plain jnp on the
  (B, 4) state) are traced together: ONE compiled program per (B, n)
  shape (``jit_mt_hh256_batch``), one dispatch per call.  It has to
  stay one: op by op, the jnp around the kernel is ~840 Python
  dispatches per call — seconds under a server's GIL, around 4 ms of
  kernel.

Bit-identical to minio_tpu.hashing.highwayhash (reference:
cmd/bitrot.go:30-57, minio/highwayhash AVX2 assembly) — conformance-
tested against the host C path in tests/test_hh_device.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..hashing.highwayhash import MAGIC_KEY
from . import device, hh_kernels as hk

_U32 = jnp.uint32

# packets per grid step.  The kernel holds the input block AND its
# in-VMEM byte-plane transpose simultaneously (plus double-buffered
# prefetch), so the chunk is sized to keep the working set well under
# the 16 MiB scoped-vmem limit: 64 packets -> 2 MiB block,
# 2+2+2 MiB resident (a 128-packet chunk measured 17 MiB > limit)
_PC_NAT = 64


def _update_lanes(state, lanes):
    """One packet update with the 4 u64 hash lanes fully unrolled.

    state: dict var -> list of 4 (hi, lo) pairs; lanes: list of 4
    (hi, lo) pairs.  Mirrors hh_kernels._update exactly (same helper
    arithmetic), with the lane-sliced zipper interleave written as
    explicit pair wiring."""
    v0, v1, m0, m1 = state["v0"], state["v1"], state["m0"], state["m1"]
    v0 = list(v0)
    v1 = list(v1)
    m0 = list(m0)
    m1 = list(m1)
    for i in range(4):
        v1[i] = hk._add64(*v1[i], *hk._add64(*m0[i], *lanes[i]))
    for i in range(4):
        ph, plo = hk._mul32(v1[i][1], v0[i][0])
        m0[i] = (m0[i][0] ^ ph, m0[i][1] ^ plo)
    for i in range(4):
        v0[i] = hk._add64(*v0[i], *m1[i])
    for i in range(4):
        ph, plo = hk._mul32(v0[i][1], v1[i][0])
        m1[i] = (m1[i][0] ^ ph, m1[i][1] ^ plo)
    # v0 += zipper(v1) on lane pairs (1,0) and (3,2)
    for base in (0, 2):
        add1, add0 = hk._zipper(*v1[base + 1], *v1[base])
        v0[base] = hk._add64(*v0[base], *add0)
        v0[base + 1] = hk._add64(*v0[base + 1], *add1)
    # v1 += zipper(v0)
    for base in (0, 2):
        add1, add0 = hk._zipper(*v0[base + 1], *v0[base])
        v1[base] = hk._add64(*v1[base], *add0)
        v1[base + 1] = hk._add64(*v1[base + 1], *add1)
    return {"v0": v0, "v1": v1, "m0": m0, "m1": m1}


# limb plane order in scratch/output: var-major, lane, then hi/lo
_VARS = ("v0", "v1", "m0", "m1")


def _flatten(state):
    out = []
    for v in _VARS:
        for lane in range(4):
            out.extend(state[v][lane])          # hi, lo
    return out


def _unflatten(flat):
    state = {}
    i = 0
    for v in _VARS:
        lanes = []
        for _ in range(4):
            lanes.append((flat[i], flat[i + 1]))
            i += 2
        state[v] = lanes
    return state


def _kernel_nat(in_ref, out_ref, st, tbuf, *, S, n_packets, init_consts):
    """Grid step over NATURAL-layout shard bytes: in_ref is
    (S*128, _PC_NAT*32) uint8 — rows are shards, columns byte offsets.

    The byte-plane transpose happens HERE, in VMEM, as the kernel
    prologue (swapaxes into the ``tbuf`` scratch), instead of as a
    separate pallas transpose kernel: the standalone transpose costs a
    full extra HBM round trip of the entire operand (~2 ms per 340 MiB
    step measured on v5e), which was the single largest serial stage
    left in the fused encode+bitrot pipeline (BENCH_r03 detail).  The
    packet loop is the standard revisiting-accumulator pattern: state
    lives in the ``st`` scratch, carried across the packet-chunk grid
    dimension; the tail chunk is handled by the loop BOUND, not
    per-packet selects (masking the 32 carried limb planes measured
    8.5x the whole update)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        for idx, c in enumerate(init_consts):
            st[idx] = jnp.full((S, 128), np.uint32(c), _U32)

    tbuf[:] = jnp.swapaxes(in_ref[:], 0, 1).reshape(_PC_NAT * 32, S, 128)

    carry0 = tuple(st[idx] for idx in range(32))

    def body(p, carry):
        x = tbuf[pl.ds(p * 32, 32)].astype(_U32)     # (32, S, 128)
        lanes = []
        for lane in range(4):
            b = 8 * lane
            lo = (x[b] | (x[b + 1] << 8) | (x[b + 2] << 16)
                  | (x[b + 3] << 24))
            hi = (x[b + 4] | (x[b + 5] << 8) | (x[b + 6] << 16)
                  | (x[b + 7] << 24))
            lanes.append((hi, lo))
        return tuple(_flatten(_update_lanes(_unflatten(list(carry)),
                                            lanes)))

    valid = jnp.minimum(_PC_NAT, n_packets - j * _PC_NAT)
    final = jax.lax.fori_loop(0, valid, body, carry0)
    for idx in range(32):
        st[idx] = final[idx]

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for idx in range(32):
            out_ref[0, idx] = st[idx]


@device.named_jit("mt_hh256", static_argnames=("n_packets", "S"))
def _run_nat(x2d, n_packets, S):
    """x2d: (B_pad, P_pad*32) uint8 natural-layout shard bytes (row =
    one shard).  Returns (NB, 32, S, 128) u32 state planes.  2-D u8
    operands reach pallas in canonical layout, so no XLA layout copy
    sits between the producer kernel and this one."""
    bt, cols = x2d.shape
    nb = bt // (S * 128)
    npc = cols // (32 * _PC_NAT)
    init = _init_consts()
    kernel = functools.partial(_kernel_nat, S=S, n_packets=n_packets,
                               init_consts=init)
    return pl.pallas_call(
        kernel,
        grid=(nb, npc),
        in_specs=[pl.BlockSpec((S * 128, _PC_NAT * 32),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 32, S, 128),
                               lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 32, S, 128), _U32),
        scratch_shapes=[pltpu.VMEM((32, S, 128), _U32),
                        pltpu.VMEM((_PC_NAT * 32, S, 128), jnp.uint8)],
        interpret=device.interpret(),
        name="mt_hh256",
    )(x2d)


@functools.lru_cache(maxsize=1)
def _init_consts() -> tuple[int, ...]:
    """32 scalar u32 init limbs in plane order (key = MAGIC_KEY)."""
    v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l = hk._init_state_np(MAGIC_KEY)
    per_var = {"v0": (v0h, v0l), "v1": (v1h, v1l),
               "m0": (m0h, m0l), "m1": (m1h, m1l)}
    out = []
    for v in _VARS:
        hi, lo = per_var[v]
        for lane in range(4):
            out.append(int(hi[lane]))
            out.append(int(lo[lane]))
    return tuple(out)


def _row_tile(B: int) -> tuple[int, int]:
    """(S, rows) for a batch of B shard rows: the kernel's sublane tile
    and the rows the padded operand holds.  The shard tile adapts to the
    batch: a 16-shard tail call must not pad (and hash) 1008 garbage
    rows.  Mosaic requires the 2nd-minor block dim to be 8-divisible or
    equal to the whole array dim, so small batches use S=G (one tile
    block), larger ones S=8 + padding."""
    G = -(-B // 128)
    S = G if G < 8 else 8
    return S, -(-B // (S * 128)) * S * 128


def hashed_rows(B: int, n: int) -> int:
    """Rows the compiled program hashes for (B, n) blocks: B padded to
    whole 128-lane tiles (``_row_tile``, the pad ``_hh256_batch``
    applies); B itself where ``hh256_batch`` hands the call to the XLA
    form."""
    return B if n < 32 or B == 0 else _row_tile(B)[1]


def hh256_batch(blocks, key: bytes = MAGIC_KEY):
    """Drop-in for hh_kernels.hh256_batch, pallas packet loop.

    blocks: (B, n) uint8.  Returns (B, 32) uint8 digests, bit-identical
    to the reference HighwayHash256 with the bitrot magic key.  One
    dispatch; callable under an outer trace (rs_mesh's shard_map)."""
    if key != MAGIC_KEY:
        raise ValueError("pallas path supports the bitrot magic key only")
    blocks = jnp.asarray(blocks, jnp.uint8)
    B, n = blocks.shape
    if n < 32 or B == 0:
        return hk.hh256_batch(blocks, key)
    return _hh256_batch(blocks)


@device.named_jit("mt_hh256_batch")
def _hh256_batch(blocks):
    """(B, n) uint8 -> (B, 32) uint8 digests, B >= 1 and n >= 32.
    Everything below is a function of the static shape."""
    B, n = blocks.shape
    P, rem = n // 32, n % 32
    S, bt = _row_tile(B)
    b_pad = bt - B
    p_pad = -P % _PC_NAT
    # pad in 2-D BYTE layout (safe: 2-D u8 operands reach pallas in
    # canonical layout), then ONE kernel: the byte-plane transpose is
    # the hash kernel's in-VMEM prologue (_kernel_nat), so the operand
    # crosses HBM exactly once.  Two designs this replaced, both
    # measured: a standalone pallas transpose kernel costs an extra
    # full HBM read+write of the operand (capped the fused pipeline at
    # 20.65 GiB/s, r3); an XLA-op-produced 3-D u8 operand reaches a
    # pallas call through a ~45 GB/s layout-conversion copy (r2).
    x = blocks[:, :P * 32]
    if b_pad or p_pad:
        x = jnp.pad(x, ((0, b_pad), (0, p_pad * 32)))

    planes = _run_nat(x, P, S)                   # (NB, 32, S, 128)
    flat = [planes[:, idx].reshape(bt)[:B] for idx in range(32)]
    state = _unflatten(flat)
    # reassemble (B, 4) limb arrays for the existing finalize path
    st8 = []
    for v in _VARS:
        for part in (0, 1):                      # hi then lo
            st8.append(jnp.stack([state[v][lane][part]
                                  for lane in range(4)], axis=-1))
    state8 = tuple(st8)
    if rem:
        state8 = hk._remainder_update(state8, blocks[:, P * 32:], rem)
    return _finalize(state8)


@jax.jit
def _finalize(state8):
    state = state8
    for _ in range(10):
        state = hk._permute_update(state)
    v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l = state

    s10h, s10l = hk._add64(v0h, v0l, m0h, m0l)
    s32h, s32l = hk._add64(v1h, v1l, m1h, m1l)

    def modred(a3h, a3l, a2h, a2l, a1h, a1l, a0h, a0l):
        a3h = a3h & np.uint32(0x3FFFFFFF)
        m1h_, m1l_ = a1h, a1l
        for s in (1, 2):
            th, tl = hk._shl64(a3h, a3l, s)
            tl = tl | (a2h >> (32 - s))
            m1h_, m1l_ = m1h_ ^ th, m1l_ ^ tl
        m0h_, m0l_ = a0h, a0l
        for s in (1, 2):
            th, tl = hk._shl64(a2h, a2l, s)
            m0h_, m0l_ = m0h_ ^ th, m0l_ ^ tl
        return m0h_, m0l_, m1h_, m1l_

    h0h, h0l, h1h, h1l = modred(
        s32h[..., 1], s32l[..., 1], s32h[..., 0], s32l[..., 0],
        s10h[..., 1], s10l[..., 1], s10h[..., 0], s10l[..., 0])
    h2h, h2l, h3h, h3l = modred(
        s32h[..., 3], s32l[..., 3], s32h[..., 2], s32l[..., 2],
        s10h[..., 3], s10l[..., 3], s10h[..., 2], s10l[..., 2])
    out = jnp.stack([h0l, h0h, h1l, h1h, h2l, h2h, h3l, h3h], axis=-1)
    return jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(-1, 32)

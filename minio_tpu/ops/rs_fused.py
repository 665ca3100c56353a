"""Encode + HighwayHash-256 as ONE Pallas TPU kernel — the hash rides
encode's VMEM tiles (ISSUE 12 tentpole a).

The two-kernel fused pipeline (rs_pallas matmul, then hh_pallas over
data AND parity) moves every data byte across HBM twice: once into the
encode kernel, once into the hash kernel — 2D+2P of HBM traffic for an
operation whose information-theoretic minimum is D in + P out.  That
tax is the measured 38% gap between fused (32.12 GiB/s) and plain
encode (51.95, BENCH_r05).

This kernel closes the loop: per grid step the data tile is read from
HBM ONCE, the parity tile is computed on the MXU (rs_pallas's
unpack -> block-diagonal bit-matrix matmul -> pack, verbatim), and the
HighwayHash prologue then consumes BOTH tiles while they are still
VMEM-resident — the byte-plane transpose (hh_pallas's in-VMEM
prologue) runs over the concatenated data+parity sublanes, and the
packet chain updates a 32-limb state scratch carried across the
lane-tile grid dimension.  HBM sees D in and P out, nothing else.

Geometry: one grid row-block holds ``bs`` stripes x (k+ro) shards
flattened into S x 128 hash lanes (data shards stripe-major first,
then parity, then pad lanes whose garbage state is sliced off on the
way out).  For the headline 12+4 config that is 64 stripes = 1024
lanes = full (8, 128) VPU tiles — the same per-byte hash cost as the
standalone hh_pallas kernel, so the win is pure HBM traffic.

``hash_parity=False`` hashes only the data lanes: the mesh data plane
needs this when k is sharded across chips (per-device parity is
PARTIAL before the ring XOR — hashing it would digest garbage); the
full-parity hash then runs post-ring on the small parity rows.

Digests are bit-identical to the host HighwayHash-256 with the bitrot
magic key (tests/test_fused_kernel.py pins ragged geometries, tails
and the k/m matrix from the BASELINE configs).

Two callers.  On one chip this is the route of a PUT's full blocks
(called by ``Erasure.encode_framed`` on ``--backend tpu``): the kernel,
the plane reassembly, the remainder packet and finalization are ONE
compiled program per operand shape (``jit_mt_encode_bitrot``), and the
host stages the stripes at the plan's lane tile so nothing is padded on
the device.  A body of one full block goes out one stripe per dispatch
(``launch_encode_bitrot``); a body of several goes out in stripe groups
(``launch_encode_bitrot_groups``): G stripes per dispatch, G fixed per
geometry and width by ``group_plan`` (as many stripes as lay their
shards into one 128-lane hash row: 10 at 8+4), so the hash chain runs
once for G stripes.  Over a mesh ops/rs_mesh.py calls the kernel inside
its sharded program.  Off a TPU the same contract runs in the XLA forms
(``_encode_bitrot_xla``), so tier-1 drives the route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..admin.metrics import GLOBAL as _metrics
from ..obs import trace as _trace
from . import (device, gf8, hh_pallas as hhp, hh_kernels as hk, rs_kernels,
               rs_pallas)

_U32 = jnp.uint32
# lane-tile ceiling: 2048 bytes = 64 packets per chunk, the same
# packet-chunk size hh_pallas settled on (_PC_NAT) — large enough to
# amortise the transpose, small enough that data tile + tbuf + parity
# + state stay well under the 16 MiB scoped-vmem limit at bs=64
_TN_MAX = 2048


def plan(B: int, k: int, ro: int, n: int,
         hash_parity: bool = True) -> dict:
    """Tile plan for a (B, k, n) stripe batch: stripes per row-block
    (bs, a gs multiple), hash-lane rows S, lane tile tn — sized so the
    hash lanes fill (S, 128) tiles without padding a small batch up to
    a huge one.  Raises ValueError when the geometry cannot fit (one
    stripe's shards exceed 1024 lanes)."""
    R = k + (ro if hash_parity else 0)
    if B < 1 or n < 1:
        raise ValueError(f"degenerate batch ({B}, {n})")
    if R > 1024:
        raise ValueError(f"{R} shards/stripe exceed one row-block")
    stripes_cap = max(1, 1024 // R)
    gs = rs_pallas._GS if min(B, stripes_cap) >= rs_pallas._GS else 1
    bpad0 = -(-B // gs) * gs
    bs = min(max(gs, (stripes_cap // gs) * gs), bpad0)
    B_pad = -(-bpad0 // bs) * bs
    S = -(-bs * R // 128)
    tn = min(_TN_MAX, -(-n // 256) * 256)
    n_pad = -(-n // tn) * tn
    return {"R": R, "gs": gs, "bs": bs, "B_pad": B_pad, "S": S,
            "tn": tn, "n_pad": n_pad, "pc": tn // 32}


def group_plan(k: int, ro: int, n: int) -> dict:
    """The plan of one stripe-group program at shard width n: G = bs
    stripes in ONE row-block, as many as lay their k+ro hash lanes into
    one 128-lane row (S = 1, the hash chain of a single stripe), with
    the largest block-diagonal sub-group (4, 2 or 1 stripes) that
    divides G.  G is 1 when one stripe fills the row."""
    G = max(1, 128 // (k + ro))
    p = plan(1, k, ro, n)
    p.update(gs=max(g for g in (1, 2, rs_pallas._GS) if G % g == 0),
             bs=G, B_pad=G)
    return p


def hashed_lanes(p: dict) -> int:
    """Hash lanes one call of the kernel runs under plan ``p``: every
    row-block carries S x 128 of them, shard rows or pad."""
    return p["B_pad"] // p["bs"] * p["S"] * 128


def _kernel(m_ref, in_ref, par_ref, dig_ref, st, tbuf, *, k: int,
            ro: int, gs: int, bs: int, S: int, pc: int,
            n_packets: int, hash_parity: bool, init_consts):
    """One (stripe-block, lane-tile) grid step.

    m_ref:  (gs*8*ro, gs*8*k) int8 block-diagonal bit-major matrix
    in_ref: (bs, k, tn) uint8 data; par_ref: (bs, ro, tn) uint8 out
    dig_ref:(1, 32, S, 128) u32 hash-state planes (written at last j)
    st:     VMEM (32, S, 128) u32 carried state
    tbuf:   VMEM (tn, S, 128) u8 byte-plane transpose staging
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        for idx, c in enumerate(init_consts):
            st[idx] = jnp.full((S, 128), np.uint32(c), _U32)

    # -- encode: rs_pallas._kernel verbatim, looped over gs-stripe
    # sub-groups (the block-diagonal matrix packs gs stripes per MXU
    # call; bs/gs calls cover the row-block)
    par_vals = []
    for g in range(bs // gs):
        planes = []
        for s in range(gs):
            x = in_ref[g * gs + s].astype(jnp.int32)
            planes.extend(x >> b for b in range(8))
        bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)
        acc = jax.lax.dot_general(
            m_ref[:], bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc & 1
        for s in range(gs):
            base = s * 8 * ro
            out = acc[base:base + ro]
            for b in range(1, 8):
                out = out | (acc[base + b * ro:base + (b + 1) * ro] << b)
            out = out.astype(jnp.uint8)
            par_ref[g * gs + s] = out
            par_vals.append(out)

    # -- hash prologue: byte-plane transpose of the VMEM-resident
    # tiles (data, and the parity values just computed when
    # hash_parity — the in-register copies, not a read-back of the
    # output ref) — the operand never revisits HBM, which is the point
    tn = pc * 32
    parts = [in_ref[:].reshape(bs * k, tn)]
    lanes_used = bs * k
    if hash_parity:
        parts.extend(par_vals)
        lanes_used += bs * ro
    if S * 128 - lanes_used:
        parts.append(jnp.zeros((S * 128 - lanes_used, tn), jnp.uint8))
    allb = parts[0] if len(parts) == 1 else \
        jnp.concatenate(parts, axis=0)
    tbuf[:] = jnp.swapaxes(allb, 0, 1).reshape(tn, S, 128)

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
        return tuple(hhp._flatten(hhp._update_lanes(
            hhp._unflatten(list(carry)), lanes)))

    # tail lane-tiles may hold 0..pc whole packets of the real width;
    # the loop BOUND masks them (hh_pallas discipline — masking the 32
    # carried planes per packet measured 8.5x the update itself)
    valid = jnp.maximum(0, jnp.minimum(pc, n_packets - j * pc))
    final = jax.lax.fori_loop(0, valid, body, carry0)
    for idx in range(32):
        st[idx] = final[idx]

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for idx in range(32):
            dig_ref[0, idx] = st[idx]


@device.named_jit("mt_rs_fused", static_argnames=(
    "k", "ro", "gs", "bs", "S", "pc", "n_packets", "hash_parity"))
def _fused_call(mat_bd, data, *, k: int, ro: int, gs: int, bs: int,
                S: int, pc: int, n_packets: int, hash_parity: bool):
    """data: (B_pad, k, n_pad) uint8, B_pad % bs == 0, n_pad % tn == 0
    (caller pads).  Returns (parity (B_pad, ro, n_pad) u8,
    planes (B_pad//bs, 32, S, 128) u32 hash-state limbs)."""
    Bp, _, npad = data.shape
    tn = pc * 32
    kernel = functools.partial(
        _kernel, k=k, ro=ro, gs=gs, bs=bs, S=S, pc=pc,
        n_packets=n_packets, hash_parity=hash_parity,
        init_consts=hhp._init_consts())
    return pl.pallas_call(
        kernel,
        grid=(Bp // bs, npad // tn),
        in_specs=[
            pl.BlockSpec((gs * 8 * ro, gs * 8 * k), lambda i, j: (0, 0)),
            pl.BlockSpec((bs, k, tn), lambda i, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bs, ro, tn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 32, S, 128), lambda i, j: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, ro, npad), jnp.uint8),
            jax.ShapeDtypeStruct((Bp // bs, 32, S, 128), _U32),
        ],
        scratch_shapes=[pltpu.VMEM((32, S, 128), _U32),
                        pltpu.VMEM((tn, S, 128), jnp.uint8)],
        interpret=device.interpret(),
        name="mt_rs_fused",
    )(mat_bd, data)


def _digests_from_planes(planes, data, parity, *, k: int, ro: int,
                         bs: int, S: int, B: int, n_real: int,
                         hash_parity: bool):
    """Hash-state planes -> per-shard digests (B, R, 32), pure jnp
    (shard_map-traceable).  Lane order inside a row-block is data
    stripe-major, then parity, then pad — undone here; the sub-packet
    remainder and finalization reuse the hh_kernels host-formulation
    (both are jnp over the (lanes, 4) limb state)."""
    NB = planes.shape[0]
    R = k + (ro if hash_parity else 0)
    limbs = []
    for idx in range(32):
        lane_flat = planes[:, idx].reshape(NB, S * 128)
        d = lane_flat[:, :bs * k].reshape(NB * bs, k)[:B]
        if hash_parity:
            p = lane_flat[:, bs * k:bs * R].reshape(NB * bs, ro)[:B]
            d = jnp.concatenate([d, p], axis=1)
        limbs.append(d.reshape(B * R))
    state = hhp._unflatten(limbs)
    st8 = []
    for v in hhp._VARS:
        for part in (0, 1):                      # hi then lo
            st8.append(jnp.stack([state[v][lane][part]
                                  for lane in range(4)], axis=-1))
    state8 = tuple(st8)
    rem = n_real % 32
    if rem:
        P = n_real // 32
        tails = [data[:B, :, P * 32:n_real]]
        if hash_parity:
            tails.append(parity[:B, :, P * 32:n_real])
        rb = (tails[0] if len(tails) == 1 else
              jnp.concatenate(tails, axis=1)).reshape(B * R, rem)
        state8 = hk._remainder_update(state8, rb, rem)
    return hhp._finalize(state8).reshape(B, R, 32)


@device.named_jit("mt_encode_bitrot", static_argnames=("gs", "n_real"))
def _encode_bitrot(mat_bd, shards, *, gs: int, n_real: int):
    """One chip's whole PUT program: the kernel, the plane reassembly,
    the sub-packet remainder and finalization traced together, ONE
    compiled program per operand shape and one dispatch per call (op by
    op the jnp around the kernel is a chain of one-op programs).
    ``shards`` is staged at the plan's sizes (``staged_width``), so
    nothing is padded or sliced on the device.  Returns (parity,
    digests of the k data then the ro parity rows over ``n_real``
    bytes each), both flattened: a 1-D array crosses the link at the
    link's rate, the kernel's own (B, ro, n) uint8 tiles its few rows
    to 32 sublanes and comes down several times slower than its bytes
    (my chip runs, PR 34: (2, 2, 5 MiB) in 57 ms, flat in 6.3)."""
    B, k, _ = shards.shape
    ro = mat_bd.shape[0] // (8 * gs)
    # one stripe, or one stripe group: all B stripes in one row-block
    p = plan(1, k, ro, n_real) if B == 1 else group_plan(k, ro, n_real)
    assert p["B_pad"] == B and p["gs"] == gs, (B, gs, p)
    parity, planes = _fused_call(
        mat_bd, shards, k=k, ro=ro, gs=gs, bs=p["bs"], S=p["S"],
        pc=p["pc"], n_packets=n_real // 32, hash_parity=True)
    digests = _digests_from_planes(
        planes, shards, parity, k=k, ro=ro, bs=p["bs"], S=p["S"], B=B,
        n_real=n_real, hash_parity=True)
    return parity.reshape(-1), digests.reshape(-1)


@device.named_jit("mt_encode_bitrot")
def _encode_bitrot_xla(matrix_bits, shards):
    """``_encode_bitrot``'s contract in the XLA formulations (the
    bitplane matmul of rs_kernels, the lax.scan HighwayHash of
    hh_kernels: what ``mesh._fused_encode_hash`` runs per device), one
    program under the same name: the form off a TPU."""
    n = shards.shape[2]
    parity = rs_kernels._gf2_apply(matrix_bits, shards)
    digests = hk.hh256_batch(
        jnp.concatenate([shards, parity], axis=1).reshape(-1, n))
    return parity.reshape(-1), digests.reshape(-1)


def staged_width(k: int, ro: int, n: int) -> int:
    """The width a caller stages (B, k, n) stripes at for
    :func:`launch_encode_bitrot`, zero-tailed: the kernel's lane tile
    on a TPU, n itself for the XLA form."""
    return plan(1, k, ro, n)["n_pad"] if device.use_pallas() else n


def _program(M: np.ndarray, p: dict, n: int):
    """(the call of the one-chip program under plan ``p`` with the
    matrix bound, the hash lanes one call runs) in the form in force."""
    ro, k = M.shape
    if device.use_pallas():
        return functools.partial(
            _encode_bitrot,
            rs_pallas._device_matrix_bd(M.tobytes(), ro, k, p["gs"]),
            gs=p["gs"], n_real=n), hashed_lanes(p)
    return (functools.partial(_encode_bitrot_xla, rs_kernels._put_matrix(M)),
            hk.hashed_rows(p["B_pad"] * (k + ro), n))


def _count(form: str, programs: int, stripes: int, rows: int,
           hashed: int) -> None:
    """What one launch call ran: programs and the stripes they carry
    (``form`` ``stripe`` or ``group``), and the hash lanes asked for
    (``rows`` digests per stripe) against those the programs run."""
    _metrics.inc("mt_tpu_fused_programs_total", {"form": form},
                 float(programs))
    _metrics.inc("mt_tpu_fused_stripes_total", {"form": form},
                 float(stripes))
    _metrics.inc("mt_tpu_hash_rows_total", {"kind": "real"},
                 float(stripes * rows))
    _metrics.inc("mt_tpu_hash_rows_total", {"kind": "hashed"},
                 float(programs * hashed))


def launch_encode_bitrot(M: np.ndarray, staged: np.ndarray, n: int):
    """The one-chip PUT dispatch of one-block bodies: (B, k,
    staged_width) stripes of real width ``n`` go up (``encode.upload``),
    ONE program per stripe is launched (``encode.launch``, until its
    handles are held), and the hash lanes it runs are counted against
    the k+ro digests per stripe it is asked for.  Returns the call that
    lands the results (``encode.fetch``): (parity, one (ro, staged
    width) array per stripe; digests (B, k+ro, 32)) on the host.

    One stripe per dispatch whatever batch the combiner formed: a
    program's row-block is static, so every batch size would be a
    program of its own, first met and compiled mid-traffic with its
    callers parked behind it (PERF.md section 6, PR 33).  One program
    per shard width serves any batch; all of a batch's stripes are up
    and launched before any result is fetched."""
    M = np.ascontiguousarray(M, dtype=np.uint8)
    ro, k = M.shape
    B, _, width = staged.shape
    call, hashed = _program(M, plan(1, k, ro, n), n)
    handles = []
    for b in range(B):
        dev = device.upload("encode", staged[b:b + 1])
        with _trace.span("tpu", "encode.launch", nbytes=dev.nbytes):
            handles.append(call(dev))
    _count("stripe", B, B, k + ro, hashed)

    def land():
        parity = [device.fetch("encode", par).reshape(ro, width)
                  for par, _ in handles]
        digests = np.stack([device.fetch("encode", dig)
                            for _, dig in handles])
        return parity, digests.reshape(B, k + ro, 32)

    return land


@functools.lru_cache(maxsize=16)
def _zero_stripe(k: int, width: int) -> jax.Array:
    """One all-zero stripe made on the device: what completes a partial
    group there, so its padding never crosses the link."""
    return jnp.zeros((1, k, width), jnp.uint8)


@device.named_jit("mt_group_stage")
def _group_stage(*stripes):
    """G single-stripe arrays -> the (G, k, width) group operand."""
    return jnp.concatenate(stripes, axis=0)


@device.named_jit("mt_group_split", static_argnames=("G",))
def _group_split(parity, digests, *, G: int):
    """A group program's flat results -> G per-stripe pieces of each."""
    return jnp.split(parity, G), jnp.split(digests, G)


def launch_encode_bitrot_groups(M: np.ndarray, staged: np.ndarray,
                                n: int):
    """:func:`launch_encode_bitrot`'s contract for the stripes of bodies
    of several full blocks: the stripes go out in groups of G
    (``group_plan``), ONE program per group and all of them at one
    shape per width, whatever the batch or the bodies.  A whole group
    goes up as one array and its parity and digests come down in two
    fetches.  A last partial group of r < G goes up as its r stripes,
    is completed with zero stripes on the device (``mt_group_stage``)
    and its results are split per stripe there (``mt_group_split``), so
    the link carries the r stripes both ways and no padding."""
    M = np.ascontiguousarray(M, dtype=np.uint8)
    ro, k = M.shape
    B, _, width = staged.shape
    p = group_plan(k, ro, n)
    G = p["bs"]
    call, hashed = _program(M, p, n)
    whole, r = divmod(B, G)
    handles = []
    for g in range(whole):
        dev = device.upload("encode", staged[g * G:(g + 1) * G])
        with _trace.span("tpu", "encode.launch", nbytes=dev.nbytes):
            handles.append(call(dev))
    if r:
        pieces = [device.upload("encode", staged[b:b + 1])
                  for b in range(whole * G, B)]
        with _trace.span("tpu", "encode.launch",
                         nbytes=r * pieces[0].nbytes):
            pieces += [_zero_stripe(k, width)] * (G - r)
            handles.append(_group_split(*call(_group_stage(*pieces)), G=G))
    _count("group", len(handles), B, k + ro, hashed)

    def land():
        parity, digests = [], []
        for par, dig in handles[:whole]:
            parity.extend(device.fetch("encode", par).reshape(G, ro, width))
            digests.append(device.fetch("encode", dig))
        if r:
            par, dig = handles[-1]
            parity.extend(device.fetch("encode", x).reshape(ro, width)
                          for x in par[:r])
            digests.extend(device.fetch("encode", x) for x in dig[:r])
        return parity, np.concatenate(digests).reshape(B, k + ro, 32)

    return land


def encode_hash_device(M: np.ndarray, shards, *, n_real: int | None
                       = None, hash_parity: bool = True):
    """Single-kernel fused encode+hash; returns DEVICE arrays
    (parity (B, ro, n), digests (B, R, 32)) so callers chain further
    device work without a host round trip.

    M: (ro, k) GF coefficients; shards: (B, k, n) uint8; digests cover
    ``n_real`` bytes per shard (default n — callers whose width is
    lane-padded pass the true shard width).
    """
    M = np.ascontiguousarray(M, dtype=np.uint8)
    shards = jnp.asarray(shards, jnp.uint8)
    B, k, n = shards.shape
    ro = M.shape[0]
    n_real = n if n_real is None else n_real
    p = plan(B, k, ro, n, hash_parity)
    if p["B_pad"] != B:
        shards = jnp.pad(shards, ((0, p["B_pad"] - B), (0, 0), (0, 0)))
    if p["n_pad"] != n:
        shards = jnp.pad(shards, ((0, 0), (0, 0), (0, p["n_pad"] - n)))
    mb = rs_pallas._device_matrix_bd(M.tobytes(), ro, k, p["gs"])
    parity, planes = _fused_call(
        mb, shards, k=k, ro=ro, gs=p["gs"], bs=p["bs"], S=p["S"],
        pc=p["pc"], n_packets=n_real // 32, hash_parity=hash_parity)
    digests = _digests_from_planes(
        planes, shards, parity, k=k, ro=ro, bs=p["bs"], S=p["S"], B=B,
        n_real=n_real, hash_parity=hash_parity)
    return parity[:B, :, :n], digests


def encode_with_bitrot_fused(data_blocks: int, parity_blocks: int,
                             blocks: np.ndarray,
                             matrix: np.ndarray | None = None):
    """rs_mesh.encode_with_bitrot's (parity, digests) contract through
    the single fused kernel — host numpy in, host numpy out, digests
    (B, k+m, 32) with data rows first."""
    blocks = np.asarray(blocks, dtype=np.uint8)
    if matrix is None:
        matrix = gf8.rs_matrix(data_blocks,
                               data_blocks + parity_blocks)
    rows = np.asarray(matrix)[data_blocks:]
    parity, digests = encode_hash_device(
        rows, blocks, hash_parity=True)
    return np.asarray(parity), np.asarray(digests)

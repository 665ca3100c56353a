#!/usr/bin/env python
"""North-star benchmark: RS encode/decode GiB/s per TPU chip (12+4, 1 MiB).

Mirrors the reference benchmark grid semantics (cmd/erasure-encode_test.go
b.SetBytes -> MB/s of *data* bytes processed) on the BASELINE.json headline
config: 12+4 erasure set, 1 MiB blockSize.

Methodology (honest-measurement rules):
  * iterations are DEPENDENT — each step's input is derived from the
    previous step's output inside one lax.fori_loop, so neither XLA nor
    the runtime can elide or overlap repeated identical dispatches;
  * the final result is checksummed ON HOST after timing, proving real
    bytes came out of the device;
  * a roofline sanity line reports achieved int8 TOPS against the chip's
    peak — a number over 100% means the harness is lying, not the chip.
  * the end-to-end number (BASELINE config 5: 256 x 4 MiB batched PUT)
    runs through the REAL put_object path — md5, erasure encode, bitrot
    framing, staged drive writes — but still on the HOST codec
    (backend="numpy"), and the device kernel numbers exclude host
    transfers: no leg here times the served path with the device codec.
    ``chip_smoke.py`` proves that path runs on the chip; timing it is
    ROADMAP S0.

Baseline: klauspost/reedsolomon AVX2 encode on one modern core ~= 6 GiB/s
(the reference's practical CPU bar, SURVEY.md §6); BASELINE.json's target
is >= 4x that. vs_baseline reported here is measured / 6.0.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import time
from functools import partial

import numpy as np

# the e2e leg measures the pipeline, not this VM's single ext4 disk: the
# reference's benchmarks don't fsync either (go test -bench has no sync).
# The metric key records whether fsync was actually on for the run.
os.environ.setdefault("MT_FSYNC", "0")
_FSYNC_ON = os.environ["MT_FSYNC"] not in ("0", "off", "false")

AVX2_BASELINE_GIBPS = 6.0

# int8 peak TOPS by TPU generation (public chip specs; used only for the
# roofline sanity line)
_PEAK_INT8_TOPS = {
    "v5 lite": 394.0,     # v5e
    "v5e": 394.0,
    "v4": 275.0,
    "v5p": 918.0,
    "v6": 918.0,
}


def _device_peak_tops(dev) -> float | None:
    name = str(dev).lower()
    for key, tops in _PEAK_INT8_TOPS.items():
        if key in name:
            return tops
    return None


def main() -> None:
    import jax
    import jax.numpy as jnp
    from minio_tpu.ops import gf8, rs_pallas

    k, m = 12, 4
    block_size = 1 << 20
    ss = gf8.shard_size(block_size, k)          # 87382
    GS = rs_pallas._GS
    ss_pad = ss + ((-ss) % rs_pallas._TN)       # kernel lane-tile multiple
    B = 64                                       # 64 MiB of data per step

    key = jax.random.PRNGKey(0)
    data = jax.random.randint(key, (B, k, ss_pad), 0, 256, dtype=jnp.uint8)
    data.block_until_ready()

    def bd_matrix(rows: np.ndarray) -> jax.Array:
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        return rs_pallas._device_matrix_bd(
            rows.tobytes(), rows.shape[0], rows.shape[1], GS)

    M = np.asarray(gf8.rs_matrix(k, k + m))
    enc_mat = bd_matrix(M[k:])
    # decode: BASELINE config 3 — 2 shards zeroed, reconstruct on device
    present = list(range(2, k + 2))              # lost shards 0,1; use 2..13
    dec_mat = bd_matrix(gf8.decode_rows(M, k, present, [0, 1]))
    # heal: BASELINE config 4 — 16-drive set, 3 shards offline
    present3 = list(range(3, k + 3))
    heal_mat = bd_matrix(gf8.decode_rows(M, k, present3, [0, 1, 2]))

    @partial(jax.jit, static_argnums=(2,))
    def chained(mat, d0, iters):
        """iters dependent coding steps: step i+1's input mixes step i's
        output back in (plus a counter so the chain never cycles),
        forming a data dependency no compiler or runtime can collapse —
        the round-1 harness measured elided dispatches and reported a
        physically impossible 1548 GiB/s.  The coding step is the fused
        pallas kernel (ops/rs_pallas.py): bytes in HBM, bit planes
        VMEM-only, GS stripes block-diagonal per MXU matmul."""

        def body(_, d):
            out = rs_pallas._gf2_apply_bm(mat, d, gs=GS)   # (B, r, n)
            r = out.shape[1]
            reps = -(-k // r)
            mix = jnp.tile(out, (1, reps, 1))[:, :k, :]
            return (d ^ mix) + jnp.uint8(1)

        return jax.lax.fori_loop(0, iters, body, d0)

    def timed(mat, iters, trials):
        best = float("inf")
        checksum = 0
        for _ in range(trials):
            t0 = time.perf_counter()
            out = chained(mat, data, iters)
            # HOST readback fences the device and proves real bytes
            # came back
            checksum = int(jnp.sum(out.astype(jnp.uint32)))
            best = min(best, time.perf_counter() - t0)
        assert checksum != 0, "device produced all-zero output"
        return best

    def marginal(t1, t2, iters, label):
        # never clamp: a non-positive marginal time means host noise
        # or a harness artifact — clamping would report impossible
        # throughput, exactly what this harness exists to prevent
        dt = (t2 - t1) / iters
        if dt <= 0:
            raise RuntimeError(
                f"{label}: non-positive marginal time ({t2:.4f}s for "
                f"2x iters vs {t1:.4f}s) — rerun on a quiet chip")
        return dt

    def bench(mat, iters=100, trials=3):
        # warm/compile both shapes, then time iters and 2*iters runs;
        # the MARGINAL time per step cancels dispatch + readback
        # overhead
        int(jnp.sum(chained(mat, data, iters).astype(jnp.uint32)))
        int(jnp.sum(chained(mat, data, 2 * iters).astype(jnp.uint32)))
        for attempt in range(3):
            t1 = timed(mat, iters, trials + attempt)
            t2 = timed(mat, 2 * iters, trials + attempt)
            if t2 > t1:
                break
        r = mat.shape[0] // (8 * GS)
        per_step = marginal(t1, t2, iters, f"bench(r={r})")
        macs = r * 8 * k * 8 * B * ss_pad          # int8 MACs per step
        tops = 2 * macs / per_step / 1e12
        return (B * block_size) / per_step / 2**30, tops

    def best_of(mat, rounds=3, settle=0.05):
        """Whole-leg best-of-N: single bench() invocations swung ~10%
        run to run (r3 51.2 / r4 50.5 / a same-run split-K control
        read 57.4); repeating the full warm+measure cycle and keeping
        the best absorbs that without touching the per-call
        marginal-time honesty gates.  Stops early when a round fails
        to improve by ``settle``."""
        best = (0.0, 0.0)
        for _ in range(rounds):
            g, t = bench(mat)
            if g <= best[0] * (1 + settle):
                best = max(best, (g, t))
                break
            best = max(best, (g, t))
        return best

    encode_gibps, enc_tops = best_of(enc_mat)
    decode_gibps, dec_tops = best_of(dec_mat)
    heal_gibps, heal_tops = best_of(heal_mat)
    # heal rate in shards/s: 3 shards rebuilt per stripe per step
    heal_shards_s = heal_gibps * 2**30 / block_size * 3

    # -- mesh-path parity: the SAME fused kernel through the shard_map
    # data-plane engine (ops/rs_mesh, 1x1 mesh = single-chip case).
    # Proves the multi-chip wiring costs ~nothing per chip; on real
    # multi-chip it scales by the mesh with ring-XOR ICI traffic.
    def bench_mesh() -> float:
        try:
            from minio_tpu.ops import rs_mesh
            from minio_tpu.parallel import mesh as pmesh
            mesh1 = pmesh.make_mesh(devices=jax.devices()[:1])
            fnm = rs_mesh._sharded_apply_pallas(
                mesh1, m, k, GS, rs_pallas._TN, False)
            mats = enc_mat[None]            # S=1: one column slice

            @partial(jax.jit, static_argnums=(1,))
            def chained_mesh(d0, iters):
                def body(_, d):
                    out = fnm(mats, d)
                    reps = -(-k // out.shape[1])
                    mix = jnp.tile(out, (1, reps, 1))[:, :k, :]
                    return (d ^ mix) + jnp.uint8(1)
                return jax.lax.fori_loop(0, iters, body, d0)

            def timed_m(iters, trials):
                best = float("inf")
                for _ in range(trials):
                    t0 = time.perf_counter()
                    out = chained_mesh(data, iters)
                    checksum = int(jnp.sum(out.astype(jnp.uint32)))
                    best = min(best, time.perf_counter() - t0)
                assert checksum != 0
                return best

            iters = 100
            int(jnp.sum(chained_mesh(data, iters).astype(jnp.uint32)))
            int(jnp.sum(chained_mesh(data, 2 * iters)
                        .astype(jnp.uint32)))
            t1 = timed_m(iters, 3)
            t2 = timed_m(2 * iters, 3)
            per = marginal(t1, t2, iters, "mesh")
            return (B * block_size) / per / 2**30
        except Exception as e:  # noqa: BLE001 — optional leg
            import sys as _sys
            print(f"mesh leg failed: {e!r}", file=_sys.stderr)
            return 0.0

    mesh_gibps = bench_mesh()

    dev = jax.devices()[0]
    peak = _device_peak_tops(dev)
    roofline_pct = round(100 * enc_tops / peak, 1) if peak else None
    # the harness's own credibility gate: >100% of chip peak = broken.
    # Every measured leg is gated, not just encode.
    if peak:
        for label, tops in [("encode", enc_tops), ("decode", dec_tops),
                            ("heal", heal_tops)]:
            assert tops <= peak, (
                f"{label}: measured {tops:.1f} TOPS exceeds {peak} TOPS "
                "peak — harness artifact")

    # fused encode + on-device HighwayHash (bit-identical digests):
    # one pipeline emits parity AND per-shard bitrot digests.  The hash
    # is the single-kernel pallas formulation (ops/hh_pallas.py) — the
    # lax.scan version pays per-op dispatch latency 2732x per batch and
    # measures ~4x slower

    from minio_tpu.ops import hh_pallas

    # fused batch: 256 stripes -> data (3072 shards) and parity (1024)
    # are exact 1024-shard tile multiples, so neither hash leg pads
    BF = 256
    fdata = jax.random.randint(jax.random.PRNGKey(1), (BF, k, ss_pad),
                               0, 256, dtype=jnp.uint8)
    fdata.block_until_ready()

    @partial(jax.jit, static_argnums=(1,))
    def fused_chained(d0, iters):
        def body(_, carry):
            d, hacc = carry
            par = rs_pallas._gf2_apply_bm(enc_mat, d, gs=GS)
            # hash data and parity as separate batches: digests are
            # per-shard, so materializing a concatenated (BF*16, n)
            # array first would cost a full extra HBM round trip
            hd = hh_pallas.hh256_batch(d.reshape(BF * k, ss_pad))
            hp_ = hh_pallas.hh256_batch(par.reshape(BF * m, ss_pad))
            # XOR-reduce ALL digests into the carry: every one of the
            # BF*(k+m) hashes is live, none can be narrowed away by XLA
            hall = jax.lax.reduce(hd, jnp.uint8(0),
                                  jax.lax.bitwise_xor, (0,)) ^ \
                jax.lax.reduce(hp_, jnp.uint8(0),
                               jax.lax.bitwise_xor, (0,))
            # chain: next input folds the digest XOR into every packet
            # of d — step i+1 depends on EVERY byte of step i's data,
            # parity and digests (stronger than mixing parity tiles,
            # and one full HBM round trip cheaper)
            mixed = d.reshape(BF, k, ss_pad // 32, 32) ^ hall
            return mixed.reshape(BF, k, ss_pad), hacc ^ hall

        return jax.lax.fori_loop(0, iters, body,
                                 (d0, jnp.zeros(32, jnp.uint8)))

    def fused_timed(iters, trials=3):
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            d_out, h_out = fused_chained(fdata, iters)
            s = int(jnp.sum(h_out.astype(jnp.uint32)))   # host fence
            best = min(best, time.perf_counter() - t0)
        assert s != 0
        return best

    fiters = 12
    fused_chained(fdata, fiters)[1].block_until_ready()      # compile
    fused_chained(fdata, 2 * fiters)[1].block_until_ready()
    # best-of-rounds like the headline legs: a gated-but-stable reading
    # taken in a bad-weather window once recorded 1.4 GiB/s while heal
    # measured 79 in the same run — keep the best VALID round rather
    # than the first
    fused_best = 0.0
    fdt_best = 0.0
    for attempt in range(5):
        ft1 = fused_timed(fiters, trials=3 + attempt)
        ft2 = fused_timed(2 * fiters, trials=3 + attempt)
        fdt = (ft2 - ft1) / fiters
        fused_gibps = (BF * block_size) / fdt / 2**30 if fdt > 0 else -1
        # physical gate: the fused step is a superset of the encode
        # step (same matmul + two hash kernels), so it cannot beat the
        # encode-only rate.  A reading above it is marginal-time noise
        # (fiters=4 once reported an impossible 610 GiB/s) — retry.
        # Margin 1.2: encode and fused are measured minutes apart and
        # legs have swung ±20% between runs; a real elision artifact
        # overshoots by 10x, not 10%.
        if 0 < fused_gibps <= encode_gibps * 1.2:
            if fused_gibps > fused_best:
                fused_best, fdt_best = fused_gibps, fdt
            # stop early once a round lands in the normal band (>= 60%
            # of encode — the pipeline adds two hash kernels, not a
            # 10x slowdown); otherwise keep trying for a quiet window
            if fused_best >= encode_gibps * 0.6 or attempt == 4:
                break
    if fused_best <= 0:
        reason = ("non-positive marginal time (elided dispatch or "
                  "host noise)" if fdt <= 0 else
                  f"{fused_gibps:.1f} GiB/s exceeds the encode-only "
                  f"rate {encode_gibps:.1f}")
        raise RuntimeError(f"fused: unstable marginal — {reason}; "
                           "rerun on a quiet chip")
    fused_gibps, fdt = fused_best, fdt_best
    if peak:   # fused leg contains the encode matmul — same gate
        fused_tops = 2 * (m * 8 * k * 8 * BF * ss_pad) / fdt / 1e12
        assert fused_tops <= peak, (
            f"fused: {fused_tops:.1f} TOPS exceeds {peak} peak — "
            "harness artifact")

    # -- single-kernel fused formulation (ops/rs_fused.py): the hash
    # prologue consumes encode's VMEM-resident tiles, so the operand
    # crosses HBM once (D in + P out, the information-theoretic
    # minimum) instead of twice.  Measured with the same chained
    # dependent-iteration + marginal-time discipline; the two-kernel
    # number above stays as the proven fallback and the HEADLINE
    # fused_encode_hh256_GiBps takes the best VALID of the two.
    def bench_fused_single() -> float | str:
        try:
            from minio_tpu.ops import rs_fused
            p6 = rs_fused.plan(BF, k, m, ss_pad)
            assert p6["B_pad"] == BF and p6["n_pad"] == ss_pad and \
                p6["gs"] == GS, p6

            @partial(jax.jit, static_argnums=(1,))
            def single_chained(d0, iters):
                def body(_, carry):
                    d, hacc = carry
                    par, planes = rs_fused._fused_call(
                        enc_mat, d, k=k, ro=m, gs=GS, bs=p6["bs"],
                        S=p6["S"], pc=p6["pc"],
                        n_packets=ss_pad // 32, hash_parity=True)
                    digs = rs_fused._digests_from_planes(
                        planes, d, par, k=k, ro=m, bs=p6["bs"],
                        S=p6["S"], B=BF, n_real=ss_pad,
                        hash_parity=True)
                    hall = jax.lax.reduce(
                        digs.reshape(BF * (k + m), 32), jnp.uint8(0),
                        jax.lax.bitwise_xor, (0,))
                    mixed = d.reshape(BF, k, ss_pad // 32, 32) ^ hall
                    return mixed.reshape(BF, k, ss_pad), hacc ^ hall

                return jax.lax.fori_loop(
                    0, iters, body, (d0, jnp.zeros(32, jnp.uint8)))

            def single_timed(iters, trials=3):
                best = float("inf")
                for _ in range(trials):
                    t0 = time.perf_counter()
                    _, h_out = single_chained(fdata, iters)
                    s = int(jnp.sum(h_out.astype(jnp.uint32)))
                    best = min(best, time.perf_counter() - t0)
                assert s != 0
                return best

            single_chained(fdata, fiters)[1].block_until_ready()
            single_chained(fdata, 2 * fiters)[1].block_until_ready()
            best = 0.0
            for attempt in range(5):
                t1 = single_timed(fiters, trials=3 + attempt)
                t2 = single_timed(2 * fiters, trials=3 + attempt)
                dt = (t2 - t1) / fiters
                g = (BF * block_size) / dt / 2**30 if dt > 0 else -1
                if 0 < g <= encode_gibps * 1.2:
                    best = max(best, g)
                    if best >= encode_gibps * 0.6 or attempt == 4:
                        break
            if best <= 0:
                return "unstable marginal (see two-kernel leg)"
            return best
        except Exception as e:  # noqa: BLE001 — optional formulation
            import sys as _sys
            print(f"fused single-kernel leg failed: {e!r}",
                  file=_sys.stderr)
            return f"{type(e).__name__}: {e}"

    fused_single = bench_fused_single()
    fused_two_kernel = fused_gibps
    if isinstance(fused_single, float) and fused_single > fused_gibps:
        fused_gibps = fused_single

    e2e = _bench_end_to_end_put()
    cfg12 = _bench_baseline_configs()
    codec_batching = _bench_codec_batching()

    value = round(min(encode_gibps, decode_gibps), 2)
    result = {
        "metric": "rs_encode_decode_GiBps_12+4_1MiB",
        "value": value,
        "unit": "GiB/s",
        "vs_baseline": round(value / AVX2_BASELINE_GIBPS, 2),
        "detail": {
            "encode_GiBps": round(encode_gibps, 2),
            "decode2_GiBps": round(decode_gibps, 2),
            "heal3_GiBps": round(heal_gibps, 2),
            "heal_shards_per_s": round(heal_shards_s, 1),
            # fused = pallas encode -> pallas byte-plane hash, TWO
            # kernels total: the byte-plane transpose is the hash
            # kernel's in-VMEM prologue (ops/hh_pallas._kernel_nat), so
            # the operand crosses HBM once.  r3's standalone transpose
            # kernel cost a full extra HBM round trip (~2 ms/340 MiB
            # step) and capped the pipeline at 20.65; removing it
            # measured 33.6 GiB/s (bar: >= 24).
            "fused_encode_hh256_GiBps": round(fused_gibps, 2),
            # the roofline target (ISSUE 12): fused within ~15% of
            # plain encode means ratio >= ~0.85
            "fused_vs_plain_ratio": round(fused_gibps / encode_gibps, 3)
            if encode_gibps > 0 else None,
            "fused_two_kernel_GiBps": round(fused_two_kernel, 2),
            "fused_single_kernel_GiBps": (
                round(fused_single, 2)
                if isinstance(fused_single, float) else fused_single),
            # the data-plane mesh engine (shard_map + pallas + ring
            # XOR) on a 1x1 mesh: per-chip cost of the multi-chip
            # wiring relative to encode_GiBps (the direct kernel)
            "mesh_1chip_pallas_GiBps": round(mesh_gibps, 2),
            ("e2e_put_256x4MiB_fsync" if _FSYNC_ON
             else "e2e_put_256x4MiB_nofsync"): e2e,
            # driver BASELINE configs 1 + 2 as FIRST-CLASS rows (the
            # two weakest driver-tracked numbers must not hide in a
            # nested dict), measured end to end through the real
            # object layer (r4 verdict #2); the full sub-report with
            # methodology keeps its slot below
            "config1_4+2_put_64MiB_GiBps": (cfg12 or {}).get(
                "config1_4+2_put_64MiB_GiBps"),
            "config2_8+4_multipart_1GiB_GiBps": (cfg12 or {}).get(
                "config2_8+4_multipart_1GiB_GiBps"),
            "baseline_configs_1_2": cfg12,
            # cross-request batching codec service (ISSUE 9): aggregate
            # GiB/s + occupancy at 1/4/16/64 concurrent streams vs the
            # serial per-request dispatch baseline
            "codec_batching": codec_batching,
            "achieved_int8_TOPS": round(enc_tops, 1),
            "decode_int8_TOPS": round(dec_tops, 1),
            "roofline_pct_of_peak": roofline_pct,
            # roofline_pct counts LOGICAL MACs (r*8 x k*8 bit-matrix).
            # The kernel is MXU-slot-bound, not HBM-bound: bit planes
            # never leave VMEM (HBM traffic is 1.33x data, vs 9x for
            # the old XLA formulation), a no-matmul kernel variant
            # sustains ~116 GiB/s (the VPU unpack + HBM legs), and the
            # MXU executes the padded 128-slot tiles — diag(E,E,E,E)
            # packs M=128/K=384 exactly (GS=4); measured slot rate is
            # ~90% of the practical int8->int32 MXU rate under the
            # serial VPU->MXU dependency.  Four structured attempts at
            # breaking that dependency all measured negative and were
            # dropped: bf16 feed (39), ping-pong VMEM software
            # pipelining (44), split-K partial dots interleaved with
            # per-stripe unpack (r4: 45.7 vs 57.4 baseline same run;
            # the extra int32 accumulator adds outweigh any VPU/MXU
            # overlap), and int8-native unpack (not legalizable: the
            # VPU is a 32-bit-lane machine, Mosaic has no i8 vector
            # shift — arith.shrsi/shrui on vector<...xi8> fail, so the
            # int32 widening in the unpack is a hardware floor).
            "kernel": "pallas fused unpack+matmul+pack, GS=4 "
                      "block-diagonal, bit planes VMEM-only",
            "methodology": "chained dependent iterations, host checksum",
            "device": str(dev),
            "baseline": f"klauspost AVX2 ~{AVX2_BASELINE_GIBPS} GiB/s/core",
        },
    }
    print(json.dumps(result))


def _bench_baseline_configs() -> dict | None:
    """Driver BASELINE configs 1 and 2, end to end through the real
    object layer on tmpfs drives (pipeline rate without the throttled
    virtio disk; see _bench_end_to_end_put's hardware controls):

      1. 4+2 set, 1 MiB blockSize, single 64 MiB object PUT
         (cmd/erasure-encode_test.go:209-248's geometry driven through
         putObject, cmd/erasure-object.go:614)
      2. 8+4 set, 1 MiB blocks, 1 GiB multipart PutObject —
         NewMultipartUpload -> 64 x 16 MiB PutObjectPart ->
         CompleteMultipartUpload (cmd/erasure-multipart.go:342)

    Methodology: strict-compat mode (md5 ETag, the client default),
    fresh object names per iteration (no page recycling), and a host
    md5 GET round-trip check on the final object of each leg.
    """
    import hashlib
    import os
    import shutil
    import sys
    import tempfile
    import time

    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage.xl_storage import XLStorage

    if not (os.path.isdir("/dev/shm") and os.access("/dev/shm", os.W_OK)):
        return None
    prev = os.environ.get("MT_NO_COMPAT")
    os.environ["MT_NO_COMPAT"] = "0"                # strict compat
    root = None
    try:
        root = tempfile.mkdtemp(prefix="bench-cfg-", dir="/dev/shm")

        def mk(n, parity, sub):
            ds = []
            for i in range(n):
                d = os.path.join(root, sub, f"d{i}")
                os.makedirs(d)
                ds.append(XLStorage(d))
            lay = ErasureObjects(ds, parity=parity, block_size=1 << 20,
                                 backend="numpy")
            lay.make_bucket("cfgbkt")
            return lay

        out = {}

        # best-of-N policy: the 1-vCPU VM shares its core with the
        # harness; a single timing can land in a contention window
        # (observed 4x swings run to run)
        # -- config 1: 4+2, single 64 MiB PUT ----------------------------
        lay1 = mk(6, 2, "c1")
        body = os.urandom(64 * (1 << 20))
        lay1.put_object("cfgbkt", "warm", body)     # warm the code path
        best1 = 0.0
        for r in range(3):
            t0 = time.perf_counter()
            for i in range(4):
                lay1.put_object("cfgbkt", f"o{r}-{i}", body)
            dt = (time.perf_counter() - t0) / 4
            best1 = max(best1, len(body) / dt / 2**30)
            if r == 0:
                got = lay1.get_object("cfgbkt", "o0-3")[1]
                assert hashlib.md5(bytes(got)).digest() == \
                    hashlib.md5(body).digest(), \
                    "config1 round-trip mismatch"
            # bound tmpfs usage: delete each round's objects after
            # timing (fresh names keep page allocation honest; the
            # deletes are outside the timed window)
            for i in range(4):
                lay1.delete_object("cfgbkt", f"o{r}-{i}")
        out["config1_4+2_put_64MiB_GiBps"] = round(best1, 3)
        shutil.rmtree(os.path.join(root, "c1"), ignore_errors=True)

        # -- config 2: 8+4, 1 GiB multipart ------------------------------
        lay2 = mk(12, 4, "c2")
        part = os.urandom(16 * (1 << 20))           # 64 parts x 16 MiB
        nparts = 64

        def one_multipart(name):
            uid = lay2.new_multipart_upload("cfgbkt", name)
            etags = []
            for pn in range(1, nparts + 1):
                pi = lay2.put_object_part("cfgbkt", name, uid, pn, part)
                etags.append((pn, pi.etag))
            return lay2.complete_multipart_upload("cfgbkt", name, uid,
                                                  etags)

        one_multipart("mpwarm")                     # warm
        lay2.delete_object("cfgbkt", "mpwarm")      # bound tmpfs usage
        best2 = 0.0
        for r in range(2):
            t0 = time.perf_counter()
            oi = one_multipart(f"mpbig{r}")
            dt = time.perf_counter() - t0
            assert oi.size == nparts * len(part)
            best2 = max(best2, nparts * len(part) / dt / 2**30)
            got0 = lay2.get_object("cfgbkt", f"mpbig{r}", offset=0,
                                   length=len(part))[1]
            assert hashlib.md5(bytes(got0)).digest() == \
                hashlib.md5(part).digest(), "config2 round-trip mismatch"
            lay2.delete_object("cfgbkt", f"mpbig{r}")
        out["config2_8+4_multipart_1GiB_GiBps"] = round(best2, 3)
        out["methodology"] = ("strict compat (md5 ETag), tmpfs drives, "
                              "fresh names, host-md5 round-trip check")
        return out
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"baseline-config leg failed: {e!r}", file=sys.stderr)
        return None
    finally:
        if prev is None:
            os.environ.pop("MT_NO_COMPAT", None)
        else:
            os.environ["MT_NO_COMPAT"] = prev
        if root:
            shutil.rmtree(root, ignore_errors=True)


def _bench_md5_lanes(body: bytes) -> dict | None:
    """Native multi-lane MD5 sweep (ISSUE 6): single-stream native rate
    plus aggregate throughput of N concurrent streams sharing the lane
    scheduler at ``pipeline.md5_lanes`` = N — the new strict-ETag
    ceiling for concurrent PUTs/multipart parts.  Returns
    {md5_native_GiBps, md5_hashlib_GiBps, lanes: {N: aggregate}}."""
    import threading

    from minio_tpu.hashing import md5fast
    if not md5fast.available():
        return None
    obj_size = len(body)

    def rate(fn, streams=1, reps=6) -> float:
        fn()                                        # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            ts = [threading.Thread(target=fn) for _ in range(streams)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        dt = time.perf_counter() - t0
        return reps * streams * obj_size / dt / 2**30

    import hashlib as _hl
    out = {
        "md5_hashlib_GiBps": round(
            rate(lambda: _hl.md5(body)), 3),
        "md5_native_GiBps": round(
            rate(lambda: md5fast.MD5Fast(body)), 3),
        "lanes_aggregate_GiBps": {},
    }

    def one_sched():
        h = md5fast.md5()
        mv = memoryview(body)
        for off in range(0, obj_size, md5fast.ONESHOT_SLICE):
            md5fast.SCHED.update(h, mv[off:off + md5fast.ONESHOT_SLICE])

    try:
        for lanes in (1, 2, 4, 8):
            md5fast.SCHED.set_lanes(lanes)
            out["lanes_aggregate_GiBps"][str(lanes)] = round(
                rate(one_sched, streams=lanes, reps=4), 3)
    finally:
        md5fast.SCHED.set_lanes(4)

    # device multi-buffer MD5 (hashing/md5_device.py): the probed
    # end-to-end device rate (transfer included), the aggregate of 4
    # concurrent streams
    # through the md5 combining bucket, and which rung ``auto``
    # actually resolved to on THIS host — the calibration decision the
    # pipeline.md5_backend ladder rides
    try:
        from minio_tpu.hashing import md5_device
        from minio_tpu.parallel import batcher as _bt
        if md5_device.available():
            out["md5_device_probe_GiBps"] = round(
                md5_device.device_rate_gibps(), 3)

            def one_dev():
                h = md5_device.MD5Device()
                mv = memoryview(body)
                for off in range(0, obj_size, md5fast.ONESHOT_SLICE):
                    h.update(mv[off:off + md5fast.ONESHOT_SLICE])
                h.digest()

            s0 = _bt.MD5_GLOBAL.snapshot()
            out["md5_device_4stream_GiBps"] = round(
                rate(one_dev, streams=4, reps=2), 3)
            s1 = _bt.MD5_GLOBAL.snapshot()
            disp = s1["dispatches"] - s0["dispatches"]
            reqs = s1["requests"] - s0["requests"]
            out["md5_device_occupancy"] = round(reqs / disp, 1) \
                if disp else None
        else:
            out["md5_device_probe_GiBps"] = None
            out["md5_device_unavailable"] = \
                md5_device.unavailable_reason()
        # the auto probe runs on a background thread (first-PUT
        # latency protection); the bench wants the SETTLED decision —
        # but only an actual ``auto`` resolution has one to wait for
        # (a pinned rung never starts a probe)
        choice = md5fast._resolve_backend()
        env_pin = (os.environ.get("MT_MD5") or "").strip().lower()
        if md5fast._BACKEND == "auto" and \
                env_pin not in ("device", "native", "hashlib"):
            for _ in range(200):
                if md5fast._AUTO_CHOICE is not None:
                    break
                time.sleep(0.05)
        out["md5_backend_auto_choice"] = md5fast._AUTO_CHOICE or choice
    except Exception as e:  # noqa: BLE001 — optional sub-leg
        import sys as _sys
        print(f"md5 device leg failed: {e!r}", file=_sys.stderr)
    return out


def _bench_stream_chunks(body: bytes, base_dir: str | None) -> dict | None:
    """Internode streaming sweep (ISSUE 6): one remote drive behind a
    real loopback RPC, whole-shard create_file at each
    ``rpc.stream_chunk_bytes`` setting (off = the materialized raw
    call) — makes the frame-size knob's cost/benefit driver-visible."""
    import shutil
    import tempfile

    from minio_tpu.parallel.rpc import STREAM, RPCClient, RPCServer
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    from minio_tpu.storage.xl_storage import XLStorage
    root = tempfile.mkdtemp(prefix="bench-stream-", dir=base_dir)
    rpc = None
    prev = (STREAM.enable, STREAM.chunk_bytes, STREAM._loaded)
    try:
        dpath = os.path.join(root, "rd")
        os.makedirs(dpath)
        drive = XLStorage(dpath)
        drive.make_vol("benchvol")
        rpc = RPCServer("benchsecret")
        register_storage_service(rpc, {"rd": drive})
        rpc.start()
        r = RemoteStorage(RPCClient(rpc.endpoint, "benchsecret"), "rd")
        out = {}
        seq = [0]
        for label, chunk in (("off", 0), ("2MiB", 2 << 20),
                             ("1MiB", 1 << 20), ("256KiB", 256 << 10)):
            STREAM.enable = chunk > 0
            STREAM.chunk_bytes = chunk or (1 << 20)
            STREAM._loaded = True
            reps = 8

            def put():
                seq[0] += 1
                r.create_file("benchvol", f"s-{seq[0]}", body,
                              file_size=len(body))
            put()                                    # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                put()
            dt = time.perf_counter() - t0
            out[label] = round(reps * len(body) / dt / 2**30, 3)
        return out
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys
        print(f"stream-chunk leg failed: {e!r}", file=sys.stderr)
        return None
    finally:
        STREAM.enable, STREAM.chunk_bytes, STREAM._loaded = prev
        if rpc is not None:
            rpc.stop()
        shutil.rmtree(root, ignore_errors=True)


def _bench_codec_batching() -> dict | None:
    """Cross-request batching sweep (ISSUE 9): aggregate encode GiB/s
    of N concurrent small-object streams through the shared codec
    batcher (parallel/batcher.py) vs the serial per-request dispatch
    baseline, same geometry and hardware, plus the realized dispatch
    occupancy — the concurrent-user throughput the batching codec
    service converts idle device headroom into."""
    import threading as _th

    try:
        from minio_tpu.ops.codec import Erasure
        from minio_tpu.parallel import batcher
        from minio_tpu.parallel import mesh as pmesh
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys as _sys
        print(f"codec-batching leg failed to import: {e!r}",
              file=_sys.stderr)
        return None
    cfg = batcher.CONFIG
    saved = (cfg.enable, cfg.window_s, cfg.max_blocks,
             cfg.queue_depth, cfg._loaded)
    prev_mesh = pmesh._ACTIVE
    try:
        # the shared-mesh topology the batching service exists for:
        # stripe-axis (batch) parallelism over every visible device —
        # concurrent small-object encodes from many "frontend" threads
        # share ONE mesh through the combining queue, per-request
        # dispatches pay the shard_map/pjit launch cost per call
        pmesh.set_active_mesh(pmesh.make_mesh())
        k, m, bs = 12, 4, 64 * 1024
        obj = os.urandom(bs)                # small object: one block
        codec = Erasure(k, m, bs, backend="mesh")
        window_us = 1000                    # ~launch-latency sized
        cfg.max_blocks, cfg.queue_depth = 512, 4096
        cfg._loaded = True

        def leg(enabled: bool, streams: int) -> tuple[float, float]:
            cfg.enable = enabled
            cfg.window_s = window_us / 1e6
            reps = max(4, 64 // streams)    # ~constant total work
            codec.encode_object(obj)        # warm path / compile
            best, occ_best = 0.0, 1.0
            for _ in range(2):              # best-of-2: thread-start
                s0 = batcher.GLOBAL.snapshot()   # jitter swings legs
                barrier = _th.Barrier(streams + 1)

                def run():
                    barrier.wait()
                    for _ in range(reps):
                        codec.encode_object(obj)

                ths = [_th.Thread(target=run,
                                  name=f"mt-codec-bench{i}")
                       for i in range(streams)]
                for t in ths:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in ths:
                    t.join()
                dt = max(time.perf_counter() - t0, 1e-9)
                s1 = batcher.GLOBAL.snapshot()
                reqs = s1["requests"] - s0["requests"]
                disp = s1["dispatches"] - s0["dispatches"]
                gibps = streams * reps * len(obj) / dt / 2**30
                if gibps > best:
                    best = gibps
                    occ_best = (reqs / disp) if (enabled and disp) \
                        else 1.0
            return best, occ_best

        out = {"geometry": f"{k}+{m} x {bs // 1024}KiB blocks",
               "object_bytes": len(obj), "backend": "mesh",
               "mesh_devices": int(np.prod(list(
                   pmesh.get_active_mesh().shape.values()))),
               "batch_window_us": window_us, "streams": {}}
        for streams in (1, 4, 16, 64):
            serial_gibps, _ = leg(False, streams)
            batched_gibps, occ = leg(True, streams)
            out["streams"][str(streams)] = {
                "serial_GiBps": round(serial_gibps, 4),
                "batched_GiBps": round(batched_gibps, 4),
                "speedup": round(batched_gibps / serial_gibps, 2)
                if serial_gibps > 0 else None,
                "occupancy": round(occ, 1),
            }
        out["speedup_16"] = out["streams"]["16"]["speedup"]
        return out
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys as _sys
        print(f"codec-batching leg failed: {e!r}", file=_sys.stderr)
        return None
    finally:
        (cfg.enable, cfg.window_s, cfg.max_blocks, cfg.queue_depth,
         cfg._loaded) = saved
        pmesh.set_active_mesh(prev_mesh)


def _bench_hot_get() -> dict | None:
    """Hot-read plane sweep (ISSUE 14): aggregate GET GiB/s of N
    concurrent readers over a zipf-distributed key set through the
    REAL erasure layer, single-flight+cache plane ON vs the
    per-request path, bodies digest-checked bit-identical.  The
    acceptance bar: >=3x aggregate at 64 concurrent readers of one
    hot object."""
    import hashlib as _hl
    import random as _random
    import shutil
    import tempfile
    import threading as _th

    try:
        from minio_tpu.objectlayer import hotread
        from minio_tpu.objectlayer.erasure_object import ErasureObjects
        from minio_tpu.storage.xl_storage import XLStorage
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys as _sys
        print(f"hot-get leg failed to import: {e!r}", file=_sys.stderr)
        return None
    cfg = hotread.CONFIG
    saved = (cfg.enable, cfg.max_bytes, cfg.heat_threshold,
             cfg.singleflight_queue, cfg.window_bytes, cfg._loaded)
    root = "/dev/shm" if os.path.isdir("/dev/shm") and \
        os.access("/dev/shm", os.W_OK) else None
    tmp = tempfile.mkdtemp(prefix="hotget-", dir=root)
    try:
        disks = []
        for i in range(6):
            d = os.path.join(tmp, f"d{i}")
            os.makedirs(d)
            disks.append(XLStorage(d))
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        layer.make_bucket("hot")
        key_space, zipf = 8, 1.2
        obj_bytes = 1 << 20
        rng = _random.Random(7)
        digests = {}
        for i in range(key_space):
            body = rng.randbytes(obj_bytes)
            layer.put_object("hot", f"o{i}", body)
            digests[f"o{i}"] = _hl.md5(body).hexdigest()
        weights = [1.0 / (i + 1) ** zipf for i in range(key_space)]
        cfg.max_bytes, cfg.heat_threshold = 256 << 20, 1
        cfg.singleflight_queue, cfg.window_bytes = 64, 8 << 20
        cfg._loaded = True
        layer.hotread.heat_fn = lambda: 1000

        def leg(enabled: bool, streams: int) -> float:
            cfg.enable = enabled
            layer.hotread.clear()
            reps = max(4, 96 // streams)    # ~constant total work
            layer.get_object("hot", "o0")   # warm drives/codec
            best = 0.0
            for _ in range(2):              # best-of-2: thread jitter
                barrier = _th.Barrier(streams + 1)
                bad: list = []

                def run(wid: int):
                    r = _random.Random(100 + wid)
                    barrier.wait()
                    for _ in range(reps):
                        k = f"o{r.choices(range(key_space), weights=weights)[0]}"
                        _, data = layer.get_object("hot", k)
                        if _hl.md5(data).hexdigest() != digests[k]:
                            bad.append(k)   # bit-identity is the bar
                            return

                ths = [_th.Thread(target=run, args=(i,),
                                  name=f"mt-hotget-bench{i}")
                       for i in range(streams)]
                for t in ths:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in ths:
                    t.join()
                dt = max(time.perf_counter() - t0, 1e-9)
                if bad:
                    raise AssertionError(
                        f"hot-get body mismatch on {bad[0]}")
                best = max(best,
                           streams * reps * obj_bytes / dt / 2**30)
            return best

        out = {"geometry": "4+2 x 64KiB blocks",
               "object_bytes": obj_bytes, "key_space": key_space,
               "zipf": zipf, "drives_root": root or "disk",
               "streams": {}}
        for streams in (1, 16, 64):
            serial = leg(False, streams)
            hot = leg(True, streams)
            st = layer.hotread.stats()
            out["streams"][str(streams)] = {
                "per_request_GiBps": round(serial, 3),
                "hot_plane_GiBps": round(hot, 3),
                "speedup": round(hot / serial, 2) if serial > 0
                else None,
                "cache_hits": st["cache"]["hits"],
                "coalesced": st["singleflight"]["coalesced"],
            }
        out["speedup_64"] = out["streams"]["64"]["speedup"]
        return out
    except AssertionError:
        # a body digest mismatch is a CORRECTNESS regression, not an
        # unavailable leg — fail the bench loudly
        raise
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys as _sys
        print(f"hot-get leg failed: {e!r}", file=_sys.stderr)
        return None
    finally:
        (cfg.enable, cfg.max_bytes, cfg.heat_threshold,
         cfg.singleflight_queue, cfg.window_bytes, cfg._loaded) = saved
        shutil.rmtree(tmp, ignore_errors=True)


def hot_get_main() -> None:
    """``bench.py hot_get`` — run the hot-read plane sweep standalone
    and print ONE BENCH_*-shaped JSON line."""
    stats = _bench_hot_get()
    if stats is None:
        raise SystemExit("hot_get leg unavailable")
    print(json.dumps({
        "metric": "hot_get_speedup_64_readers",
        "value": stats["speedup_64"],
        "unit": "x vs per-request GET path",
        "detail": stats,
    }))


def codec_batching_main() -> None:
    """``bench.py codec_batching`` — run the cross-request batching
    sweep standalone and print ONE BENCH_*-shaped JSON line."""
    stats = _bench_codec_batching()
    if stats is None:
        raise SystemExit("codec_batching leg unavailable")
    print(json.dumps({
        "metric": "codec_batching_speedup_16_streams",
        "value": stats["speedup_16"],
        "unit": "x vs serial per-request dispatch",
        "detail": stats,
    }))


def _bench_end_to_end_put() -> dict | None:
    """BASELINE config 5 end to end: 256 x 4 MiB PUTs through the REAL
    put_object pipeline (erasure encode + bitrot framing + staged
    writes + quorum commit; fsync per MT_FSYNC, default off to match
    go test -bench semantics), host codec (see module docstring for why
    the device codec is excluded here).  Two legs matching the
    reference's two modes: strict compat (md5 ETag, the default) and
    --no-compat (md5 skipped, random ETag — the reference's own
    perf-testing mode, cmd/common-main.go:208).  Plus a per-stage
    breakdown so the remaining cost is attributable."""
    import os
    import shutil
    import sys
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    tmp = None
    try:
        import hashlib

        from minio_tpu.hashing import bitrot as hbitrot
        from minio_tpu.objectlayer.erasure_object import ErasureObjects
        from minio_tpu.storage.xl_storage import XLStorage

        def mk_layer(base_dir=None):
            root = tempfile.mkdtemp(prefix="bench-e2e-", dir=base_dir)
            ds = []
            for i in range(16):
                d = os.path.join(root, f"d{i}")
                os.makedirs(d)
                ds.append(XLStorage(d))
            lay = ErasureObjects(ds, parity=4, block_size=1 << 20,
                                 backend="numpy")
            lay.make_bucket("benchbkt")
            return root, lay

        tmp, layer = mk_layer()
        n_obj, obj_size = 256, 4 * (1 << 20)
        body = os.urandom(obj_size)
        gib = n_obj * obj_size / 2**30

        # hardware control: raw sequential buffered write + sync on the
        # SAME filesystem, one plain file, no pipeline at all.  This VM's
        # virtio disk is cgroup-throttled: the kernel's dirty throttling
        # clamps sustained buffered writers to the device rate almost
        # immediately, so the disk legs below are bounded by this number
        # x (data/(data+parity)) no matter how fast the pipeline is.  It
        # also explains the r3 strict>nocompat inversion: the FASTER
        # writer hits balance_dirty_pages sooner and harder.
        def raw_disk_gibps() -> float:
            import tempfile as _tf
            blk = body[:4 * (1 << 20)]
            os.sync()
            fd, path = _tf.mkstemp(prefix="bench-raw-", dir=tmp)
            n = 0
            t0 = time.perf_counter()
            try:
                while n < 512 * (1 << 20):
                    os.write(fd, blk)
                    n += len(blk)
                os.close(fd)
                os.sync()                       # include the flush
                return n / (time.perf_counter() - t0) / 2**30
            finally:
                os.unlink(path)

        raw_gibps = raw_disk_gibps()

        def drain():
            # writeback of a previous leg's ~1.4 GiB steals the one
            # vCPU mid-run (run-to-run swings of 2-4x measured) — flush
            # and WAIT until dirty pages are actually gone before timing
            import re
            os.sync()
            for _ in range(90):
                try:
                    with open("/proc/meminfo") as f:
                        mi = f.read()
                    dirty = int(re.search(r"Dirty:\s+(\d+)",
                                          mi).group(1))
                    wb = int(re.search(r"Writeback:\s+(\d+)",
                                       mi).group(1))
                except (OSError, AttributeError):  # non-Linux host
                    return
                if dirty + wb < 200 * 1024:        # kB
                    break
                time.sleep(1)

        # ---- stage table (single-thread, per-stage, same code paths the
        # put pipeline calls) -------------------------------------------
        codec = layer._codec_for(4)
        reps = 12

        def stage(fn):
            fn()                                   # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                fn()
            return (time.perf_counter() - t0) / reps * 1000  # ms/obj

        ss = codec.shard_size()
        t_md5 = stage(lambda: hashlib.md5(body))
        framed2d = codec.encode_object_framed(body)
        t_encode = stage(lambda: codec.encode_object_framed(body))
        t_hash = stage(lambda: hbitrot.fill_framed(framed2d, ss))
        kept = [0]

        def commit_only():
            layer._commit_put(
                "benchbkt", f"stage-{kept[0]}", _stage_fi(layer, body),
                list(framed2d), False,
                layer.disks)
            kept[0] += 1

        def _stage_fi(lay, data):
            from minio_tpu.objectlayer import metadata as meta
            from minio_tpu.storage.datatypes import (
                ChecksumInfo, ErasureInfo, FileInfo, ObjectPartInfo)
            import uuid as _uuid
            dist = meta.hash_order("benchbkt/stage", len(lay.disks))
            return FileInfo(
                volume="benchbkt", name=f"stage-{kept[0]}",
                version_id="", data_dir=str(_uuid.uuid4()),
                mod_time=1, size=len(data),
                metadata={"etag": "0" * 32},
                parts=[ObjectPartInfo(1, len(data), len(data),
                                      "0" * 32, 1)],
                erasure=ErasureInfo(
                    data_blocks=12, parity_blocks=4,
                    block_size=1 << 20, distribution=dist,
                    checksums=[ChecksumInfo(1, lay.bitrot_algo)]),
                fresh=True)

        t_commit = stage(commit_only)
        # per-op commit decomposition (ISSUE 17): the always-on drive
        # micro-profiler recorded every create/fsync/rename/meta_merge
        # of the commit_only runs above — aggregate across the 16
        # drives and normalize to ms per object so the stage table
        # decomposes drive_fanout_commit the way the table itself
        # decomposes the request
        commit_per_op_ms = {}
        per_op: dict = {}
        for d in layer.disks:
            for op, (c, t_ns, b) in d.commit_profile.totals().items():
                agg = per_op.setdefault(op, [0, 0])
                agg[0] += c
                agg[1] += t_ns
        for op, (c, t_ns) in sorted(per_op.items()):
            commit_per_op_ms[op] = {
                "ms_per_object": round(t_ns / max(kept[0], 1) / 1e6, 3),
                "calls_per_object": round(c / max(kept[0], 1), 2),
            }

        # ---- streaming-pipeline overlap (tmpfs, 4 MiB batches) ---------
        # wall per batch, pipelined vs serial, against the stage table:
        # perfect overlap drives per-batch wall to ~max(stage); serial
        # is the sum.  overlap_efficiency = max(stage) / pipelined wall
        # (1.0 = nothing but the slowest stage remains on the wall).
        def put_pipeline_leg() -> dict | None:
            if not (os.path.isdir("/dev/shm")
                    and os.access("/dev/shm", os.W_OK)):
                return None
            import io

            from minio_tpu.objectlayer import erasure_object as eo
            prev_compat = os.environ.get("MT_NO_COMPAT")
            prev_batch = eo.STREAM_BATCH_BYTES
            shm_root = None
            try:
                os.environ["MT_NO_COMPAT"] = "0"      # strict md5 ETag
                eo.STREAM_BATCH_BYTES = 4 * (1 << 20)
                shm_root, lay = mk_layer("/dev/shm")
                nbatch = 16
                sbody = os.urandom(nbatch * 4 * (1 << 20))

                def run(depth, tag):
                    lay._pipe_depth = depth
                    best = float("inf")
                    for r in range(3):
                        t0 = time.perf_counter()
                        lay.put_object_stream(
                            "benchbkt", f"pl-{tag}-{r}",
                            io.BytesIO(sbody))
                        best = min(best,
                                   time.perf_counter() - t0)
                        lay.delete_object("benchbkt", f"pl-{tag}-{r}")
                    return best / nbatch * 1000.0      # ms per batch

                run(0, "warm")                          # warm the path
                serial_ms = run(0, "ser")
                pipe_ms = run(2, "pipe")
                enc = t_encode + t_hash
                fanout = max(serial_ms - t_md5 - enc, 0.0)
                max_stage = max(t_md5, enc, fanout)
                return {
                    "serial_wall_ms_per_batch": round(serial_ms, 2),
                    "pipelined_wall_ms_per_batch": round(pipe_ms, 2),
                    "pipelined_vs_serial": round(serial_ms / pipe_ms, 2)
                    if pipe_ms > 0 else None,
                    "max_stage_ms": round(max_stage, 2),
                    "overlap_efficiency": round(max_stage / pipe_ms, 2)
                    if pipe_ms > 0 else None,
                    "layer_reported": {
                        k: round(v, 4) if isinstance(v, float) else v
                        for k, v in lay._pipe_stats.items()},
                }
            except Exception as e:  # noqa: BLE001 — optional leg
                print(f"put-pipeline leg failed: {e!r}", file=sys.stderr)
                return None
            finally:
                eo.STREAM_BATCH_BYTES = prev_batch
                if prev_compat is None:
                    os.environ.pop("MT_NO_COMPAT", None)
                else:
                    os.environ["MT_NO_COMPAT"] = prev_compat
                if shm_root:
                    shutil.rmtree(shm_root, ignore_errors=True)

        pipeline_stats = put_pipeline_leg()
        md5_lane_stats = _bench_md5_lanes(body)
        stream_chunk_stats = _bench_stream_chunks(
            body, "/dev/shm" if (os.path.isdir("/dev/shm")
                                 and os.access("/dev/shm", os.W_OK))
            else None)

        # ---- throughput legs -------------------------------------------
        def run_leg(lay=None):
            lay = lay or layer

            def put(i):
                lay.put_object("benchbkt", f"obj-{i:04d}", body)
            # one client per core: oversubscribing a 1-vCPU VM measures
            # GIL thrash, not the pipeline (2 workers tested 0.22 vs
            # 0.43 GiB/s serial)
            workers = min(8, os.cpu_count() or 8)
            if workers <= 1:
                put(0)                             # warm path
                t0 = time.perf_counter()
                for i in range(n_obj):
                    put(i)
                return gib / (time.perf_counter() - t0)
            with ThreadPoolExecutor(max_workers=workers) as pool:
                list(pool.map(put, range(4)))      # warm path
                t0 = time.perf_counter()
                list(pool.map(put, range(n_obj)))
                return gib / (time.perf_counter() - t0)

        def best_leg(lay=None):
            best = 0.0
            for _ in range(2):
                drain()
                best = max(best, run_leg(lay))
            return best

        def get_leg(lay):
            """Sustained GET over objects the PUT legs wrote: k-shard
            read + bitrot verify + stripe assemble (the full
            get_object_reader pipeline, page-cache warm)."""
            def rd(i):
                _, body2 = lay.get_object("benchbkt", f"obj-{i:04d}")
                return len(body2)
            rd(0)                                      # warm path
            best = 0.0
            for _ in range(2):
                t0 = time.perf_counter()
                total = sum(rd(i) for i in range(n_obj))
                assert total == n_obj * obj_size
                best = max(best,
                           total / (time.perf_counter() - t0) / 2**30)
            return best

        def drop_caches() -> bool:
            """Evict the page cache so disk READ legs hit the device,
            not RAM (needs root; returns False when unavailable)."""
            try:
                os.sync()
                with open("/proc/sys/vm/drop_caches", "w") as f:
                    f.write("3")
                return True
            except OSError:
                return False

        def cold_get_leg(lay) -> float:
            """Disk GET end to end, page cache COLD: k-shard read +
            native bitrot verify + stripe assemble, served by the
            actual device (r4 verdict #6a — the warm get_leg measures
            the pipeline, this measures the pipeline + disk)."""
            if not drop_caches():
                return 0.0
            t0 = time.perf_counter()
            total = 0
            for i in range(n_obj):
                _, body2 = lay.get_object("benchbkt", f"obj-{i:04d}")
                total += len(body2)
            assert total == n_obj * obj_size
            return total / (time.perf_counter() - t0) / 2**30

        def raw_disk_read_gibps() -> float:
            """Hardware control for the cold GET leg: read the SAME
            shard part files the GET leg reads, raw sequential, no
            pipeline — same cache temperature on both sides of the
            virtio seam (a separate freshly-written control file
            measured 1.8 GiB/s because the HOST page cache still held
            it; the guest cannot drop that).  GET reads k data shards =
            payload-sized bytes, so its payload-rate bound is this
            number directly."""
            import glob as _glob
            files = sorted(_glob.glob(
                os.path.join(tmp, "d*", "benchbkt", "obj-*", "*",
                             "part.*")))
            if not files or not drop_caches():
                return 0.0
            blk = 4 * (1 << 20)
            n = 0
            t0 = time.perf_counter()
            for path in files:
                with open(path, "rb", buffering=0) as f:
                    while True:
                        b = f.read(blk)
                        if not b:
                            break
                        n += len(b)
            return n / (time.perf_counter() - t0) / 2**30

        def fresh_write_floor_ms(root) -> float:
            """Hardware control for the commit fan-out: 16 FRESH shard
            files (2 mkdirs + open/write/close each), zero Python
            framework.  On tmpfs this is dominated by first-touch page
            allocation — recycled pages measure ~2.5x faster, a rate no
            real PUT of a new object can reach.  strict PUT's honest
            single-core ceiling = obj / (t_md5 + this floor)."""
            dirs = [os.path.join(root, f"floor{i}") for i in range(16)]
            for d in dirs:
                os.makedirs(d, exist_ok=True)
            rows = list(framed2d)
            seq = [0]

            def one():
                j = seq[0]
                seq[0] += 1
                for i, d in enumerate(dirs):
                    od = os.path.join(d, f"o{j}", "ddir")
                    os.makedirs(od)
                    fd = os.open(os.path.join(od, "part.1"),
                                 os.O_WRONLY | os.O_CREAT)
                    try:
                        os.write(fd, rows[i])
                    finally:
                        os.close(fd)
            one()
            t0 = time.perf_counter()
            for _ in range(reps):
                one()
            return (time.perf_counter() - t0) / reps * 1000

        prev = os.environ.get("MT_NO_COMPAT")
        shm_gibps, shm_strict, shm_get = None, None, None
        shm_floor_ms = None
        try:
            os.environ["MT_NO_COMPAT"] = "0"
            strict_gibps = best_leg()
            os.environ["MT_NO_COMPAT"] = "1"
            nocompat_gibps = best_leg()
            # control FIRST (host-cache-cold for every shard file),
            # then the pipeline leg; if the host cache assists the
            # second pass the GET number is optimistic, which the
            # control/leg ratio makes visible
            disk_raw_read = raw_disk_read_gibps()
            disk_get_gibps = cold_get_leg(layer)

            # tmpfs drives: the full real code path with the shared
            # virtio disk taken out of the picture (its latency swings
            # 3x with host weather) — the pipeline's own sustained rate.
            # Optional: a failure here (tiny /dev/shm) must not discard
            # the disk legs already measured.
            try:
                if os.path.isdir("/dev/shm") and \
                        os.access("/dev/shm", os.W_OK):
                    shm_root, shm_layer = mk_layer("/dev/shm")
                    try:
                        shm_gibps = best_leg(shm_layer)
                        os.environ["MT_NO_COMPAT"] = "0"
                        shm_strict = best_leg(shm_layer)
                        shm_get = get_leg(shm_layer)
                        shm_floor_ms = fresh_write_floor_ms(shm_root)
                    finally:
                        shutil.rmtree(shm_root, ignore_errors=True)
            except Exception as e:  # noqa: BLE001 — optional leg
                print(f"tmpfs leg failed: {e!r}", file=sys.stderr)
        finally:
            if prev is None:
                os.environ.pop("MT_NO_COMPAT", None)
            else:
                os.environ["MT_NO_COMPAT"] = prev

        # amplification: 4 MiB of data fans out to k+m/k framed bytes
        amp = 16 / 12
        return {
            "disk_strict_GiBps": round(strict_gibps, 3),
            "disk_nocompat_GiBps": round(nocompat_gibps, 3),
            "tmpfs_nocompat_GiBps": (round(shm_gibps, 3)
                                     if shm_gibps else None),
            "tmpfs_strict_GiBps": (round(shm_strict, 3)
                                   if shm_strict else None),
            "tmpfs_get_GiBps": (round(shm_get, 3) if shm_get else None),
            # hardware roofline for the disk legs: raw one-file
            # sequential buffered write+sync on the same fs.  The
            # SUSTAINED pipeline bound = raw / (16/12 write
            # amplification); short runs can read above it because the
            # page cache absorbs roughly the first GiB before the
            # kernel's dirty throttling clamps the writer to device
            # speed — which is also why the strict/nocompat disk
            # ordering flips run to run (the faster leg hits the clamp
            # sooner).  tmpfs legs are the pipeline's own rate.
            "disk_raw_seq_write_GiBps": round(raw_gibps, 3),
            "disk_sustained_bound_GiBps": round(raw_gibps / amp, 3),
            # cold-cache disk GET + its hardware control (raw
            # sequential cold read; GET reads k of k+m shard files so
            # its bound is raw_read — the k-cheapest read already
            # skips the parity 4/16)
            "disk_get_cold_GiBps": round(disk_get_gibps, 3),
            "disk_raw_seq_read_GiBps": round(disk_raw_read, 3),
            # single-core strict bound: the md5 ETag is one sequential
            # stream per object (S3 compat pins the algorithm); on this
            # 1-vCPU VM nothing can overlap it, so strict <=
            # obj_size/t_md5 even with a zero-cost pipeline.  The
            # md5-in-parallel-with-encode overlap IS implemented
            # (erasure_object._put_object_bytes) and engages when
            # os.cpu_count() > 1.
            "strict_md5_bound_GiBps": round(
                obj_size / (t_md5 / 1000) / 2**30, 3),
            # the NEW ceilings (ISSUE 6): the native single-stream core
            # raises the per-stream md5 bound, and the lane sweep shows
            # the aggregate rate N concurrent strict streams share;
            # the chunk sweep prices the internode framed mode
            "md5_native_GiBps": (md5_lane_stats or {}).get(
                "md5_native_GiBps"),
            "md5_lane_sweep": md5_lane_stats,
            "internode_stream_chunk_GiBps": stream_chunk_stats,
            # the tighter honest ceiling: md5 (compat-pinned, serial)
            # + the fresh-file write floor measured above — both
            # irreducible on 1 vCPU; everything else (encode, hash,
            # meta) is the optimizable residue
            "tmpfs_fresh_write_floor_ms": (round(shm_floor_ms, 2)
                                           if shm_floor_ms else None),
            "tmpfs_strict_floor_GiBps": (round(
                obj_size / ((t_md5 + shm_floor_ms) / 1000) / 2**30, 3)
                if shm_floor_ms else None),
            "stages_ms_per_4MiB": {
                "md5_etag(strict only)": round(t_md5, 2),
                "md5_etag_native": (round(
                    obj_size / (md5_lane_stats["md5_native_GiBps"]
                                * 2**30) * 1000, 2)
                    if md5_lane_stats else None),
                # device multi-buffer MD5, probed end-to-end rate
                # (transfer included); None when no device
                "md5_etag_device": (round(
                    obj_size / (md5_lane_stats[
                        "md5_device_probe_GiBps"] * 2**30) * 1000, 2)
                    if md5_lane_stats and md5_lane_stats.get(
                        "md5_device_probe_GiBps") else None),
                "erasure_encode_into_frames": round(t_encode, 2),
                "bitrot_hh256_fill": round(t_hash, 2),
                "drive_fanout_commit": round(t_commit, 2),
                # the micro-profiler's decomposition of the line above
                # (sums can exceed it: 16 drives overlap on the wall)
                "drive_fanout_commit_per_op": commit_per_op_ms,
                # streaming-pipeline overlap: per-4MiB-batch wall with
                # the writer plane on vs off, and how close the
                # pipelined wall gets to the slowest single stage
                "put_pipeline": pipeline_stats,
            },
        }
    except Exception as e:  # noqa: BLE001 — e2e leg must not sink the bench
        print(f"e2e leg failed: {e!r}", file=sys.stderr)
        return None
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def _bench_xray() -> dict | None:
    """``bench.py xray`` — ns/request overhead of the X-ray stage
    clock + flight-recorder ring on the GET and PUT hot paths, through
    the REAL S3 server (ISSUE 15 satellite).  A/B per round: the same
    request loop with the plane armed (stages.ENABLED + flight ring)
    vs disabled (no clock minted, ring append no-opped) — the target
    is an overhead indistinguishable from run-to-run noise, reported
    beside it."""
    import shutil
    import statistics
    import tempfile

    try:
        from minio_tpu.obs import stages as _stages
        from minio_tpu.objectlayer.erasure_object import ErasureObjects
        from minio_tpu.s3.client import S3Client
        from minio_tpu.s3.server import S3Server
        from minio_tpu.storage.xl_storage import XLStorage
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys as _sys
        print(f"xray leg failed to import: {e!r}", file=_sys.stderr)
        return None
    root = "/dev/shm" if os.path.isdir("/dev/shm") and \
        os.access("/dev/shm", os.W_OK) else None
    tmp = tempfile.mkdtemp(prefix="xraybench-", dir=root)
    saved_enabled = _stages.ENABLED
    srv = None
    try:
        disks = []
        for i in range(4):
            d = os.path.join(tmp, f"d{i}")
            os.makedirs(d)
            disks.append(XLStorage(d))
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        srv = S3Server(layer, access_key="bk", secret_key="bs")
        srv.start()
        c = S3Client(srv.endpoint, "bk", "bs")
        c.make_bucket("xbench")
        body = os.urandom(64 * 1024)
        c.put_object("xbench", "warm", body)
        c.get_object("xbench", "warm")
        real_record = srv.flightrec.record
        reps, rounds = 60, 5
        from minio_tpu.admin.metrics import GLOBAL as _gm
        gate0 = {k: v for k, v in _gm.snapshot().items()
                 if k[0] == "mt_quorum_gating_total"}
        strag0 = {k: (v[-2], v[-1]) for k, v in
                  _gm.hist_snapshot().items()
                  if k[0] == "mt_quorum_straggler_seconds"}

        def one_round(op: str) -> float:
            t0 = time.perf_counter()
            for i in range(reps):
                if op == "put":
                    c.put_object("xbench", f"o{i % 8}", body)
                else:
                    c.get_object("xbench", "warm")
            return (time.perf_counter() - t0) / reps * 1e9  # ns/req

        out: dict = {"reps": reps, "rounds": rounds,
                     "body_bytes": len(body),
                     "drives_root": root or "disk"}
        for op in ("get", "put"):
            on: list[float] = []
            off: list[float] = []
            for _ in range(rounds):
                _stages.ENABLED = True
                srv.flightrec.record = real_record
                on.append(one_round(op))
                _stages.ENABLED = False
                srv.flightrec.record = lambda *a, **k: None
                off.append(one_round(op))
            med_on = statistics.median(on)
            med_off = statistics.median(off)
            noise = max(off) - min(off)
            overhead = med_on - med_off
            out[op] = {
                "ns_per_request_on": round(med_on),
                "ns_per_request_off": round(med_off),
                "overhead_ns": round(overhead),
                "run_to_run_noise_ns": round(noise),
                "unmeasurable": overhead <= noise,
            }
        # critical-path report (ISSUE 17): which drives gated quorum
        # reductions over the run (counter deltas across the whole A/B
        # loop), and the mean straggler trail per plane — the
        # cluster-level "who is slow" readout the gating plane exists
        # to answer
        gates = []
        for k, v in _gm.snapshot().items():
            if k[0] != "mt_quorum_gating_total":
                continue
            d = v - gate0.get(k, 0)
            if d > 0:
                gates.append({**dict(k[1]), "count": int(d)})
        gates.sort(key=lambda g: g["count"], reverse=True)
        trails = {}
        for k, v in _gm.hist_snapshot().items():
            if k[0] != "mt_quorum_straggler_seconds":
                continue
            c0, s0 = strag0.get(k, (0, 0.0))
            dc, ds = v[-2] - c0, v[-1] - s0
            if dc > 0:
                plane = dict(k[1]).get("plane", "")
                trails[plane] = round(ds / dc * 1e6, 1)   # us mean
        out["critical_path"] = {
            "top_gating": gates[:8],
            "mean_straggler_trail_us": trails,
        }
        return out
    except Exception as e:  # noqa: BLE001 — optional leg
        import sys as _sys
        print(f"xray leg failed: {e!r}", file=_sys.stderr)
        return None
    finally:
        _stages.ENABLED = saved_enabled
        if srv is not None:
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def xray_main() -> None:
    """``bench.py xray`` — run the X-ray overhead leg standalone and
    print ONE BENCH_*-shaped JSON line."""
    stats = _bench_xray()
    if stats is None:
        raise SystemExit("xray leg unavailable")
    print(json.dumps({
        "metric": "xray_overhead_ns_per_get",
        "value": stats["get"]["overhead_ns"],
        "unit": "ns/request",
        "detail": stats,
    }))


def _bench_commit_profile() -> dict | None:
    """``bench.py commit_profile`` — the always-on commit
    micro-profiler read out as a per-op stage table (ISSUE 17): N real
    PUTs through the erasure layer, then the per-drive
    create/append/fsync/rename/meta_merge windows aggregated into
    ms-per-object rows, the same decomposition the BENCH stage table
    applies to the request."""
    import shutil
    import sys as _sys
    import tempfile

    try:
        from minio_tpu.admin.metrics import GLOBAL as _gm
        from minio_tpu.objectlayer.erasure_object import ErasureObjects
        from minio_tpu.storage.xl_storage import XLStorage
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"commit_profile leg failed to import: {e!r}",
              file=_sys.stderr)
        return None
    root = "/dev/shm" if os.path.isdir("/dev/shm") and \
        os.access("/dev/shm", os.W_OK) else None
    tmp = tempfile.mkdtemp(prefix="commitprof-", dir=root)
    try:
        disks = []
        for i in range(8):
            d = os.path.join(tmp, f"d{i}")
            os.makedirs(d)
            disks.append(XLStorage(d))
        layer = ErasureObjects(disks, parity=2, block_size=1 << 20,
                               backend="numpy")
        layer.make_bucket("profbkt")
        body = os.urandom(1 << 20)
        n_obj = 64
        hist0 = {k: (v[-2], v[-1]) for k, v in
                 _gm.hist_snapshot().items()
                 if k[0] == "mt_drive_op_seconds"}
        layer.put_object("profbkt", "warm", body)   # warm the path
        t0 = time.perf_counter()
        for i in range(n_obj):
            layer.put_object("profbkt", f"o{i:03d}", body)
        wall_ms = (time.perf_counter() - t0) * 1000
        per_op = {}
        for k, v in _gm.hist_snapshot().items():
            if k[0] != "mt_drive_op_seconds":
                continue
            c0, s0 = hist0.get(k, (0, 0.0))
            dc, ds = v[-2] - c0, v[-1] - s0
            if dc <= 0:
                continue
            op = dict(k[1]).get("op", "")
            per_op[op] = {
                "calls_per_object": round(dc / (n_obj + 1), 2),
                "mean_us": round(ds / dc * 1e6, 1),
                "ms_per_object": round(ds / (n_obj + 1) * 1000, 3),
            }
        total_ms = sum(r["ms_per_object"] for r in per_op.values())
        return {
            "objects": n_obj, "object_bytes": len(body),
            "drives": len(disks), "drives_root": root or "disk",
            "wall_ms_per_object": round(wall_ms / n_obj, 3),
            # sum across 8 drives; overlapped on the wall, so the sum
            # exceeding the per-object wall is expected, not an error
            "drive_op_ms_per_object_sum": round(total_ms, 3),
            "per_op": dict(sorted(per_op.items())),
        }
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"commit_profile leg failed: {e!r}", file=_sys.stderr)
        return None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def commit_profile_main() -> None:
    """``bench.py commit_profile`` — run the commit micro-profiler leg
    standalone and print ONE BENCH_*-shaped JSON line."""
    stats = _bench_commit_profile()
    if stats is None:
        raise SystemExit("commit_profile leg unavailable")
    print(json.dumps({
        "metric": "commit_profile_drive_op_ms_per_object",
        "value": stats["drive_op_ms_per_object_sum"],
        "unit": "ms/object",
        "detail": stats,
    }))


def _bench_commit_plane() -> dict | None:
    """``bench.py commit_plane`` — the per-drive group-commit plane
    (ISSUE 20) A/B'd with durability ON.  Runs in a subprocess because
    this module pins MT_FSYNC=0 at import (go test -bench semantics);
    grouping only has something to coalesce when every commit actually
    fsyncs.  Legs: grouped-vs-ungrouped commit fan-out wall at 16
    concurrent 4 MiB streams, and the small-object PUT rate at
    1/16/64 streams (packed segments vs per-object files), plus the
    mt_commit_group_* counter deltas that prove the plane engaged."""
    import subprocess
    import sys as _sys
    env = dict(os.environ)
    env["MT_FSYNC"] = "1"
    # a host-only fsync leg: the parent may hold the chip, and one chip
    # belongs to one process
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [_sys.executable, os.path.abspath(__file__),
             "commit_plane_child"],
            capture_output=True, text=True, timeout=900, env=env)
        if out.returncode != 0:
            print("commit_plane child failed: "
                  f"{out.stderr.strip()[-800:]}", file=_sys.stderr)
            return None
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"commit_plane leg failed: {e!r}", file=_sys.stderr)
        return None


def commit_plane_child_main() -> None:
    """The in-process body of the commit_plane leg (MT_FSYNC=1 was set
    by the parent BEFORE interpreter start, so the storage layer and
    the commit plane both see durability on).  Prints one JSON dict."""
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from minio_tpu.admin.metrics import GLOBAL as _gm
    from minio_tpu.objectlayer import metadata as _ometa
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.storage import commit as _commit
    from minio_tpu.storage.datatypes import (ChecksumInfo, ErasureInfo,
                                             FileInfo, ObjectPartInfo)
    from minio_tpu.storage.xl_storage import XLStorage
    import uuid as _uuid

    assert os.environ.get("MT_FSYNC") == "1", "child needs MT_FSYNC=1"
    n_drives, parity = 8, 2
    k = n_drives - parity
    tmp = tempfile.mkdtemp(prefix="bench-commit-plane-")
    try:
        disks = []
        for i in range(n_drives):
            d = os.path.join(tmp, f"d{i}")
            os.makedirs(d)
            disks.append(XLStorage(d))
        layer = ErasureObjects(disks, parity=parity, block_size=1 << 20,
                               backend="numpy")
        # single-core hosts default to the serial fan-out; the plane
        # (and with it the group-commit drain) only lives on the
        # per-drive writer threads, so force it the way tests do
        layer._pipe_depth = 2
        layer.make_bucket("cbkt")

        # ---- leg 1: commit fan-out wall, 16 concurrent 4 MiB streams
        body = os.urandom(4 << 20)
        codec = layer._codec_for(parity)
        rows = list(codec.encode_object_framed(body))
        from minio_tpu.hashing import bitrot as _hbitrot
        import numpy as _np
        framed2d = _np.stack([_np.frombuffer(r, dtype=_np.uint8)
                              for r in rows])
        _hbitrot.fill_framed(framed2d, codec.shard_size())
        rows = [bytes(r) for r in framed2d]
        dist = _ometa.hash_order("cbkt/commit", n_drives)
        seq = [0]

        def mkfi(name: str) -> FileInfo:
            return FileInfo(
                volume="cbkt", name=name, version_id="",
                data_dir=str(_uuid.uuid4()), mod_time=1, size=len(body),
                metadata={"etag": "0" * 32},
                parts=[ObjectPartInfo(1, len(body), len(body),
                                      "0" * 32, 1)],
                erasure=ErasureInfo(
                    data_blocks=k, parity_blocks=parity,
                    block_size=1 << 20, distribution=dist,
                    checksums=[ChecksumInfo(1, layer.bitrot_algo)]),
                fresh=True)

        def commit_leg(grouped: bool, streams: int, n_obj: int) -> float:
            _commit.CONFIG.enable = grouped
            tag = f"{'g' if grouped else 'u'}{streams}-{seq[0]}"
            seq[0] += 1

            def one(j):
                name = f"c{tag}-{j}"
                layer._commit_put("cbkt", name, mkfi(name), rows,
                                  False, layer.disks)
            with ThreadPoolExecutor(max_workers=streams) as pool:
                list(pool.map(one, range(streams)))       # warm
                t0 = time.perf_counter()
                list(pool.map(one, range(streams, streams + n_obj)))
                return (time.perf_counter() - t0) / n_obj * 1000

        n_obj = 32
        commit_leg(True, 16, 4)                            # warm path
        ungrouped_ms = min(commit_leg(False, 16, n_obj) for _ in range(2))
        grouped_ms = min(commit_leg(True, 16, n_obj) for _ in range(2))

        # ---- leg 2: small-object PUT rate at 1/16/64 streams --------
        # 256 KiB sits mid packing band (inline 128 KiB < size, framed
        # shard well under pack_threshold): ungrouped it is a per-
        # object part file + its own fsyncs, grouped it folds into the
        # drive's journaled segment + one covering fsync
        sbody = os.urandom(256 << 10)
        small = {}

        def put_leg(grouped: bool, streams: int) -> float:
            _commit.CONFIG.enable = grouped
            tag = f"s{'g' if grouped else 'u'}{streams}-{seq[0]}"
            seq[0] += 1
            n_obj = max(16, 2 * streams)

            def one(j):
                layer.put_object("cbkt", f"{tag}-{j}", sbody)
            with ThreadPoolExecutor(max_workers=streams) as pool:
                list(pool.map(one, range(min(streams, 8))))  # warm
                t0 = time.perf_counter()
                list(pool.map(one, range(100, 100 + n_obj)))
                return n_obj / (time.perf_counter() - t0)

        c0 = {key: v for key, v in _gm.snapshot().items()
              if key[0].startswith("mt_commit_group_")}
        for streams in (1, 16, 64):
            small[str(streams)] = {
                "per_object_fsync_ops": round(put_leg(False, streams), 1),
                "packed_group_ops": round(put_leg(True, streams), 1),
            }
        groups = {}
        for key, v in _gm.snapshot().items():
            if key[0].startswith("mt_commit_group_"):
                groups[key[0]] = groups.get(key[0], 0) + v - c0.get(key, 0)

        s1, s64 = small["1"], small["64"]
        print(json.dumps({
            "drives": n_drives, "parity": parity, "fsync": True,
            "commit_16x4MiB_ungrouped_ms_per_object":
                round(ungrouped_ms, 2),
            "commit_16x4MiB_grouped_ms_per_object": round(grouped_ms, 2),
            "grouped_vs_ungrouped": round(ungrouped_ms / grouped_ms, 2)
            if grouped_ms > 0 else None,
            "small_put_256KiB_ops_per_s": small,
            # superlinear check: packed 64-stream rate vs 64x the
            # packed single-stream rate, and vs the eager 64-stream
            "small_put_64s_scaling_vs_1s": round(
                s64["packed_group_ops"] / s1["packed_group_ops"], 2)
            if s1["packed_group_ops"] > 0 else None,
            "small_put_64s_packed_vs_eager": round(
                s64["packed_group_ops"] / s64["per_object_fsync_ops"], 2)
            if s64["per_object_fsync_ops"] > 0 else None,
            "mt_commit_group_counters": {key: round(v, 1)
                                         for key, v in groups.items()},
        }))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def commit_plane_main() -> None:
    """``bench.py commit_plane`` — run the group-commit A/B leg
    standalone and print ONE BENCH_*-shaped JSON line."""
    stats = _bench_commit_plane()
    if stats is None:
        raise SystemExit("commit_plane leg unavailable")
    print(json.dumps({
        "metric": "commit_plane_grouped_vs_ungrouped",
        "value": stats.get("grouped_vs_ungrouped"),
        "unit": "x",
        "detail": stats,
    }))


def _bench_watchdog() -> dict | None:
    """``bench.py watchdog`` — ns/request cost of the SLO watchdog
    plane on the GET hot path, through the REAL S3 server (ISSUE 18
    acceptance: overhead within run-to-run noise).  A/B per round: the
    same request loop with the plane live (mt-obs-history sampler
    thread ticking every second + rule engine) vs disabled (the idle
    contract: no thread, no rings).  The watchdog never touches the
    request path, so anything measurable here is GIL pressure from the
    sampler — the number the idle contract promises is noise."""
    import shutil
    import statistics
    import sys as _sys
    import tempfile

    try:
        from minio_tpu.objectlayer.erasure_object import ErasureObjects
        from minio_tpu.s3.client import S3Client
        from minio_tpu.s3.server import S3Server
        from minio_tpu.storage.xl_storage import XLStorage
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"watchdog leg failed to import: {e!r}", file=_sys.stderr)
        return None
    root = "/dev/shm" if os.path.isdir("/dev/shm") and \
        os.access("/dev/shm", os.W_OK) else None
    tmp = tempfile.mkdtemp(prefix="wdbench-", dir=root)
    srv = None
    try:
        disks = []
        for i in range(4):
            d = os.path.join(tmp, f"d{i}")
            os.makedirs(d)
            disks.append(XLStorage(d))
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        srv = S3Server(layer, access_key="wk", secret_key="ws")
        srv.start()
        c = S3Client(srv.endpoint, "wk", "ws")
        c.make_bucket("wdbench")
        body = os.urandom(64 * 1024)
        c.put_object("wdbench", "warm", body)
        c.get_object("wdbench", "warm")

        def arm(on: bool) -> None:
            srv.config.set("watchdog", "enable", "on" if on else "off")
            srv.config.set("watchdog", "interval", "1s")
            srv.reload_watchdog_config()

        reps, rounds = 60, 5

        def one_round() -> float:
            t0 = time.perf_counter()
            for _ in range(reps):
                c.get_object("wdbench", "warm")
            return (time.perf_counter() - t0) / reps * 1e9  # ns/req

        on: list[float] = []
        off: list[float] = []
        for _ in range(rounds):
            arm(True)
            on.append(one_round())
            arm(False)
            off.append(one_round())
        med_on = statistics.median(on)
        med_off = statistics.median(off)
        noise = max(off) - min(off)
        overhead = med_on - med_off
        # the sampler's own tick cost (scrape + fold + rules), off the
        # request path but worth pinning: it runs every interval
        arm(True)
        wd = srv.watchdog
        ticks = []
        for i in range(5):
            t0 = time.perf_counter()
            wd.sampler.tick(time.time() - (5 - i))
            ticks.append((time.perf_counter() - t0) * 1000)
        stats = wd.history.stats()
        arm(False)
        return {
            "reps": reps, "rounds": rounds, "body_bytes": len(body),
            "drives_root": root or "disk",
            "get": {
                "ns_per_request_on": round(med_on),
                "ns_per_request_off": round(med_off),
                "overhead_ns": round(overhead),
                "run_to_run_noise_ns": round(noise),
                "unmeasurable": overhead <= noise,
            },
            "sampler_tick_ms_median": round(
                statistics.median(ticks), 3),
            "history_series": stats["series"],
            "history_samples": stats["samplesTotal"],
        }
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"watchdog leg failed: {e!r}", file=_sys.stderr)
        return None
    finally:
        if srv is not None:
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def watchdog_main() -> None:
    """``bench.py watchdog`` — run the watchdog overhead leg
    standalone and print ONE BENCH_*-shaped JSON line."""
    stats = _bench_watchdog()
    if stats is None:
        raise SystemExit("watchdog leg unavailable")
    print(json.dumps({
        "metric": "watchdog_overhead_ns_per_get",
        "value": stats["get"]["overhead_ns"],
        "unit": "ns/request",
        "detail": stats,
    }))


def _bench_metering() -> dict | None:
    """``bench.py metering`` — ns/request cost of the workload
    attribution plane on the GET hot path, through the REAL S3 server
    (ISSUE 19 acceptance: overhead unmeasurable against run-to-run
    noise).  A/B per round: the same request loop with metering armed
    (per-(bucket,api,tenant) accounting + count-min/space-saving
    offers at completion-record time) vs disabled (the idle contract:
    ``srv.metering is None``, zero work).  Rides along: the raw
    ``charge()`` microbench — the exact per-request cost the sketches
    add, measured off the socket path where noise can't hide it."""
    import shutil
    import statistics
    import sys as _sys
    import tempfile

    try:
        from minio_tpu.obs.metering import Metering
        from minio_tpu.objectlayer.erasure_object import ErasureObjects
        from minio_tpu.s3.client import S3Client
        from minio_tpu.s3.server import S3Server
        from minio_tpu.storage.xl_storage import XLStorage
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"metering leg failed to import: {e!r}", file=_sys.stderr)
        return None
    root = "/dev/shm" if os.path.isdir("/dev/shm") and \
        os.access("/dev/shm", os.W_OK) else None
    tmp = tempfile.mkdtemp(prefix="mtrbench-", dir=root)
    srv = None
    try:
        disks = []
        for i in range(4):
            d = os.path.join(tmp, f"d{i}")
            os.makedirs(d)
            disks.append(XLStorage(d))
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        srv = S3Server(layer, access_key="mk", secret_key="ms")
        srv.start()
        c = S3Client(srv.endpoint, "mk", "ms")
        c.make_bucket("mtrbench")
        body = os.urandom(64 * 1024)
        c.put_object("mtrbench", "warm", body)
        c.get_object("mtrbench", "warm")

        def arm(on: bool) -> None:
            srv.config.set("metering", "enable", "on" if on else "off")
            srv.reload_metering_config()

        reps, rounds = 60, 5

        def one_round() -> float:
            t0 = time.perf_counter()
            for _ in range(reps):
                c.get_object("mtrbench", "warm")
            return (time.perf_counter() - t0) / reps * 1e9  # ns/req

        on: list[float] = []
        off: list[float] = []
        for _ in range(rounds):
            arm(True)
            on.append(one_round())
            arm(False)
            off.append(one_round())
        med_on = statistics.median(on)
        med_off = statistics.median(off)
        noise = max(off) - min(off)
        overhead = med_on - med_off
        # the charge path in isolation: one warm-table hit and one
        # distinct-key miss (the worst case — every sketch evicts)
        m = Metering(seed=1)
        n = 20_000
        t0 = time.perf_counter()
        for i in range(n):
            m.charge(bucket="mtrbench", api="GetObject", tenant="mk",
                     key="warm", tx=65536, dur_ns=1000)
        hot_ns = (time.perf_counter() - t0) / n * 1e9
        t0 = time.perf_counter()
        for i in range(n):
            m.charge(bucket="mtrbench", api="GetObject",
                     tenant=f"t{i}", key=f"k{i}", tx=65536,
                     dur_ns=1000)
        cold_ns = (time.perf_counter() - t0) / n * 1e9
        return {
            "reps": reps, "rounds": rounds, "body_bytes": len(body),
            "drives_root": root or "disk",
            "get": {
                "ns_per_request_on": round(med_on),
                "ns_per_request_off": round(med_off),
                "overhead_ns": round(overhead),
                "run_to_run_noise_ns": round(noise),
                "unmeasurable": overhead <= noise,
            },
            "charge_ns_hot_key": round(hot_ns),
            "charge_ns_distinct_key": round(cold_ns),
            "sketch_memory_bytes": m.memory_bytes(),
        }
    except Exception as e:  # noqa: BLE001 — optional leg
        print(f"metering leg failed: {e!r}", file=_sys.stderr)
        return None
    finally:
        if srv is not None:
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def metering_main() -> None:
    """``bench.py metering`` — run the attribution-plane overhead leg
    standalone and print ONE BENCH_*-shaped JSON line."""
    stats = _bench_metering()
    if stats is None:
        raise SystemExit("metering leg unavailable")
    print(json.dumps({
        "metric": "metering_overhead_ns_per_get",
        "value": stats["get"]["overhead_ns"],
        "unit": "ns/request",
        "detail": stats,
    }))


def host_main() -> None:
    """``bench.py host`` — the host-measurable legs only (BASELINE
    configs 1-2, the e2e PUT pipeline, md5 lanes/backends, codec
    batching): everything that moves without a TPU attached.  Prints
    ONE BENCH_*-shaped JSON line keyed on config 1 — the weakest
    driver-tracked number and the one the host-path work targets."""
    e2e = _bench_end_to_end_put()
    cfg12 = _bench_baseline_configs()
    codec_batching = _bench_codec_batching()
    hot_get = _bench_hot_get()
    xray = _bench_xray()
    watchdog = _bench_watchdog()
    metering = _bench_metering()
    commit_plane = _bench_commit_plane()
    c1 = (cfg12 or {}).get("config1_4+2_put_64MiB_GiBps")
    print(json.dumps({
        "metric": "baseline_config1_4+2_put_64MiB_GiBps",
        "value": c1,
        "unit": "GiB/s",
        "detail": {
            "config1_4+2_put_64MiB_GiBps": c1,
            "config2_8+4_multipart_1GiB_GiBps": (cfg12 or {}).get(
                "config2_8+4_multipart_1GiB_GiBps"),
            "baseline_configs_1_2": cfg12,
            ("e2e_put_256x4MiB_fsync" if _FSYNC_ON
             else "e2e_put_256x4MiB_nofsync"): e2e,
            "codec_batching": codec_batching,
            "hot_get": hot_get,
            "xray": xray,
            "watchdog": watchdog,
            "metering": metering,
            "commit_plane": commit_plane,
            "methodology": "host legs only (bench.py host); device "
                           "kernel legs need a TPU",
        },
    }))


def soak_main(argv: list[str]) -> None:
    """``bench.py soak [duration_s] [out.json]`` — run the soak
    scenario matrix (minio_tpu/soak): every production workload mix
    under the full concurrent chaos timeline on a 3-node cluster, with
    SLO assertions (last-minute p50/p99 per S3 API, error-rate
    ceiling, zero telemetry dead-letters, heal convergence, thread
    hygiene).  Writes one {scenario, metric, value, unit, detail} row
    per scenario x assertion to SOAK_r01.json (BENCH_* shape) and
    prints ONE summary JSON line."""
    import sys as _sys

    from minio_tpu.soak.report import default_matrix, run_matrix

    duration_s = float(argv[0]) if argv else 12.0
    out_path = argv[1] if len(argv) > 1 else "SOAK_r01.json"
    report = run_matrix(default_matrix(duration_s=duration_s),
                        out_path=out_path)
    failed = [r for r in report["rows"] if not r["passed"]]
    print(json.dumps({
        "metric": "soak_scenarios_passed",
        "value": len(report["scenarios"]) - len(
            {r["scenario"] for r in failed}),
        "unit": "scenarios",
        "detail": {
            "scenarios": report["scenarios"],
            "assertions_passed": report["passed"],
            "assertions_failed": report["failed"],
            "out": out_path,
            "failed": [
                {"scenario": r["scenario"], "metric": r["metric"],
                 "value": r["value"]} for r in failed],
        },
    }))
    if failed:
        print(f"soak: {len(failed)} SLO assertion(s) failed",
              file=_sys.stderr)
        _sys.exit(1)


if __name__ == "__main__":
    import sys as _sys
    if len(_sys.argv) > 1 and _sys.argv[1] == "soak":
        soak_main(_sys.argv[2:])
    elif len(_sys.argv) > 1 and _sys.argv[1] == "codec_batching":
        codec_batching_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "hot_get":
        hot_get_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "xray":
        xray_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "commit_profile":
        commit_profile_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "commit_plane":
        commit_plane_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "commit_plane_child":
        commit_plane_child_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "watchdog":
        watchdog_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "metering":
        metering_main()
    elif len(_sys.argv) > 1 and _sys.argv[1] == "host":
        host_main()
    else:
        main()

"""Erasure codec facade — the TPU-native counterpart of MinIO's ``Erasure``.

API mirrors cmd/erasure-coding.go:28-143 (NewErasure/EncodeData/
DecodeDataBlocks/DecodeDataAndParityBlocks/ShardSize/ShardFileSize/
ShardFileOffset) with a pluggable backend:

  * ``numpy`` — pure-host reference path (always available, conformance oracle)
  * ``tpu``   — batched bitplane MXU matmuls (rs_kernels.py), one chip
  * ``mesh``  — matmuls sharded over the active jax.sharding.Mesh with
                ICI XOR fan-in (rs_mesh.py); 1-device mesh = single chip
  * ``auto``  — tpu when JAX's platform is a TPU, else numpy

Which backend a name resolves to, and whether an explicit device backend
may run at all, is ops/device.py's decision (``resolve_backend``): an
explicit ``tpu``/``mesh`` without a TPU and without an explicit CPU
opt-in raises instead of computing on the host under a device's name.

Shard layout, padding, and matrix construction are bit-identical between
backends (and with klauspost/reedsolomon's defaults).

The object layer reaches the kernels through two methods and nothing
else: ``Erasure.encode_framed`` (a batch of body bytes -> the k+m
bitrot-framed shard rows; which route encodes and frames is decided
there) and ``Erasure.reconstruct_files`` (k surviving shard files ->
the wanted ones, degraded GET and heal).  On a device backend a PUT's
full blocks cross the link once: the stripes go up, parity and the k+m
bitrot digests come down from fused programs that ride the combiner
(ops/rs_fused.py on one chip: one per stripe, or one per stripe group
for a body of several blocks; ops/rs_mesh.py over a mesh), and the rows
are framed on the host through views.  On one chip a tail block
(every object under a block) keeps the two-dispatch route:
parity through ``rs_kernels``, then the device bitrot leg
(``_streaming_encode_batch_device``, here next to the kernels it
drives); ``Erasure.encode_framed`` says why.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from ..admin import metrics as _metrics
from ..hashing import bitrot
from ..obs import trace as _obstrace
from . import gf8, gf8_ref

MAX_SHARDS = 256  # data+parity <= 256 (cmd/erasure-coding.go:41)


def _nbytes(x) -> int:
    n = getattr(x, "nbytes", None)
    if n is not None:
        return int(n)
    try:
        return len(x)
    except TypeError:
        return 0


class ErasureError(ValueError):
    pass


@contextlib.contextmanager
def dispatch_span(op: str, backend: str, nbytes: int, detail=None,
                  blocks: int = 0):
    """One codec dispatch, whole: the ``tpu`` span ``<op>.dispatch``
    (obs/trace.py: ring, leg histogram, profiler annotation, and the
    span dict with ``detail()`` when a trace consumer is active), and
    always counted into the mt_tpu_* families (encode GiB/s falls out
    of bytes_total / kernel_seconds_sum).  Cost is a handful of counter
    bumps against megabytes of GF(2^8) math — noise on this path."""
    sp = _obstrace.span("tpu", op + ".dispatch", nbytes, detail)
    try:
        with sp:
            yield
    finally:
        labels = {"op": op, "backend": backend}
        m = _metrics.GLOBAL
        m.inc("mt_tpu_ops_total", labels)
        m.inc("mt_tpu_bytes_total", labels, float(nbytes))
        m.observe("mt_tpu_kernel_seconds", labels, sp.dur_ns / 1e9,
                  buckets=_metrics.KERNEL_BUCKETS)
        if blocks:
            m.observe("mt_tpu_batch_blocks", {"op": op}, float(blocks),
                      buckets=_metrics.BATCH_BUCKETS)
        if sp.error:
            m.inc("mt_tpu_errors_total", labels)


def resolve_backend(backend: str) -> str:
    """``auto``/``tpu``/``mesh``/``numpy`` -> the backend that will run
    (ops/device.py; imported lazily so the host codec never loads JAX)."""
    if backend == "numpy":
        return backend
    from . import device
    return device.resolve_backend(backend)


def _batcher(codec: "Erasure"):
    """The cross-request combining batcher (parallel/batcher.py) when
    enabled AND the codec dispatches to a device (tpu/mesh) — batching
    amortizes per-dispatch launch cost, which the numpy host path does
    not have: its GIL-releasing native matmuls already run in parallel
    across caller threads, and funneling them through one combiner
    would serialize them for nothing.  Lazy import both ways: a bare
    codec must not pull the parallel package at import time, and the
    batcher's bucket executors call back into
    ``Erasure._apply_matrix`` directly (the serial engine), so routing
    here can never recurse."""
    if not codec.is_device:
        return None
    try:
        from ..parallel import batcher as _b
    except Exception:  # pragma: no cover — parallel plane unavailable
        return None
    return _b.GLOBAL if _b.CONFIG.on() else None


def _interleave(data: bytes, shard_size: int, hashes) -> bytes:
    out = bytearray()
    for i, h in enumerate(hashes):
        out += bytes(h)
        out += data[i * shard_size:(i + 1) * shard_size]
    return bytes(out)


def _device_hh256_batch(blocks):
    """Digests of (B, n) host blocks, (B, 32) uint8 back on the host.
    Single fused pallas kernel on a TPU, lax.scan packet loop elsewhere
    (both bit-identical; ops/device.py decides).  Three legs:
    ``hash.upload`` hands the bytes to JAX; ``hash.launch`` is the call
    of ``hh256_batch`` to its return — one dispatch of one compiled
    program (slice, pad, kernel, limb reassembly, remainder, finalize)
    — until the digests' handle is held; ``hash.fetch`` waits for them
    and copies them down.  Each dispatch counts its rows into
    ``mt_tpu_hash_rows_total{kind="real"|"hashed"}``."""
    from . import device
    if device.use_pallas():
        from . import hh_pallas as hh
    else:
        from . import hh_kernels as hh
    blocks = device.upload("hash", blocks)
    # rows asked for against rows the program hashes (the Pallas form
    # pads B to whole 128-row tiles), from the function it pads by
    real, hashed = blocks.shape[0], hh.hashed_rows(*blocks.shape)
    with _obstrace.span("tpu", "hash.launch", nbytes=blocks.nbytes,
                        detail=lambda: {"op": "hash", "rows": real,
                                        "rowsHashed": hashed}):
        digests = hh.hh256_batch(blocks)
    m = _metrics.GLOBAL
    m.inc("mt_tpu_hash_rows_total", {"kind": "real"}, float(real))
    m.inc("mt_tpu_hash_rows_total", {"kind": "hashed"}, float(hashed))
    return device.fetch("hash", digests)


def _streaming_encode_batch_device(shards, shard_size: int) -> list[bytes]:
    """Frame a full stripe of equal-length shard files with the
    per-block HighwayHash run ON the device, after the erasure encode,
    so parity AND bitrot digests come off it (BASELINE config 5).  A
    device failure raises: the host C path never stands in for it
    silently."""
    with _obstrace.span("tpu", "hash.prep") as sp:
        arrs = [np.asarray(bytearray(s), dtype=np.uint8) for s in shards]
        L = len(arrs[0])
        if L == 0:
            return [b"" for _ in arrs]
        if any(len(a) != L for a in arrs):
            raise ValueError("shard lengths differ")
        full, rem = divmod(L, shard_size)
        stacked = np.stack(arrs)                       # (S, L)
        sp.nbytes = stacked.nbytes
        blocks = stacked[:, :full * shard_size].reshape(-1, shard_size) \
            if full else None
    hs_full = _device_hh256_batch(blocks).reshape(len(arrs), full, 32) \
        if full else None
    hs_tail = _device_hh256_batch(stacked[:, full * shard_size:]) \
        if rem else None
    with _obstrace.span("tpu", "hash.frame", nbytes=stacked.nbytes):
        out = []
        for si, arr in enumerate(arrs):
            digests = [hs_full[si, b].tobytes() for b in range(full)]
            if rem:
                digests.append(hs_tail[si].tobytes())
            out.append(_interleave(arr.tobytes(), shard_size, digests))
        return out


class Erasure:
    """Erasure coding details for one (k, m, blockSize) geometry."""

    def __init__(self, data_blocks: int, parity_blocks: int,
                 block_size: int, backend: str = "auto"):
        if data_blocks <= 0 or parity_blocks <= 0:
            raise ErasureError("invalid shard number")
        if data_blocks + parity_blocks > MAX_SHARDS:
            raise ErasureError("max shard number exceeded")
        self.data_blocks = data_blocks
        self.parity_blocks = parity_blocks
        self.block_size = int(block_size)
        if backend not in ("auto", "numpy", "tpu", "mesh"):
            raise ErasureError(f"unknown backend {backend!r}")
        self.backend = backend = resolve_backend(backend)
        # resolve the compute impl once; all modules expose the same
        # encode_parity/reconstruct surface
        if backend == "tpu":
            from . import rs_kernels as impl
        elif backend == "mesh":
            from . import rs_mesh as impl
        else:
            impl = gf8_ref
        self._impl = impl
        self.matrix = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)

    @property
    def is_device(self) -> bool:
        """True when the matmul engine dispatches to accelerator(s) and
        accepts batched (B, k, n) operands (tpu and mesh backends)."""
        return self.backend in ("tpu", "mesh")

    # -- kernel observability ----------------------------------------------

    def _dispatch(self, op: str, nbytes: int, blocks: int = 0):
        """:func:`dispatch_span` for one dispatch of this codec, with
        its geometry as the span's detail."""
        return dispatch_span(
            op, self.backend, nbytes,
            lambda: {"op": op, "backend": self.backend,
                     "k": self.data_blocks, "m": self.parity_blocks,
                     "blockSize": self.block_size, "blocks": blocks},
            blocks)

    def apply_matrix(self, rows: np.ndarray, shards) -> np.ndarray:
        """rows (GF) @ shards through this codec's engine; accepts
        (k, n) or batched (B, k, n) on device backends.  When the
        cross-request batcher is enabled the dispatch rides its
        combining queue (GET reconstruction and heal stripes from
        concurrent requests coalesce); the observed wall time then
        includes the combining window."""
        with self._dispatch("matmul", _nbytes(shards)):
            b = _batcher(self)
            if b is not None:
                return b.apply(self, "reconstruct", rows, shards)
            return self._apply_matrix(rows, shards)

    def _apply_matrix(self, rows: np.ndarray, shards,
                      op: str = "decode") -> np.ndarray:
        """The serial engine.  ``op`` (``encode`` / ``decode``) names
        the legs of a device form's dispatch (prep / upload / launch /
        fetch: rs_kernels.apply_matrix on one chip, rs_mesh.apply_matrix
        for the one sharded program of a mesh)."""
        if self.is_device:
            return self._impl.apply_matrix(rows, shards, op=op)
        shards = np.asarray(shards, dtype=np.uint8)
        if shards.ndim == 3:
            return np.stack([gf8.gf_matmul(rows, s) for s in shards])
        return gf8.gf_matmul(rows, shards)

    # -- coding ------------------------------------------------------------

    def _encode_parity_blocks(self, blocks: np.ndarray) -> np.ndarray:
        """(B, k, n) stripes -> (B, m, n) parity.  Routes through the
        shared cross-request batcher when enabled (concurrent PUTs'
        stripe batches coalesce into one padded device dispatch),
        otherwise the backend impl directly — bit-identical either way
        (stripes are batch-axis independent)."""
        b = _batcher(self)
        if b is not None:
            return b.apply(
                self, "encode",
                np.asarray(self.matrix)[self.data_blocks:], blocks)
        if self.is_device:
            return self._impl.encode_parity(
                blocks, self.parity_blocks, self.matrix)
        return np.stack([
            self._impl.encode_parity(blk, self.parity_blocks,
                                     self.matrix) for blk in blocks])

    def encode_data(self, data) -> list[np.ndarray]:
        """EncodeData (cmd/erasure-coding.go:70): split+encode one block.

        Returns k+m shards; empty input returns k+m empty shards.
        """
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) else data
        if buf.size == 0:
            return [np.zeros(0, dtype=np.uint8)
                    for _ in range(self.data_blocks + self.parity_blocks)]
        with _obstrace.span("tpu", "encode.prep", buf.nbytes):
            data_shards = gf8.split(buf, self.data_blocks)
        par = self._encode_parity_blocks(data_shards[None])[0]
        return [data_shards[i] for i in range(self.data_blocks)] + \
               [par[i] for i in range(self.parity_blocks)]

    def _reconstruct(self, shards, data_only: bool):
        lens = {len(s) for s in shards if s is not None and len(s) > 0}
        if len(lens) > 1:
            raise ErasureError("shard size mismatch")
        present = sum(_nbytes(s) for s in shards if s is not None)
        with self._dispatch("decode", present):
            b = _batcher(self)
            if b is not None:
                # shared survivor/solve logic (host) with the heavy
                # matmul routed through the combining queue: concurrent
                # decodes with the same missing pattern fuse into one
                # dispatch.  rs_kernels.reconstruct with a numpy apply
                # is bit-identical to gf8_ref.reconstruct (GF matrix
                # algebra is exact, so composed decode rows produce the
                # same bytes as decode-then-reencode).
                try:
                    from . import rs_kernels
                except ImportError:
                    b = None
                if b is not None:
                    return rs_kernels.reconstruct(
                        shards, self.data_blocks, self.parity_blocks,
                        data_only=data_only, matrix=self.matrix,
                        apply=lambda rows, surv: b.apply(
                            self, "decode", rows, surv))
            return self._impl.reconstruct(
                shards, self.data_blocks, self.parity_blocks,
                data_only=data_only, matrix=self.matrix)

    def decode_data_blocks(self, shards) -> list[np.ndarray]:
        """DecodeDataBlocks (cmd/erasure-coding.go:89): rebuild data only.

        Mirrors the reference's zero check exactly (it breaks on the first
        empty shard, so the count is 0 or 1): with no shard missing it is a
        no-op; otherwise reconstruction runs and fails if fewer than k shards
        survive -- including the all-empty case, which must surface an error
        rather than silently serving a truncated object.
        """
        n_zero = 0
        for s in shards:
            if s is None or len(s) == 0:
                n_zero += 1
                break
        if n_zero == 0 or n_zero == len(shards):
            return list(shards)
        return self._reconstruct(shards, data_only=True)

    def decode_data_and_parity_blocks(self, shards) -> list[np.ndarray]:
        """DecodeDataAndParityBlocks (cmd/erasure-coding.go:106)."""
        return self._reconstruct(shards, data_only=False)

    # -- shard math (cmd/erasure-coding.go:115-143) ------------------------

    def shard_size(self) -> int:
        return gf8.shard_size(self.block_size, self.data_blocks)

    def shard_file_size(self, total_length: int) -> int:
        return gf8.shard_file_size(
            self.block_size, self.data_blocks, total_length)

    def shard_file_offset(self, start_offset: int, length: int,
                          total_length: int) -> int:
        return gf8.shard_file_offset(
            self.block_size, self.data_blocks,
            start_offset, length, total_length)

    # -- batched whole-object path (TPU fast path) -------------------------

    def encode_object(self, data) -> list[np.ndarray]:
        """Encode a whole object's worth of bytes into per-disk shard files.

        Streams the reference's block loop (cmd/erasure-encode.go:80-107) as
        ONE batched device dispatch over all full blocks plus one small
        dispatch for the tail block.  Returns k+m shard-file byte arrays whose
        concatenated per-block layout matches block-by-block encode_data.
        """
        total = _nbytes(data)
        with self._dispatch("encode", total,
                            blocks=-(-total // self.block_size)):
            return self._encode_object(data)

    def _encode_object(self, data) -> list[np.ndarray]:
        # ``encode.prep`` is this function's host copies either side of
        # the parity dispatch: the body, the padded blocks, the shards
        with _obstrace.span("tpu", "encode.prep", _nbytes(data)):
            buf = np.frombuffer(bytes(data), dtype=np.uint8) \
                if not isinstance(data, np.ndarray) \
                else np.asarray(data, np.uint8).ravel()
            total = buf.size
            k, m = self.data_blocks, self.parity_blocks
            if total == 0:
                return [np.zeros(0, dtype=np.uint8) for _ in range(k + m)]
            bs = self.block_size
            ssize = self.shard_size()
            nfull = total // bs
            outs: list[list[np.ndarray]] = [[] for _ in range(k + m)]
            if nfull:
                blocks = buf[: nfull * bs].reshape(nfull, k, ssize) \
                    if bs == k * ssize else None
                if blocks is None:
                    # blockSize not divisible by k: per-block zero padding
                    blocks = np.zeros((nfull, k, ssize), dtype=np.uint8)
                    flat = buf[: nfull * bs].reshape(nfull, bs)
                    blocks.reshape(nfull, k * ssize)[:, :bs] = flat
        if nfull:
            par = self._encode_parity_blocks(blocks)
            with _obstrace.span("tpu", "encode.prep",
                                blocks.nbytes + par.nbytes):
                for i in range(k):
                    outs[i].append(
                        np.ascontiguousarray(blocks[:, i]).reshape(-1))
                for j in range(m):
                    outs[k + j].append(
                        np.ascontiguousarray(par[:, j]).reshape(-1))
        tail = buf[nfull * bs:]
        if tail.size:
            for i, s in enumerate(self.encode_data(tail)):
                outs[i].append(s)
        if len(outs[0]) == 1:          # full blocks only, or a tail only
            return [chunks[0] for chunks in outs]
        with _obstrace.span("tpu", "encode.prep", total * (k + m) // k):
            return [np.concatenate(chunks) for chunks in outs]

    def speedtest(self, size: int = 8 << 20, iters: int = 3) -> dict:
        """Timed probe of this codec's hot paths (the admin
        ``speedtest-tpu`` leg): whole-object batched encode and
        worst-case reconstruction (all m parity shards consumed to
        rebuild m lost data shards), after one untimed warmup so
        device backends measure steady-state, not compile time.

        Dispatches ride the normal encode/decode paths, so the probe
        itself lands in mt_tpu_* metrics and ``tpu`` spans like any
        production traffic."""
        import os as _os
        iters = max(1, int(iters))
        size = max(1, int(size))
        data = np.frombuffer(_os.urandom(size), dtype=np.uint8)
        self.encode_object(data)                      # warmup/compile
        t0 = time.monotonic()
        for _ in range(iters):
            shards = self.encode_object(data)
        encode_s = max(time.monotonic() - t0, 1e-9)
        # per-block decode with the first m data shards lost
        block = data[:min(self.block_size, size)]
        block_shards = self.encode_data(block)
        lost = list(block_shards)
        for i in range(min(self.parity_blocks, self.data_blocks)):
            lost[i] = None
        nblocks = max(1, size // max(len(block), 1))
        self.decode_data_blocks(list(lost))           # warmup
        t0 = time.monotonic()
        for _ in range(iters * nblocks):
            self.decode_data_blocks(list(lost))
        decode_s = max(time.monotonic() - t0, 1e-9)
        del shards
        gib = 1 << 30
        return {
            "encodeGiBps": round(size * iters / encode_s / gib, 3),
            "decodeGiBps": round(
                len(block) * iters * nblocks / decode_s / gib, 3),
            "bytes": size,
            "iters": iters,
            "k": self.data_blocks,
            "m": self.parity_blocks,
            "blockSize": self.block_size,
            "backend": self.backend,
        }

    # -- the object layer's two entries -------------------------------------

    def dispatch_devices(self) -> int:
        """Devices one dispatch of this codec spans — what a stream
        batch must fill.  On ``mesh`` it is the size of the mesh the
        encode is about to run on: a mesh that cannot be built fails
        the caller here rather than at the dispatch."""
        if self.backend != "mesh":
            return 1
        from ..parallel import mesh as pmesh
        return pmesh.get_active_mesh().devices.size

    def _fills_in_place(self, algo: str) -> bool:
        """True when :meth:`encode_framed` takes the host one-copy
        route.  Both natives must be present: without ``hh256_fill``
        the framed encode would be thrown away and done again by the
        copying route."""
        if self.backend != "numpy" or algo != bitrot.HIGHWAYHASH256S:
            return False
        from ..hashing.highwayhash import _get_lib
        from . import gf8_native
        return gf8_native.available() and _get_lib() is not None

    def framed_shape(self, total: int,
                     algo: str = bitrot.DEFAULT_BITROT_ALGORITHM
                     ) -> tuple[int, int] | None:
        """Shape of the buffer :meth:`encode_framed` fills in place for
        a ``total``-byte batch — lets the put pipeline acquire a
        recycled one (utils/bufpool.py) before encoding — or None when
        the route it will take makes its own rows (a device backend, a
        missing native library, an empty batch)."""
        if not total or not self._fills_in_place(algo):
            return None
        *_, flen = gf8.framed_layout(self.block_size, self.data_blocks,
                                     total)
        return (self.data_blocks + self.parity_blocks, flen)

    def encode_framed(self, data,
                      algo: str = bitrot.DEFAULT_BITROT_ALGORITHM,
                      out: np.ndarray | None = None) -> list:
        """One batch of body bytes -> the k+m bitrot-framed shard rows,
        each the final on-disk layout (``gf8.framed_layout``).  The one
        entry of every PUT path, and the one place the route is chosen:

          * ``mesh`` + HighwayHash256S: the fused multi-chip pipeline —
            parity via ICI XOR fan-in, per-shard digests all_gathered,
            one sharded dispatch per block batch (rs_mesh), counted
            and timed as ONE ``encode`` dispatch of the body's bytes,
            like ``encode_object``;
          * ``tpu`` + HighwayHash256S, a body of a block or more
            (:meth:`_encode_framed_chip`): the same contract on one
            chip for the full blocks — the stripes up, parity and the
            k+m digests down from fused programs (ops/rs_fused.py): ONE
            per stripe for a body of one block, ONE per stripe group of
            G for a body of several — framed on the host through views —
            and the route below for a tail block; counted and timed as ONE
            ``encode`` dispatch of the body's bytes too;
          * ``numpy`` with both native libraries: shard bytes and parity
            land once in the framed layout, digests filled in place by
            one GIL-free pass — into ``out`` when its shape is
            :meth:`framed_shape`'s, stale bytes and all;
          * otherwise ``encode_object`` (ONE parity dispatch) + streaming
            framing, with the digests from the device too when the
            codec runs there (op ``hash``: counted and timed like a
            codec dispatch; its kernels are the one-chip forms
            whichever device backend asked).

        Why a tail keeps two dispatches on one chip: a width is a
        program, and the fused program's build holds the interpreter
        several times longer than the two small programs it replaces
        (the kernel's body is ~10,000 traced operations).  A server
        meets ONE full-block width (``shard_size()``), which every
        object of a block or more is made of, and as many tail widths
        as its clients have object sizes: seven at once cost 17-23 s of
        a 50 s set-up (PERF.md section 6, PR 34 and PR 35).  The shape
        decides, nothing else."""
        device_hash = self.is_device and algo == bitrot.HIGHWAYHASH256S
        total = _nbytes(data)
        if device_hash and (self.backend == "mesh"
                            or total >= self.block_size):
            with self._dispatch("encode", total,
                                blocks=-(-total // self.block_size)):
                if self.backend == "tpu":
                    return list(self._encode_framed_chip(data))
                from . import rs_mesh
                return list(rs_mesh.encode_object_framed_fused(
                    self.data_blocks, self.parity_blocks,
                    self.block_size, data))
        ss = self.shard_size()
        if self._fills_in_place(algo):
            framed2d = self.encode_object_framed(data, out=out)
            if bitrot.fill_framed(framed2d, ss, algo):
                return list(framed2d)
        shards = self.encode_object(data)
        if self.is_device and bitrot.is_streaming(algo):
            return self._device_framed(shards)
        return bitrot.streaming_encode_batch(shards, ss, algo)

    def _device_framed(self, shards) -> list[bytes]:
        """The device bitrot leg over k+m equal shard files, as ONE
        ``hash`` dispatch counted and timed like a codec dispatch."""
        ss = self.shard_size()
        with dispatch_span(
                "hash", "tpu", sum(_nbytes(s) for s in shards),
                lambda: {"op": "hash", "shards": len(shards),
                         "shardSize": ss}):
            return _streaming_encode_batch_device(shards, ss)

    def _encode_bitrot(self, staged: np.ndarray):
        """(parity per stripe, digests) of one body's staged full-block
        stripes (``rs_fused.launch_encode_bitrot``'s contract), shared
        with concurrent PUTs through the combiner when the batcher is on
        (stripes are batch-axis independent, so each caller's slice is
        what it would get alone).  One block goes out one stripe per
        program, from the ``encode-bitrot`` bucket; several go out in
        stripe groups (``rs_fused.launch_encode_bitrot_groups``), from
        the ``encode-bitrot-group`` bucket, where the stripes of the
        bodies that meet fill the groups together."""
        from . import rs_fused
        k, m = self.data_blocks, self.parity_blocks
        rows = np.asarray(self.matrix)[k:]
        n = self.shard_size()
        op, launch = "encode-bitrot", rs_fused.launch_encode_bitrot
        if staged.shape[0] > 1 and rs_fused.group_plan(k, m, n)["bs"] > 1:
            op, launch = ("encode-bitrot-group",
                          rs_fused.launch_encode_bitrot_groups)
        b = _batcher(self)
        if b is None:
            return launch(rows, staged, n)()
        return b.submit(self, op, rows, staged,
                        fn=lambda rows, cat: launch(rows, cat, n)())

    def _encode_framed_chip(self, data, digest: int = 32) -> np.ndarray:
        """The one-chip device route of :meth:`encode_framed` for a body
        of a block or more: (k+m, framed_len) uint8, bit-identical to
        the host streaming-bitrot layout.  All full blocks go in one
        fused submission (:meth:`_encode_bitrot`: one block as one
        stripe, several as stripe groups).  ``encode.prep`` is the one
        copy of their bytes into staged stripes (row i of a stripe holds
        bytes [i*width, (i+1)*width) of its block, zeros after the
        block's end and up to the kernel's lane tile: no program pads);
        the dispatch's own legs are rs_fused's; ``hash.frame`` lands
        payloads, each stripe's parity (a view of its group's when the
        stripes went as groups) and the digests in the on-disk layout
        through views, one copy each.  A tail block takes the
        two-dispatch route (``encode_data``, then the device bitrot
        leg) and lands behind them."""
        from . import rs_fused
        k, m = self.data_blocks, self.parity_blocks
        bs, width = self.block_size, self.shard_size()
        buf = np.frombuffer(data, dtype=np.uint8) \
            if not isinstance(data, np.ndarray) \
            else np.asarray(data, np.uint8).ravel()
        nfull, tail_len, _, flen = gf8.framed_layout(bs, k, buf.size,
                                                     digest)
        with _obstrace.span("tpu", "encode.prep", nbytes=nfull * bs):
            staged = np.zeros(
                (nfull, k, rs_fused.staged_width(k, m, width)), np.uint8)
            src = buf[:nfull * bs].reshape(nfull, bs)
            whole, rest = divmod(bs, width)
            staged[:, :whole, :width] = \
                src[:, :whole * width].reshape(nfull, whole, width)
            if rest:
                staged[:, whole, :rest] = src[:, whole * width:]
        parity, digs = self._encode_bitrot(staged)
        tail = self._device_framed(self.encode_data(buf[nfull * bs:])) \
            if tail_len else None
        F = digest + width
        with _obstrace.span("tpu", "hash.frame", nbytes=(k + m) * flen):
            out = np.empty((k + m, flen), dtype=np.uint8)
            frames = out[:, :nfull * F].reshape(k + m, nfull, F)
            frames[:k, :, digest:] = \
                staged[:, :, :width].transpose(1, 0, 2)
            for b, par in enumerate(parity):
                frames[k:, b, digest:] = par[:, :width]
            frames[:, :, :digest] = digs.transpose(1, 0, 2)
            if tail:
                for row, framed in zip(out, tail):
                    row[nfull * F:] = np.frombuffer(framed, np.uint8)
        return out

    def reconstruct_files(self, surviving, present, wanted,
                          part_size: int,
                          block_size: int | None = None
                          ) -> list[np.ndarray]:
        """Whole shard files for the ``wanted`` indices (data or parity)
        from the k ``surviving`` ones (unframed, in ``present``'s
        order) over ``part_size`` bytes of object — degraded GET and
        heal.  The decode rows are solved once; the survivor pattern is
        the same across all full stripes, so they go in ONE batched
        dispatch and the short tail stripe in a second.  ``block_size``
        is the object's persisted one where it differs from this
        codec's.  A device dispatch is counted (op ``matmul``) and may
        ride the batcher; the host engine is called bare."""
        k = self.data_blocks
        bs = self.block_size if block_size is None else block_size
        ssize = gf8.shard_size(bs, k)
        nfull, tail = divmod(part_size, bs)
        rows = gf8.decode_rows(self.matrix, k, list(present), list(wanted))
        apply = self.apply_matrix if self.is_device else self._apply_matrix
        outs = [np.empty(gf8.shard_file_size(bs, k, part_size),
                         dtype=np.uint8) for _ in wanted]
        if nfull:
            surv = np.stack([s[: nfull * ssize].reshape(nfull, ssize)
                             for s in surviving], axis=1)  # (nfull, k, ssize)
            reb = apply(rows, surv)
            for j, o in enumerate(outs):
                o[: nfull * ssize] = reb[:, j].reshape(-1)
        if tail:
            t_ssize = gf8.ceil_frac(tail, k)
            surv_t = np.stack([s[nfull * ssize: nfull * ssize + t_ssize]
                               for s in surviving])        # (k, t_ssize)
            reb_t = apply(rows, surv_t)
            for j, o in enumerate(outs):
                o[nfull * ssize:] = reb_t[j]
        return outs

    def encode_object_framed(self, data, digest: int = 32,
                             out: np.ndarray | None = None) -> np.ndarray:
        """Encode a whole object straight into bitrot-framed shard files.

        Returns (k+m, framed_len) uint8 where each row is the final
        on-disk layout [digest-slot][block] per erasure block
        (cmd/bitrot-streaming.go framing around cmd/erasure-encode.go
        blocks).  Digest slots are left ZEROED for the caller to fill
        in place (hashing.highwayhash.hh256_fill).  One copy total:
        data bytes land once in their final frame position; parity is
        computed by the native kernel directly into its frame payloads.
        Requires the native GF8 library (:meth:`encode_framed` takes
        the encode_object + streaming framing route without it)."""
        total = _nbytes(data)
        with self._dispatch("encode-framed", total,
                            blocks=-(-total // self.block_size)):
            return self._encode_object_framed(data, digest, out)

    def _encode_object_framed(self, data, digest: int = 32,
                              out: np.ndarray | None = None) -> np.ndarray:
        from . import gf8_native
        assert gf8_native.available()
        # zero-copy view: bytes AND memoryview slices (the put path
        # feeds whole-body memoryviews) frame without materializing
        buf = np.frombuffer(data, dtype=np.uint8) \
            if not isinstance(data, np.ndarray) \
            else np.asarray(data, np.uint8).ravel()
        total = buf.size
        k, m = self.data_blocks, self.parity_blocks
        bs = self.block_size
        ssize = self.shard_size()
        nfull, tail_len, tail_ss, flen = gf8.framed_layout(
            bs, k, total, digest)
        F = digest + ssize
        # np.empty + targeted clears: every payload byte is overwritten
        # below (data copy / native parity matmul), so a full calloc
        # would memset ~6 MB per 4 MiB object only to overwrite it.
        # Only the digest slots and the short-row padding gaps need
        # zeroing (framing contract: digest filled later in place,
        # padding must be zero for bit-identical shard math).  A
        # recycled ``out`` (bufpool) relies on the same targeted
        # clears, so stale bytes from the previous batch never leak.
        if out is None or out.shape != (k + m, flen) \
                or out.dtype != np.uint8:
            out = np.empty((k + m, flen), dtype=np.uint8)
        if nfull:
            fview = out[:, :nfull * F].reshape(k + m, nfull, F)
            fview[:, :, :digest] = 0                  # digest slots
            for i in range(k):                        # short data rows
                ln = min(ssize, max(0, bs - i * ssize))
                if ln < ssize:
                    fview[i, :, digest + ln:] = 0
        if tail_len:
            out[:, nfull * F:] = 0                    # whole tail frame
        parity_rows = np.asarray(self.matrix)[k:]
        if nfull:
            src = buf[:nfull * bs].reshape(nfull, bs)
            dview = out[:, :nfull * F].reshape(k + m, nfull, F)
            for i in range(k):
                lo = i * ssize
                ln = min(ssize, bs - lo)
                dview[i, :, digest:digest + ln] = src[:, lo:lo + ln]
            if m:
                for b in range(nfull):
                    base = b * F + digest
                    gf8_native.matmul_into(
                        parity_rows, out[:k, base:base + ssize],
                        out[k:, base:base + ssize])
        if tail_len:
            base = nfull * F + digest
            tsrc = buf[nfull * bs:]
            for i in range(k):
                lo = i * tail_ss
                ln = max(0, min(tail_ss, tail_len - lo))
                if ln:
                    out[i, base:base + ln] = tsrc[lo:lo + ln]
            if m and tail_ss:
                gf8_native.matmul_into(
                    parity_rows, out[:k, base:base + tail_ss],
                    out[k:, base:base + tail_ss])
        return out

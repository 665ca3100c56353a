"""erasureObjects — object CRUD on one erasure set (cmd/erasure-object.go).

The TPU-first redesign of the reference's hot path:

  * PUT (ref: cmd/erasure-object.go:614 + cmd/erasure-encode.go): the whole
    object is encoded as ONE batched device dispatch (all stripes at once,
    minio_tpu/ops/codec.encode_object) instead of a per-10MiB-block loop;
    bitrot framing is applied per shard file; staged writes then an atomic
    quorum rename_data commit, exactly the reference's tmp+rename contract.
  * GET (ref: cmd/erasure-object.go:242 + cmd/erasure-decode.go): read the
    k cheapest shard files, verify bitrot per block, and if any shard is
    missing/corrupt reconstruct ALL stripes in one batched device call
    (same missing pattern across a part's stripes -> one compiled kernel).
  * HEAL (ref: cmd/erasure-healing.go:233): decode + re-encode on device,
    write healed shards to stale disks with quorum-1 tolerance.

Fan-out to drives uses a thread pool (goroutine-per-disk analog,
cmd/erasure-encode.go:36 parallelWriter) with quorum error reduction.
"""

from __future__ import annotations

import collections
import hashlib
import os
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from ..admin.metrics import GLOBAL as _metrics
from ..admin.metrics import KERNEL_BUCKETS
from ..hashing import bitrot, md5fast
from ..obs import critpath as _critpath
from ..obs import stages as _stages
from ..obs import trace as _trace
from ..ops import gf8
from ..ops.codec import Erasure
from ..storage import errors as serrors
from ..storage.api import StorageAPI
from ..storage.writers import WriterPlane
from ..utils import bufpool
from ..storage.datatypes import (ChecksumInfo, ErasureInfo, FileInfo,
                                 ObjectPartInfo, now_ns)
from ..storage import xl_storage as _xl
from ..storage.xl_storage import SYS_DIR
from . import metadata as meta
from .interface import (BucketExists, BucketInfo, BucketNotEmpty,
                        BucketNotFound, ListObjectsInfo, MethodNotAllowed,
                        ObjectInfo, ObjectLayer, ObjectNotFound,
                        ObjectOptions, PutObjectOptions, ReadQuorumError,
                        VersionNotFound, WriteQuorumError)
from .multipart import MultipartOps

# local drive fan-out runs serially on single-core hosts (the pool only
# adds queue/lock churn there); MT_FORCE_POOL=1 restores the pool.
# Remote drives always keep the pool: their RPCs overlap network waits
# regardless of core count (see _serial_fanout in __init__).
_SINGLE_CORE = (os.cpu_count() or 2) <= 1 and \
    os.environ.get("MT_FORCE_POOL", "0") == "0"


def _strict_compat() -> bool:
    """True unless the reference's hidden --no-compat perf mode is on
    (cmd/common-main.go:208-210).  Empty/whitespace/cased values of
    MT_NO_COMPAT mean OFF — only an explicit truthy value disables
    strict S3 compatibility."""
    return os.environ.get("MT_NO_COMPAT", "0").strip().lower() in (
        "", "0", "off", "false", "no")


def _md5_timed(clock, fn, *args):
    """One piece of the ETag md5, its wall charged to stage ``md5`` of
    ``clock`` (the submitting request's StageClock: pool threads carry
    none).  Always as async detail: on the pool the md5 overlaps encode
    and commit, and on the request thread it sits inside a serial stage
    or ``other``, so the serial vector reads what it read without it."""
    t0 = time.monotonic_ns()
    try:
        return fn(*args)
    finally:
        if clock is not None:
            clock.add_async("md5", time.monotonic_ns() - t0)


DEFAULT_BLOCK_SIZE = 10 * 1024 * 1024   # blockSizeV1 (cmd/object-api-common.go:32)
INLINE_THRESHOLD = 128 * 1024           # small-object inline into xl.meta
ETAG_KEY = "etag"
# how long a node may answer from memory what only make/delete_bucket or
# a first bucket configuration changes: the bucket-existence cache below
# and the cached "no document" of BucketMetadataSys
BUCKET_TTL_S = 3.0
# streaming pipeline batch: stripes are encoded/decoded this many bytes at
# a time so memory is O(batch * n/k) regardless of object size, while each
# device dispatch still carries enough stripes to fill the MXU
# (cmd/erasure-encode.go:80-107 block loop, widened for TPU batching)
STREAM_BATCH_BYTES = int(os.environ.get("MT_STREAM_BATCH",
                                        64 * 1024 * 1024))


class _LockedStream:
    """Iterator holding a DRWMutex until exhausted/closed/GC'd; the
    unlock runs exactly once (see _locked_stream)."""

    def __init__(self, lk, inner, on_close=None):
        self._lk = lk
        self._inner = inner
        self._on_close = on_close
        self._done = False

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        try:
            return next(self._inner)
        except BaseException:
            self.close()
            raise

    def close(self) -> None:
        if self._done:
            return
        self._done = True
        try:
            close = getattr(self._inner, "close", None)
            if close is not None:
                close()
        finally:
            try:
                self._lk.unlock()
            finally:
                if self._on_close is not None:
                    self._on_close()

    def __del__(self):
        self.close()


def _read_full(source, n: int) -> bytes:
    """Read exactly n bytes from a file-like source unless EOF comes
    first (sockets and chunked decoders return short reads)."""
    chunks = []
    remaining = n
    while remaining > 0:
        c = source.read(remaining)
        if not c:
            break
        chunks.append(c)
        remaining -= len(c)
    return chunks[0] if len(chunks) == 1 else b"".join(chunks)


def default_parity_count(drive_count: int) -> int:
    """Default parity by set size (cmd/format-erasure.go:896-906)."""
    if drive_count <= 1:
        return 0
    if drive_count <= 3:
        return 1
    if drive_count <= 5:
        return 2
    if drive_count <= 7:
        return 3
    return 4


class ErasureObjects(MultipartOps, ObjectLayer):
    """One erasure set over `len(disks)` drives (cmd/erasure.go:48)."""

    def __init__(self, disks: list[Optional[StorageAPI]],
                 parity: Optional[int] = None,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 backend: str = "auto",
                 bitrot_algo: str = bitrot.DEFAULT_BITROT_ALGORITHM,
                 inline_threshold: int = INLINE_THRESHOLD,
                 enforce_min_part_size: bool = True,
                 ns_lock=None):
        if not disks:
            raise ValueError("no disks")
        self.disks = list(disks)
        n = len(disks)
        self.parity = default_parity_count(n) if parity is None else parity
        self.data_blocks = n - self.parity
        if self.data_blocks <= 0:
            raise ValueError("parity too large for drive count")
        self.block_size = block_size
        self.backend = backend
        if not bitrot.available(bitrot_algo):
            # fail at construction, not on the first read: an unknown
            # algo would write shards that can never be verified back
            raise ValueError(f"unknown bitrot algorithm {bitrot_algo!r}")
        self.bitrot_algo = bitrot_algo
        self.inline_threshold = inline_threshold
        self.enforce_min_part_size = enforce_min_part_size
        if ns_lock is None:
            from ..parallel.dsync import NamespaceLock
            ns_lock = NamespaceLock()
        self.ns_lock = ns_lock
        # sized for REQUEST concurrency x drive fan-out: the reference
        # runs a goroutine per disk per request (parallelWriter,
        # cmd/erasure-encode.go:36); a pool of exactly n workers would
        # serialize concurrent PUTs behind one request's drive writes
        self._pool = ThreadPoolExecutor(max_workers=min(4 * max(4, n), 64))
        self._codec = Erasure(self.data_blocks, self.parity, block_size,
                              backend=backend) if self.parity > 0 else None
        # per-storage-class codecs (x-amz-storage-class picks parity per
        # object; geometry persists in each version's ErasureInfo)
        self._codecs: dict[int, "Erasure"] = {}
        # MRF hook (cmd/erasure-object.go:1141 addPartial): a background
        # MRFQueue attaches here; post-quorum partial writes are enqueued
        self.mrf = None
        # serial fan-out only when single-core AND all drives are local:
        # remote RPCs overlap network waits in threads on any core count
        self._serial_fanout = _SINGLE_CORE and all(
            d is None or getattr(d, "is_local", lambda: True)()
            for d in self.disks)
        # listing cache (cmd/metacache-manager.go): snapshots persist
        # through the drives' system volume; local writes invalidate
        from .metacache import MetacacheManager
        self.metacache = MetacacheManager(
            disks=[d for d in self.disks if d is not None],
            sys_volume=SYS_DIR)
        # bucket-existence cache (bucketMetadataSys role for the hot
        # path): a 16-drive stat fan-out per request re-verifies a fact
        # that changes only through make/delete_bucket.  TTL-bounded for
        # out-of-band wipes; a majority VolumeNotFound at commit time
        # also evicts and surfaces BucketNotFound (see _commit_put).
        self._bucket_ttl = BUCKET_TTL_S
        self._buckets_seen: dict[str, float] = {}
        # pipelined PUT data plane (storage/writers.py): one persistent
        # writer thread per drive with a bounded in-order queue, shared
        # by streaming PUT, the overlapped bytes commit, multipart part
        # uploads and heal writes.  Knobs come from the ``pipeline``
        # kvconfig subsystem (env-overridable at construction; the
        # server re-reads them on admin SetConfigKV) and are consulted
        # live — the queue bound is a callable into this layer.
        self._pipe_depth = 2
        self._pipe_queue_depth = 2
        try:
            from ..utils.kvconfig import Config as _KVConfig
            self.reload_pipeline_config(_KVConfig())
        except Exception:  # noqa: BLE001 — defaults above already set
            self._pipe_depth = 0 if self._serial_fanout else 2
        self._write_plane = WriterPlane(
            queue_depth=lambda: self._pipe_queue_depth)
        # last streaming PUT's overlap numbers (mt_put_pipeline_* scrape
        # + bench.py's pipelined leg read these)
        self._pipe_stats: dict = {}
        # hot-read plane (objectlayer/hotread.py): single-flight GET
        # coalescing + the hot-object cache.  Zero owned threads;
        # knobs ride the process-global ``cache`` kvconfig subsystem
        # (S3Server.reload_cache_config pushes admin SetConfigKV and
        # wires the api_stats admission heat source)
        from .hotread import HotReadPlane
        self.hotread = HotReadPlane(self)

    def reload_pipeline_config(self, config) -> None:
        """(Re)read the ``pipeline`` kvconfig knobs — at construction
        (env > defaults) and from the server after admin SetConfigKV so
        depth changes retune a live layer.  Single-core all-local hosts
        keep the serial fan-out (same reasoning as _serial_fanout: the
        threads only add churn there); tests force the pipeline by
        assigning _pipe_depth directly."""
        try:
            depth = int(config.get("pipeline", "depth"))
        except (KeyError, ValueError):
            depth = 2
        try:
            qd = int(config.get("pipeline", "queue_depth"))
        except (KeyError, ValueError):
            qd = 2
        self._pipe_depth = 0 if self._serial_fanout else max(0, depth)
        self._pipe_queue_depth = max(1, qd)
        try:
            self._mesh_batch_cap = max(
                STREAM_BATCH_BYTES,
                int(config.get("pipeline", "mesh_batch_bytes")))
        except (KeyError, ValueError):
            # the registered default, not a guess: a malformed knob
            # value must not silently shrink the mesh batch cap
            self._mesh_batch_cap = max(STREAM_BATCH_BYTES, 268435456)
        try:
            md5fast.SCHED.set_lanes(int(config.get("pipeline",
                                                   "md5_lanes")))
        except (KeyError, ValueError):
            pass
        try:
            md5fast.set_backend(config.get("pipeline", "md5_backend"))
        except KeyError:
            pass

    def _pipeline_on(self) -> bool:
        return self._pipe_depth > 0

    # -- drive fan-out helpers --------------------------------------------

    def _fanout_items(self, fn, items, ends=None, plane=None, inline=None):
        """Run fn(item) concurrently over arbitrary items; returns
        (results, errs) aligned with items (parallelWriter/Reader
        analog, cmd/erasure-encode.go:36).  On a single-core host the
        thread pool buys nothing (local drive ops barely release the
        GIL) and costs queue/lock churn per item — run serially there.

        ``ends`` (optional, pre-sized to ``len(items)``): each child's
        completion time in monotonic ns lands at its item position —
        the completion vector the quorum critical-path engine
        (obs/critpath.py) reduces.

        ``plane`` (optional, ``meta`` / ``get`` / ``delete``): the
        runner also takes each child's START, and after the gather the
        CALLING thread folds the children into the read-leg family
        under ``op=<plane>``: ``leg="queue"`` = start_i - submit per
        child (its wait for a pool thread and for the GIL to start),
        ``leg="gather"`` = caller resumed - last end.  One registry
        call per leg and fan-out; the drive call between a child's
        start and end is in ``mt_drive_call_seconds``.

        ``inline`` (optional, ``(positions, run)``): the items at
        ``positions`` are not submitted; once the others are, the
        CALLING thread runs ``run()``, which returns ``(result, error,
        start_ns, end_ns)`` for each of them in order, and they fold
        with the children (an inline item's ``queue`` is its start -
        submit too)."""

        def run(x):
            try:
                return fn(x), None
            except Exception as e:  # noqa: BLE001 — per-item isolation
                return None, e

        starts = [0] * len(items) if plane is not None and items else None
        if starts is not None and ends is None:
            ends = [0] * len(items)
        if ends is None and inline is None:
            runner, seq = run, items
        else:
            def runner(pair):
                if starts is not None:
                    starts[pair[0]] = time.monotonic_ns()
                out = run(pair[1])
                if ends is not None:
                    ends[pair[0]] = time.monotonic_ns()
                return out
            seq = list(enumerate(items))
            if inline is not None:
                here = set(inline[0])
                seq = [p for p in seq if p[0] not in here]
        submit = time.monotonic_ns()
        if self._serial_fanout or not seq:
            out = [runner(x) for x in seq]
        else:
            # submitted here; the inline items run while these do
            out = self._pool.map(self._with_request_id(runner), seq)
        if inline is not None:
            got = [None] * len(items)
            for i, (r, e, s0, e0) in zip(inline[0], inline[1]()):
                got[i] = r, e
                if starts is not None:
                    starts[i] = s0
                if ends is not None:
                    ends[i] = e0
            for (i, _), r in zip(seq, out):
                got[i] = r
            out = got
        else:
            out = list(out)
        if starts is not None:
            resumed = time.monotonic_ns()
            family = _trace.LEG_FAMILIES["read"]
            _metrics.observe_many(
                family, {"op": plane, "leg": "queue"},
                [(s0 - submit) / 1e9 for s0 in starts],
                buckets=KERNEL_BUCKETS)
            _metrics.observe(
                family, {"op": plane, "leg": "gather"},
                (resumed - max(ends)) / 1e9, buckets=KERNEL_BUCKETS)
        return [r for r, _ in out], [e for _, e in out]

    @staticmethod
    def _with_request_id(run):
        """Carry the caller's request ID (plus its X-ray stage clock
        and causal span parent) into pool threads: contextvars do not
        cross thread boundaries, and pool workers are REUSED — setting
        unconditionally (even to ""/None) also clears a previous
        request's context, so per-drive spans never mislabel, stage
        detail never lands on the wrong request, and drive-op spans
        parent under the submitting span in the request's tree (the
        span-discipline lint pins this shape)."""
        rid = _trace.get_request_id()
        parent = _trace.get_span_parent()
        clock = _stages.current()

        def run_ctx(x):
            _trace.set_request_id(rid)
            _trace.set_span_parent(parent)
            _stages.set_clock(clock)
            return run(x)

        return run_ctx

    def _fanout(self, fn, disks=None, ends=None, plane=None, inline=None):
        """fn(disk) on every drive concurrently; offline (None) drives
        report DiskNotFound in the aligned error list.  ``ends``,
        ``plane`` and ``inline`` as in :meth:`_fanout_items`."""

        def run(d):
            if d is None:
                raise serrors.DiskNotFound("offline")
            return fn(d)

        return self._fanout_items(run,
                                  self.disks if disks is None else disks,
                                  ends=ends, plane=plane, inline=inline)

    def _fanout_indexed(self, fn, shuffled_disks, ends=None):
        """fn((shard_idx, disk)) per drive, aligned errors; offline drives
        report DiskNotFound.  ``ends`` as in :meth:`_fanout_items`."""

        def run(pair):
            if pair[1] is None:
                return None, serrors.DiskNotFound("offline")
            try:
                out = fn(pair), None
            except Exception as e:  # noqa: BLE001
                out = None, e
            if ends is not None:
                ends[pair[0]] = time.monotonic_ns()
            return out

        if self._serial_fanout:
            out = [run(p) for p in enumerate(shuffled_disks)]
        else:
            out = list(self._pool.map(self._with_request_id(run),
                                      enumerate(shuffled_disks)))
        return [r for r, _ in out], [e for _, e in out]

    @staticmethod
    def _drive_labels(disks) -> list[str]:
        return [_critpath.drive_label(d) if d is not None else "offline"
                for d in disks]

    def _geometry(self, parity_override: int | None) -> tuple[int, int]:
        """(k, m) for a write: the layer default or a per-request parity
        from the storage class (cmd/erasure-object.go:631-642)."""
        n = len(self.disks)
        if parity_override is None:
            return self.data_blocks, self.parity
        m = parity_override
        if not 0 < m <= n // 2:
            raise ValueError(f"parity {m} out of range for {n} drives")
        return n - m, m

    def _codec_for(self, parity: int) -> "Erasure":
        """Codec for a parity count (cached; default reuses the layer's)."""
        if parity == self.parity and self._codec is not None:
            return self._codec
        codec = self._codecs.get(parity)
        if codec is None:
            n = len(self.disks)
            codec = Erasure(n - parity, parity, self.block_size,
                            backend=self.backend)
            self._codecs[parity] = codec
        return codec

    def _write_quorum(self, fi: FileInfo | None = None) -> int:
        if fi is not None:
            _, wq = meta.object_quorum_from_meta(fi)
            return wq
        wq = self.data_blocks
        if self.data_blocks == self.parity:
            wq += 1
        return wq

    # -- bucket ops (cmd/erasure-bucket.go) --------------------------------

    def make_bucket(self, bucket: str) -> None:
        _, errs = self._fanout(lambda d: d.make_vol(bucket))
        if sum(1 for e in errs if isinstance(e, serrors.VolumeExists)) \
                >= self._write_quorum():
            raise BucketExists(bucket)
        try:
            meta.reduce_errs(
                [None if isinstance(e, serrors.VolumeExists) else e
                 for e in errs],
                self._write_quorum(), WriteQuorumError)
        except serrors.StorageError as e:
            raise WriteQuorumError(str(e)) from e

    def get_bucket_info(self, bucket: str) -> BucketInfo:
        res, errs = self._fanout(lambda d: d.stat_vol(bucket))
        for r in res:
            if r is not None:
                return BucketInfo(r.name, r.created)
        raise BucketNotFound(bucket)

    def list_buckets(self) -> list[BucketInfo]:
        res, _ = self._fanout(lambda d: d.list_vols())
        seen: dict[str, BucketInfo] = {}
        for vols in res:
            if vols is None:
                continue
            for v in vols:
                seen.setdefault(v.name, BucketInfo(v.name, v.created))
        return sorted(seen.values(), key=lambda b: b.name)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        self._buckets_seen.pop(bucket, None)
        self.get_bucket_info(bucket)
        _, errs = self._fanout(lambda d: d.delete_vol(bucket, force))
        if any(isinstance(e, serrors.VolumeNotEmpty) for e in errs) \
                and not force:
            raise BucketNotEmpty(bucket)
        # the whole namespace went away: fence + release every cached
        # hot-read window of the bucket (hits were already safe — their
        # quorum revalidation now raises — this frees the bytes)
        plane = getattr(self, "hotread", None)
        if plane is not None:
            plane.invalidate_bucket(bucket)

    def _check_bucket(self, bucket: str) -> None:
        exp = self._buckets_seen.get(bucket)
        if exp is not None and time.monotonic() < exp:
            return
        self.get_bucket_info(bucket)
        self._buckets_seen[bucket] = time.monotonic() + self._bucket_ttl

    # -- PUT (cmd/erasure-object.go:614 putObject) ------------------------

    def put_object(self, bucket: str, object_name: str, data,
                   opts: Optional[PutObjectOptions] = None) -> ObjectInfo:
        """PUT from bytes or a file-like reader.  Anything larger than one
        stream batch goes through the block-batched pipeline so memory
        stays O(batch) (cmd/erasure-encode.go:80-107); smaller bodies take
        the single-dispatch fast path."""
        opts = opts or PutObjectOptions()
        if hasattr(data, "read"):
            return self.put_object_stream(bucket, object_name, data, opts)
        data = bytes(data) if not isinstance(data, bytes) else data
        if len(data) > STREAM_BATCH_BYTES:
            # zero-copy hand-off: feed the streaming pipeline memoryview
            # slices of the body instead of re-buffering the whole
            # object through io.BytesIO (one full-object copy saved)
            batch = self._stream_batch_size()
            mv = memoryview(data)
            chunks = (mv[o:o + batch] for o in range(0, len(mv), batch))
            return self._put_object_streaming(bucket, object_name,
                                              chunks, opts,
                                              readahead_body=False)
        return self._put_object_bytes(bucket, object_name, data, opts)

    def _stream_batch_size(self) -> int:
        """Whole-stripe stream batch (cmd/erasure-encode.go block loop,
        widened for TPU batching): a multiple of block_size so framing
        stays batch-invariant.

        When one codec dispatch spans several devices (a mesh) the
        batch additionally scales with their count (capped by
        ``pipeline.mesh_batch_bytes``): one huge object's stripes must
        fill the whole stripe axis per dispatch, or a 5 TiB PUT
        saturates one chip while the rest idle — the single-transfer
        form of ISSUE 12 tentpole c.  Framing is batch-invariant, so
        the on-disk result is bit-identical at any batch size
        (test_put_pipeline's contract)."""
        blocks = max(1, STREAM_BATCH_BYTES // self.block_size)
        codec = self._codec
        devs = codec.dispatch_devices() if codec is not None else 1
        if devs > 1:
            cap = max(1, self._mesh_batch_cap // self.block_size)
            blocks = max(blocks, min(blocks * devs, cap))
        return blocks * self.block_size

    def put_object_stream(self, bucket: str, object_name: str, reader,
                          opts: Optional[PutObjectOptions] = None
                          ) -> ObjectInfo:
        opts = opts or PutObjectOptions()
        # fail BEFORE touching the body: without this a PUT to a dead
        # bucket drains a full stream batch first (the re-check inside
        # either branch below rides the TTL cache, so this costs one
        # stat fan-out per TTL, not per PUT)
        self._check_bucket(bucket)
        batch = self._stream_batch_size()
        first = _read_full(reader, batch)
        if len(first) < batch:     # whole object fits one batch
            return self._put_object_bytes(bucket, object_name, first, opts)

        def _chunks():
            c = first
            while c:
                yield c
                if len(c) < batch:
                    return
                c = _read_full(reader, batch)

        return self._put_object_streaming(bucket, object_name, _chunks(),
                                          opts, readahead_body=True)

    def _put_object_bytes(self, bucket: str, object_name: str, data: bytes,
                          opts: PutObjectOptions) -> ObjectInfo:
        self._check_bucket(bucket)
        n = len(self.disks)
        k, m = self._geometry(opts.parity)
        # Overlap the ETag md5 with erasure encode + bitrot framing:
        # hashlib releases the GIL for large buffers, and so does the
        # native gf8 matmul, so on multi-core hosts the two truly run
        # in parallel (the reference overlaps its hash.Reader with the
        # erasure goroutines the same way, pkg/hash/reader.go).  On a
        # single-core host the handoff is pure overhead — skip it.
        etag_future = None
        if (not _SINGLE_CORE and len(data) >= (1 << 20)
                and (opts.content_md5 or _strict_compat()) and m > 0):
            # md5_of routes through the lane scheduler in 1 MiB slices:
            # concurrent PUTs' ETag passes coalesce into one multi-lane
            # native call instead of running two full serial chains
            etag_future = self._pool.submit(
                _md5_timed, _stages.current(), md5fast.md5_of, data)
        etag = None if etag_future is not None \
            else self._etag_for(data, opts)
        mod_time = opts.mod_time or now_ns()
        version_id = opts.version_id or (
            str(uuid.uuid4()) if opts.versioned else "")
        distribution = meta.hash_order(f"{bucket}/{object_name}", n)
        size = len(data)

        fi = FileInfo(
            volume=bucket, name=object_name, version_id=version_id,
            data_dir=str(uuid.uuid4()), mod_time=mod_time, size=size,
            metadata={ETAG_KEY: etag, **opts.user_defined},
            parts=[ObjectPartInfo(1, size, size, etag, mod_time)],
            erasure=ErasureInfo(
                data_blocks=k, parity_blocks=m, block_size=self.block_size,
                distribution=distribution,
                checksums=[ChecksumInfo(1, self.bitrot_algo)]),
            fresh=True)

        with _stages.stage("encode"):
            framed = self._encode_and_frame(data, m, fi)
        inline = size <= self.inline_threshold
        shuffled = meta.shuffle_disks(self.disks, distribution)
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=True)  # cmd/erasure-object.go:729-735 nsLock
        try:
            with _stages.stage("drive_commit"):
                if etag_future is not None and not inline \
                        and self._pipeline_on():
                    # overlapped commit: the writer plane lands the
                    # part bytes in their final data dirs WHILE the
                    # md5 still runs; only the xl.meta version merge
                    # waits for the digest.  Without this the hash
                    # overlapped encode alone and the whole drive
                    # fan-out trailed it serially — the dominant
                    # serial residue of BENCH_r05.
                    return self._commit_put_overlapped(
                        bucket, object_name, fi, framed, shuffled,
                        etag_future, opts, mod_time, size)
                if etag_future is not None:
                    self._stamp_etag(fi, etag_future.result(), opts,
                                     size, mod_time)
                return self._commit_put(bucket, object_name, fi, framed,
                                        inline, shuffled)
        finally:
            lk.unlock()

    def _commit_put_overlapped(self, bucket, object_name, fi, framed,
                               shuffled, etag_future, opts, mod_time,
                               size) -> ObjectInfo:
        """Overlapped single-part commit: the usual one-call-per-drive
        write_data_commit fan-out, but each drive writes its part bytes
        FIRST and parks on an etag gate before the xl.meta merge — so
        the md5's tail runs beside the whole drive fan-out instead of
        serializing ahead of it (pkg/hash/reader.go overlap carried
        through the commit).  A pool task resolves the gate the moment
        the digest lands; by the time a drive finishes its part bytes
        the gate is normally already open.  On BadDigest every gate
        aborts before any version became visible and the orphan data
        dirs are purged — the failed PUT leaves the same nothing the
        serial path leaves."""
        import threading as _threading
        wq = self._write_quorum(fi)
        gate = _threading.Event()
        state: dict = {}
        committed = False

        def meta_gate() -> dict:
            gate.wait()
            vd = state.get("vdict")
            if vd is None:          # digest failed: leave no version
                raise serrors.StorageError("commit aborted (BadDigest)")
            return vd

        # a drive's writer thread asks before it would park: with the
        # digest still out it runs its batch's other bodies first
        meta_gate.ready = gate.is_set

        def resolve():
            try:
                self._stamp_etag(fi, etag_future.result(), opts, size,
                                 mod_time)
                state["vdict"] = fi.to_dict()
            finally:
                gate.set()

        def write_one(idx, disk):
            disk.write_data_commit(bucket, object_name, fi, framed[idx],
                                   shard_index=idx + 1,
                                   meta_gate=meta_gate)

        # the resolver is SUBMITTED AFTER the md5 task and BEFORE the
        # fan-out: FIFO start order guarantees it runs even with every
        # fan-out worker parked on the gate (and on the writer plane
        # the fan-out consumes no pool workers at all — the gate park
        # happens on the drive writer threads, where batch-mates wait
        # behind it while the resolver runs on the freed pool)
        resolver = self._pool.submit(resolve)
        try:
            errs = self._commit_fanout(write_one, shuffled, wq, framed)
            resolver.result()       # BadDigest outranks quorum errors
            try:
                meta.reduce_errs(errs, wq, WriteQuorumError)
            except serrors.VolumeNotFound:
                self._buckets_seen.pop(bucket, None)
                raise BucketNotFound(bucket) from None
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            committed = True
            if self.mrf is not None and any(e is not None for e in errs):
                self.mrf.add(bucket, object_name, fi.version_id)
            self._hot_invalidate(bucket, object_name)
            self.metacache.invalidate(bucket)
            return self._to_object_info(fi)
        finally:
            gate.set()              # parked workers must never outlive us
            if not committed and state.get("vdict") is None:
                # no xl.meta anywhere: purge the orphan data dirs (a
                # failed digest check must leave no trace; partial
                # metadata failures belong to the scanner/heal, as
                # with the non-gated path)
                ddir = f"{object_name}/{fi.data_dir}"

                def _purge(d):
                    if d is not None:
                        d.delete(bucket, ddir, recursive=True)

                self._fanout_items(_purge, shuffled)

    def health(self, maintenance: bool = False) -> dict:
        """Cluster-health heuristic (cmd/erasure-server-pool.go:1462):
        healthy iff every erasure set keeps write quorum, counting only
        online drives; under maintenance=True, LOCAL drives are
        excluded — the answer to "can this node be taken down safely".
        healing_drives counts drives mid-heal (orchestrators must not
        pull a node while its drives are being rebuilt)."""
        wq = self._write_quorum()
        up = 0
        healing = 0
        for d in self.disks:
            if d is None:
                continue
            try:
                if not d.is_online():
                    continue
            except Exception:  # noqa: BLE001 — dead drive is offline
                continue
            if getattr(d, "healing", False):
                healing += 1
            if maintenance and d.is_local():
                continue
            up += 1
        return {"healthy": up >= wq and (not maintenance or healing == 0),
                "write_quorum": wq, "healing_drives": healing,
                "online_drives": up}

    def _etag_for(self, data: bytes, opts: PutObjectOptions) -> str:
        """ETag per the reference's hash.Reader semantics: md5 when the
        client sent Content-MD5 (verified) or in strict-compat mode
        (the default, cmd/common-main.go:208); random-with-hyphen under
        --no-compat (MT_NO_COMPAT=1), skipping the md5 pass entirely
        (pkg/hash/reader.go:186, cmd/object-api-utils.go:843-855)."""
        if opts.content_md5 or (opts.preserve_etag is None
                                and _strict_compat()):
            etag = _md5_timed(_stages.current(), md5fast.md5,
                              data).hexdigest()
            if opts.content_md5 and etag != opts.content_md5.lower():
                raise serrors.StorageError(
                    "Content-MD5 mismatch (BadDigest)")
            if opts.preserve_etag is None:
                return etag
        if opts.preserve_etag is not None:
            return opts.preserve_etag
        return uuid.uuid4().hex[:32] + "-1"

    def _stamp_etag(self, fi: FileInfo, md5obj, opts: PutObjectOptions,
                    size: int, mod_time: int) -> None:
        """Resolve the single-part ETag from a finished md5 (random-
        with-hyphen under --no-compat when ``md5obj`` is None), enforce
        Content-MD5 (BadDigest on mismatch), and stamp fi's size/
        metadata/parts — the ONE definition of commit-time digest
        semantics shared by the serial bytes path, the overlapped
        commit resolver, and both streaming loops."""
        if md5obj is not None:
            etag = md5obj.hexdigest()
            if opts.content_md5 and etag != opts.content_md5.lower():
                raise serrors.StorageError(
                    "Content-MD5 mismatch (BadDigest)")
        else:
            etag = uuid.uuid4().hex[:32] + "-1"
        if opts.preserve_etag is not None:
            etag = opts.preserve_etag
        fi.size = size
        fi.metadata = {ETAG_KEY: etag, **opts.user_defined}
        fi.parts = [ObjectPartInfo(1, size, size, etag, mod_time)]

    def _encode_and_frame(self, data: bytes, m: int, fi: FileInfo,
                          out=None):
        """Erasure-encode + bitrot-frame one batch of blocks into the
        per-drive framed rows: ``Erasure.encode_framed``, where the
        route is chosen (``out``: a recycled buffer of its
        ``framed_shape``).  Without parity the body is the one shard
        and only the framing remains."""
        if m > 0:
            return self._codec_for(m).encode_framed(
                data, self.bitrot_algo, out=out)
        return bitrot.streaming_encode_batch(
            [np.frombuffer(data, dtype=np.uint8)],
            fi.erasure.shard_size(), self.bitrot_algo)

    def _commit_fanout(self, write_one, shuffled, wq, framed) -> list:
        """One commit-class fan-out (one storage call per drive) with
        its quorum critical-path row.  With the pipeline on, the ops
        ride the per-drive writer plane, where CONCURRENT streams'
        commit ops coalesce into group commits — one flush settles
        many streams' writes (storage/commit.py) — and the queue bound
        widens to the group batch size so one object's whole fan-out
        enqueues without parking on itself.  The staged framed bytes
        charge the memory governor (kind=commit) while queued: a burst
        of tiny PUTs sheds 503 instead of growing every drive queue
        unbounded, and the charge releases when the stream settles —
        including death by drive error or PlaneClosed (the finally) or
        an abandoned stream (Charge.__del__).  Serial/pool fan-out
        otherwise (single-core all-local hosts)."""
        if not self._pipeline_on():
            t0 = _critpath.now_ns()
            ends = [0] * len(shuffled)
            _, errs = self._fanout_indexed(
                lambda pair: write_one(pair[0], pair[1]), shuffled,
                ends=ends)
            _critpath.record("write", wq, self._drive_labels(shuffled),
                             ends, t0, errs=errs)
            return errs
        from ..storage import commit as commitcfg
        from ..utils.memgov import GOVERNOR
        charge = GOVERNOR.charge(
            sum(len(s) for s in framed) if framed is not None else 0,
            "commit")
        sw = self._write_plane.stream(shuffled)
        bound = max(self._write_plane.queue_bound(),
                    commitcfg.CONFIG.max_batch)
        t0 = _critpath.now_ns()
        try:
            for i in range(len(shuffled)):
                sw.submit(i, write_one, bound=bound)
            sw.drain()
        except BaseException:
            sw.abort()
            sw.drain(5.0)
            raise
        finally:
            charge.release()
        sw.record_gating("write", wq, t0)
        return list(sw.errs)

    def _commit_put(self, bucket, object_name, fi, framed, inline,
                    shuffled) -> ObjectInfo:
        from ..storage import commit as commitcfg
        # packed band: past the inline threshold (below it xl.meta —
        # written regardless — carries the payload for free) but small
        # enough that the per-object data-dir mkdir + part-file
        # create/fsync trio dominates the commit: the framed shard
        # rides the drive's append-only segment instead, one batched
        # fsync pair covering every packed batch-mate.  Keyed off the
        # writer plane: grouping is a concurrency play — a lone stream
        # on a serial-fanout host pays journal overhead with no group
        # to amortize it (measured slower than eager), so packing only
        # engages where batches can actually form
        packed = (not inline and self._pipeline_on()
                  and commitcfg.CONFIG.on()
                  and 0 < fi.size <= commitcfg.CONFIG.pack_threshold
                  and len(fi.parts) == 1 and bool(fi.data_dir))
        if packed:
            fi.data_dir = ""        # the segment extent replaces it
        # serialize the version ONCE; each drive patches only its shard
        # index (the fan-out previously deep-cloned FileInfo+ErasureInfo
        # per drive — pure Python overhead on the PUT hot path)
        vdict = None if inline else fi.to_dict()

        def write_one(idx, disk):
            if inline:
                dfi = FileInfo(**{**fi.__dict__})
                dfi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
                dfi.erasure.index = idx + 1
                blob = framed[idx]
                dfi.inline_data = blob if isinstance(blob, bytes) \
                    else bytes(memoryview(blob).cast("B"))
                dfi.data_dir = ""
                disk.write_metadata(bucket, object_name, dfi)
            elif packed:
                blob = framed[idx]
                blob = blob if isinstance(blob, bytes) \
                    else bytes(memoryview(blob).cast("B"))
                disk.write_packed(bucket, object_name, fi, blob,
                                  shard_index=idx + 1,
                                  version_dict=vdict)
            else:
                # composite commit: one storage call (one RPC on remote
                # drives), direct final-location write on local ones
                disk.write_data_commit(bucket, object_name, fi,
                                       framed[idx],
                                       shard_index=idx + 1,
                                       version_dict=vdict)
            return idx

        wq = self._write_quorum(fi)
        errs = self._commit_fanout(write_one, shuffled, wq, framed)
        try:
            meta.reduce_errs(errs, wq, WriteQuorumError)
        except serrors.VolumeNotFound:
            # bucket wiped out-of-band while the existence cache was
            # warm: evict and report what a fresh stat would have said
            self._buckets_seen.pop(bucket, None)
            raise BucketNotFound(bucket) from None
        except serrors.StorageError as e:
            raise WriteQuorumError(str(e)) from e
        # failed writes become heal candidates (MRF analog,
        # cmd/erasure-object.go:783-789): quorum met but some drive
        # missed the write — queue a prompt re-heal
        if self.mrf is not None and any(e is not None for e in errs):
            self.mrf.add(bucket, object_name, fi.version_id)
        self._hot_invalidate(bucket, object_name)
        self.metacache.invalidate(bucket)
        return self._to_object_info(fi)

    def _put_object_streaming(self, bucket: str, object_name: str,
                              chunks, opts: PutObjectOptions,
                              readahead_body: bool = True) -> ObjectInfo:
        """Block-batched streaming PUT over an iterator of body chunks
        (each chunk one stream batch; only the final chunk may be
        short).  Two data planes with bit-identical on-disk results
        (tests/test_put_pipeline.py pins the contract):

          * pipelined (default): per-drive writer queues overlap batch
            N+1's encode with batch N's create/append fan-out, the ETag
            md5 runs as a chained pool task beside both, and framed
            buffers recycle through utils/bufpool — the reference's
            hash.Reader-beside-erasure-goroutines overlap
            (pkg/hash/reader.go + cmd/erasure-encode.go:80-107
            parallelWriter), batched the TPU way;
          * serial (pipeline.depth=0, single-core all-local hosts):
            the original per-batch fan-out round-trips.

        Commit stays a single quorum rename_data at EOF
        (cmd/erasure-object.go:772-779)."""
        self._check_bucket(bucket)
        n = len(self.disks)
        k, m = self._geometry(opts.parity)
        mod_time = opts.mod_time or now_ns()
        version_id = opts.version_id or (
            str(uuid.uuid4()) if opts.versioned else "")
        distribution = meta.hash_order(f"{bucket}/{object_name}", n)
        fi = FileInfo(
            volume=bucket, name=object_name, version_id=version_id,
            data_dir=str(uuid.uuid4()), mod_time=mod_time, size=0,
            metadata={**opts.user_defined},
            erasure=ErasureInfo(
                data_blocks=k, parity_blocks=m, block_size=self.block_size,
                distribution=distribution,
                checksums=[ChecksumInfo(1, self.bitrot_algo)]),
            fresh=True)
        shuffled = meta.shuffle_disks(self.disks, distribution)
        wq = self._write_quorum(fi)
        # mesh-scaled encode batches charge the node memory governor
        # for the stream's lifetime (the PR-11 deferred follow-up):
        # ``pipeline.depth`` batches of body plus the one in hand are
        # live at once, so a mesh-widened batch is pressure the
        # watermark must admit BEFORE the body is drained (over it,
        # the S3 front sheds 503 + Retry-After instead of OOMing)
        charge = self._batch_charge(-1, slots=self._pipe_depth + 1)
        try:
            if self._pipeline_on():
                return self._stream_put_pipelined(
                    bucket, object_name, chunks, opts, fi, m, shuffled,
                    wq, mod_time, readahead_body)
            return self._stream_put_serial(
                bucket, object_name, chunks, opts, fi, m, shuffled, wq,
                mod_time, readahead_body)
        finally:
            if charge is not None:
                charge.release()

    @staticmethod
    def _md5_link(prev, h, chunk, stats, clock) -> None:
        """One chained md5 update on the pool: waits for the previous
        link (updates are order-dependent), then hashes its chunk
        through the shared lane scheduler — concurrent streams'/parts'
        links coalesce into one multi-lane native call
        (hashing/md5fast.py; a lone stream degenerates to the plain
        fast core).  Native and hashlib updates both release the GIL,
        so the chain truly runs beside encode and the writer queues.
        The chain never deadlocks the pool: each link waits only on an
        EARLIER submission, and the executor starts tasks FIFO.

        ``md5_s`` is the link's WALL time: under concurrent streams it
        includes lane-scheduler sharing (parking while another stream's
        combiner hashes this chunk, or combining other streams'
        chunks), so per-PUT md5_s is a utilization view, not a pure
        hash cost — single-stream runs (the bench's pipelined leg) are
        unaffected.  The same wall is stage ``md5`` of the submitting
        request's ``clock`` (:func:`_md5_timed`)."""
        if prev is not None:
            prev.result()
        t0 = time.perf_counter()
        _md5_timed(clock, md5fast.SCHED.update, h, chunk)
        stats["md5_s"] += time.perf_counter() - t0

    def _encode_framed_pooled(self, chunk, m: int, fi: FileInfo, stats):
        """Encode + frame one batch, recycling the framed 2-D buffer
        through utils/bufpool when the codec's route fills one in place
        (``framed_shape``).  Returns (framed_rows, release_cb) —
        release fires once every drive wrote the batch (memory stays
        O(depth x batch))."""
        t0 = time.perf_counter()
        try:
            # a real stage frame (not a finally-add): time the codec
            # batcher parks inside (batch_wait) is subtracted as child
            # time, keeping the serial reconciliation exact on device
            # backends too
            with _stages.stage("encode"):
                shape = self._codec_for(m).framed_shape(
                    len(chunk), self.bitrot_algo) if m > 0 else None
                if shape is None:
                    return self._encode_and_frame(chunk, m, fi), None
                buf = bufpool.GLOBAL.acquire(shape)
                framed = self._encode_and_frame(chunk, m, fi, out=buf)
                return framed, (lambda: bufpool.GLOBAL.release(buf))
        finally:
            stats["encode_s"] += time.perf_counter() - t0

    def _pump_put_pipeline(self, chunks, sw, m, fi, md5, stats,
                           write_batch_for, wq) -> tuple[int, int]:
        """The shared stage driver of every pipelined upload (streaming
        PUT and multipart parts): chained md5 on the pool, encode into
        a recycled buffer, per-drive writer queues — batches in flight
        bounded to ``pipeline.depth`` (O(depth x batch) memory) and
        quorum re-checked as completions drain, so latched errors end
        the stream early instead of encoding the rest of a doomed body.
        ``write_batch_for(framed)`` returns the per-drive write for one
        batch's framed rows.  Returns (total_bytes, batches)."""
        n = len(self.disks)
        depth = max(1, self._pipe_depth)
        md5_links: collections.deque = collections.deque()
        inflight: collections.deque = collections.deque()
        total = batches = 0
        clock = _stages.current()
        for chunk in chunks:
            total += len(chunk)
            batches += 1
            if md5 is not None:
                md5_links.append(self._pool.submit(
                    self._md5_link,
                    md5_links[-1] if md5_links else None,
                    md5, chunk, stats, clock))
                while len(md5_links) > depth:
                    md5_links.popleft().result()
            framed, release = self._encode_framed_pooled(
                chunk, m, fi, stats)
            inflight.append(sw.submit_batch(write_batch_for(framed),
                                            release=release))
            while len(inflight) > depth:
                # depth-bound backpressure: the pipeline is full, the
                # request thread parks behind the writer plane
                t0 = time.perf_counter()
                inflight.popleft().done.wait()
                _stages.add("write_enqueue",
                            int((time.perf_counter() - t0) * 1e9))
            alive = sw.alive()
            if alive < wq:
                sw.abort()
                raise WriteQuorumError(
                    f"{alive} of {n} drives writable, need {wq}")
        for f in md5_links:
            f.result()
        return total, batches

    def _stream_put_pipelined(self, bucket, object_name, chunks, opts,
                              fi, m, shuffled, wq, mod_time,
                              readahead_body) -> ObjectInfo:
        """The pipelined loop: body readahead -> chained md5 -> encode
        into a recycled buffer -> per-drive writer queues.  Per drive
        the op order is strictly create, then appends, then rename_data
        (single writer thread per drive, FIFO queue); errors latch per
        drive and quorum is re-checked as completions drain."""
        from ..utils.readahead import readahead
        n = len(self.disks)
        tmps: list[str | None] = [None] * n
        md5 = md5fast.md5() if (opts.content_md5 or _strict_compat()) \
            else None
        stats = {"md5_s": 0.0, "encode_s": 0.0}
        depth = max(1, self._pipe_depth)
        sw = self._write_plane.stream(shuffled)
        src = None
        t_wall0 = time.perf_counter()
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=True)
        try:
            # started only after the lock is held and inside the try: a
            # lock failure must not leave a thread draining the body
            # socket with no close().  depth-1 queued + one in hand =
            # ``pipeline.depth`` batches of body in flight.
            src = readahead(chunks, depth=max(1, depth - 1)) \
                if readahead_body else chunks

            def write_batch_for(framed):
                def write_batch(idx, disk):
                    if tmps[idx] is None:
                        # tmp_dir here, ON the drive's writer (an RPC
                        # on remote drives): only this worker touches
                        # tmps[idx] until the stream drains
                        tmps[idx] = disk.tmp_dir()
                        disk.create_file(SYS_DIR, f"{tmps[idx]}/part.1",
                                         framed[idx])
                    else:
                        disk.append_file(SYS_DIR, f"{tmps[idx]}/part.1",
                                         framed[idx])
                return write_batch

            total, batches = self._pump_put_pipeline(
                src, sw, m, fi, md5, stats, write_batch_for, wq)
            self._stamp_etag(fi, md5, opts, total, mod_time)
            with _stages.stage("write_drain"):
                t_drain = _critpath.now_ns()
                sw.drain()
                sw.record_gating("write_drain", wq, t_drain)
            alive = sw.alive()
            if alive < wq:
                raise WriteQuorumError(
                    f"{alive} of {n} drives writable, need {wq}")
            # queues are DRAINED here: a lock whose grants lapsed while
            # the body streamed must abort before any commit op is
            # queued (drwmutex refresh-loss semantics)
            if hasattr(lk, "ensure_valid"):
                lk.ensure_valid()

            def commit_one(idx, disk):
                dfi = FileInfo(**{**fi.__dict__})
                dfi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
                dfi.erasure.index = idx + 1
                disk.rename_data(SYS_DIR, tmps[idx], dfi, bucket,
                                 object_name)

            with _stages.stage("drive_commit"):
                t_commit = _critpath.now_ns()
                sw.submit_batch(commit_one)
                sw.drain()
                sw.record_gating("commit", wq, t_commit)
            cerrs = list(sw.errs)
            try:
                meta.reduce_errs(cerrs, wq, WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            if self.mrf is not None and any(e is not None for e in cerrs):
                self.mrf.add(bucket, object_name, fi.version_id)
            self._hot_invalidate(bucket, object_name)
            self.metacache.invalidate(bucket)
            wall = time.perf_counter() - t_wall0
            write_s = sw.max_busy_s()
            crit = max(stats["md5_s"], stats["encode_s"], write_s)
            self._pipe_stats = {
                "wall_s": wall, "md5_s": stats["md5_s"],
                "encode_s": stats["encode_s"], "write_s": write_s,
                "batches": batches, "bytes": total,
                "overlap_efficiency": crit / wall if wall > 0 else 0.0,
            }
            return self._to_object_info(fi)
        finally:
            if src is not None and readahead_body:
                src.close()  # stop + JOIN the readahead thread: the
                             # handler reuses the body socket next
            sw.abort()
            # settle the queues before tmp cleanup — a worker must not
            # append into a dir being removed (bounded wait: a hung
            # drive op must not wedge the handler thread forever)
            sw.drain(timeout=10.0)
            lk.unlock()
            # when_drive_idle: immediate for settled drives; a drive
            # hung past the drain timeout cleans at op settlement, so
            # its resumed append (makedirs exist_ok) cannot resurrect
            # the tmp dir after the rmtree.  tmps[idx] is read at FIRE
            # time: a first-batch op still stuck inside tmp_dir() has
            # not assigned it yet — eager binding would skip the drive
            # and leak whatever the resumed op stages
            def _clean_tmp_cb(d, i):
                if tmps[i] is not None:
                    d.clean_tmp(tmps[i])

            for idx, disk in enumerate(shuffled):
                if disk is not None:
                    sw.when_drive_idle(
                        idx, lambda d=disk, i=idx: _clean_tmp_cb(d, i))

    def _stream_put_serial(self, bucket, object_name, chunks, opts, fi,
                           m, shuffled, wq, mod_time,
                           readahead_body) -> ObjectInfo:
        """The original serial loop: one synchronous fan-out round per
        batch.  Kept verbatim as the reference semantics (the pipelined
        plane must match it byte for byte) and as the single-core
        fallback."""
        n = len(self.disks)
        tmps: list[str | None] = [None] * n
        errs: list[Exception | None] = [None] * n
        # md5 only when the client sent Content-MD5 or in strict-compat
        # mode — same policy as _etag_for (pkg/hash/reader.go:186)
        md5 = md5fast.md5() if (opts.content_md5 or _strict_compat()) \
            else None
        total = 0

        # readahead on the body: the network read of batch N+1 overlaps
        # batch N's encode + drive writes (klauspost/readahead role,
        # cmd/xl-storage.go:1544-1546)
        from ..utils.readahead import readahead

        src = None
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=True)
        try:
            # started only after the lock is held and inside the try:
            # a lock failure must not leave a thread draining the body
            # socket with no close()
            src = readahead(chunks, depth=1) if readahead_body else chunks
            for chunk in src:
                if md5 is not None:
                    _md5_timed(_stages.current(), md5.update, chunk)
                total += len(chunk)
                with _stages.stage("encode"):
                    framed = self._encode_and_frame(chunk, m, fi)

                def write_batch(idx_disk):
                    idx, disk = idx_disk
                    if disk is None or errs[idx] is not None:
                        return  # dead drive: a later append would corrupt
                    if tmps[idx] is None:
                        tmps[idx] = disk.tmp_dir()
                        disk.create_file(SYS_DIR, f"{tmps[idx]}/part.1",
                                         framed[idx])
                    else:
                        disk.append_file(SYS_DIR, f"{tmps[idx]}/part.1",
                                         framed[idx])

                with _stages.stage("drive_commit"):
                    _, werrs = self._fanout_indexed(write_batch,
                                                    shuffled)
                for i, e in enumerate(werrs):
                    if e is not None and errs[i] is None:
                        errs[i] = e
                alive = sum(1 for i, d in enumerate(shuffled)
                            if d is not None and errs[i] is None)
                if alive < wq:
                    raise WriteQuorumError(
                        f"{alive} of {n} drives writable, need {wq}")
            self._stamp_etag(fi, md5, opts, total, mod_time)
            # the lock was held across the whole body stream; if its
            # grants fell below quorum meanwhile, committing would race
            # a new writer (drwmutex refresh-loss semantics)
            if hasattr(lk, "ensure_valid"):
                lk.ensure_valid()

            def commit_one(idx_disk):
                idx, disk = idx_disk
                if disk is None:
                    raise serrors.DiskNotFound("offline")
                if errs[idx] is not None:
                    raise errs[idx]
                dfi = FileInfo(**{**fi.__dict__})
                dfi.erasure = ErasureInfo(**{**fi.erasure.__dict__})
                dfi.erasure.index = idx + 1
                disk.rename_data(SYS_DIR, tmps[idx], dfi, bucket,
                                 object_name)

            t0 = _critpath.now_ns()
            cends = [0] * len(shuffled)
            _, cerrs = self._fanout_indexed(commit_one, shuffled,
                                            ends=cends)
            _critpath.record("commit", wq, self._drive_labels(shuffled),
                             cends, t0, errs=cerrs)
            try:
                meta.reduce_errs(cerrs, wq, WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            if self.mrf is not None and any(e is not None for e in cerrs):
                self.mrf.add(bucket, object_name, fi.version_id)
            self._hot_invalidate(bucket, object_name)
            self.metacache.invalidate(bucket)
            return self._to_object_info(fi)
        finally:
            if src is not None and readahead_body:
                src.close()  # stop + JOIN the readahead thread: the
                             # handler reuses the body socket next
            lk.unlock()
            for idx, disk in enumerate(shuffled):
                if disk is not None and tmps[idx] is not None:
                    try:
                        disk.clean_tmp(tmps[idx])
                    except Exception:  # noqa: BLE001 — best-effort cleanup
                        pass

    # -- GET (cmd/erasure-object.go:242 getObjectWithFileInfo) -------------

    def _read_quorum_fileinfo(self, bucket: str, object_name: str,
                              version_id: Optional[str] = None
                              ) -> tuple[FileInfo, list[FileInfo | None]]:
        """One quorum metadata read: ``read_version`` on every drive,
        then the pick of the FileInfo a read quorum agrees on.  Every
        caller (HEAD, GET, the hot-read validation and leader, metadata
        updates) is timed here and nowhere else: stage ``meta_read``
        and leg ``meta.fanout`` are this whole interval on the caller's
        thread, ``meta.pick`` the interpreter's part after the gather;
        the children are plane ``meta`` of :meth:`_fanout_items`.

        The drives a read wave can read (local, online, the native
        library loaded, no group collector on this thread:
        ``xl_storage.wave_positions``) are read by ONE native call on
        this thread (``xl_storage.read_version_wave``), after the other
        drives' children are submitted, so it overlaps them; every other
        drive is a pool child as before.  ``mt_read_meta_drives_total
        {route=wave|pool}`` counts the drives each route read."""
        with _stages.stage("meta_read"), \
                _trace.span("read", "meta.fanout"):
            t0 = _critpath.now_ns()
            ends = [0] * len(self.disks)
            here = _xl.wave_positions(self.disks)
            fis, errs = self._fanout(
                lambda d: d.read_version(bucket, object_name, version_id),
                ends=ends, plane="meta",
                inline=(here, lambda: _xl.read_version_wave(
                    [self.disks[i] for i in here], bucket, object_name,
                    version_id)) if here else None)
            if here:
                _metrics.inc("mt_read_meta_drives_total",
                             {"route": "wave"}, len(here))
            if len(here) < len(self.disks):
                _metrics.inc("mt_read_meta_drives_total",
                             {"route": "pool"},
                             len(self.disks) - len(here))
            with _trace.span("read", "meta.pick"):
                nf = sum(1 for e in errs
                         if isinstance(e, (serrors.FileNotFound,
                                           serrors.FileVersionNotFound)))
                if nf > len(self.disks) // 2:
                    if version_id is not None and any(
                            isinstance(e, serrors.FileVersionNotFound)
                            for e in errs):
                        raise VersionNotFound(
                            f"{bucket}/{object_name}@{version_id}")
                    raise ObjectNotFound(f"{bucket}/{object_name}")
                quorum = max(1, len(self.disks) // 2)
                fi = meta.find_file_info_in_quorum(fis, quorum)
            _critpath.record("read_meta", quorum,
                             self._drive_labels(self.disks), ends, t0,
                             errs=errs)
        return fi, fis

    def get_object_info(self, bucket: str, object_name: str,
                        opts: Optional[ObjectOptions] = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        self._check_bucket(bucket)
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=False)   # rlock, as GetObjectInfo does
        try:
            fi, _ = self._read_quorum_fileinfo(bucket, object_name,
                                               opts.version_id)
            return self._to_object_info(fi)
        finally:
            lk.unlock()

    def get_object(self, bucket: str, object_name: str, offset: int = 0,
                   length: int = -1,
                   opts: Optional[ObjectOptions] = None
                   ) -> tuple[ObjectInfo, bytes]:
        # fully-buffered read: joins immediately, so the readahead
        # thread would add overhead with zero overlap to exploit
        info, gen = self.get_object_reader(bucket, object_name, offset,
                                           length, opts, _readahead=False)
        return info, b"".join(gen)

    def get_object_reader(self, bucket: str, object_name: str,
                          offset: int = 0, length: int = -1,
                          opts: Optional[ObjectOptions] = None,
                          _readahead: bool = True):
        """Range GET as (info, chunk iterator): reads ONLY the shard byte
        ranges covering the requested blocks (ShardFileOffset math,
        cmd/erasure-coding.go:134 + cmd/erasure-decode.go:229-246) and
        decodes batch-of-blocks at a time, so a 1 MiB range of a 100 GiB
        object touches one block per shard and memory stays O(batch)."""
        opts = opts or ObjectOptions()
        # hot-read plane first: concurrent readers of one window share
        # ONE drive read + decode, and hot windows serve straight from
        # the validated cache.  Every non-happy path returns None and
        # falls through here, so the reference error semantics below
        # stay the single source of truth.
        plane = self.hotread
        if plane is not None:
            with _stages.stage("cache"):
                served = plane.serve(bucket, object_name, offset,
                                     length, opts)
            if served is not None:
                return served
        self._check_bucket(bucket)
        # read lock for the duration of the stream (GetObjectNInfo takes
        # the nsLock RLock, cmd/erasure-object.go:136): a reader racing a
        # PUT/DELETE commit must never observe a half-renamed version set
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=False)
        try:
            fi, fis = self._read_quorum_fileinfo(bucket, object_name,
                                                 opts.version_id)
            if fi.deleted:
                raise MethodNotAllowed(f"{bucket}/{object_name} is a "
                                       "delete marker")
            # HTTP range semantics in one pass (cmd/httprange.go):
            # negative offset = suffix (last -offset bytes); length < 0 =
            # to end; overlong ranges clamp; start past EOF is invalid
            size = fi.size
            if offset < 0:
                offset = max(0, size + offset)
            if length < 0:
                length = size - offset
            if offset > size or (size > 0 and offset == size):
                from .interface import InvalidRange
                raise InvalidRange(f"{offset}+{length} vs {size}")
            length = min(length, size - offset)
            info = self._to_object_info(fi)
        except BaseException:
            lk.unlock()
            raise
        if size == 0 or length == 0:
            lk.unlock()
            return info, iter(())
        # mesh-scaled decode batches charge the node memory governor
        # for the stream's lifetime (the PR-11 deferred follow-up): a
        # GET whose batch the mesh widened past the base is real
        # memory pressure the watermark must see (release on close)
        try:
            charge = self._batch_charge(length)
        except BaseException:
            lk.unlock()
            raise
        gen = self._locked_stream(
            lk, self._stream_range(bucket, object_name, fi, fis,
                                   offset, length),
            on_close=(charge.release if charge is not None else None))
        if not _readahead:
            return info, gen
        # readahead: block batch N+1's shard reads + decode overlap the
        # consumer sending batch N (klauspost/readahead role, go.mod:39;
        # pipeline overlap of cmd/bitrot-streaming.go:74-89).  Depth
        # follows the ``pipeline.depth`` knob minus the batch in the
        # consumer's hand, so PUT and GET share one memory bound
        # (default depth 2 -> queue 1, full double-buffering at half
        # the buffered memory — the RSS gate in test_streaming bounds
        # the whole pipeline)
        from ..utils.readahead import readahead
        return info, readahead(gen, depth=max(1, self._pipe_depth - 1))

    @staticmethod
    def _locked_stream(lk, inner, on_close=None):
        """Hold a lock until the stream is exhausted or abandoned.

        NOT a generator on purpose: per PEP 342, closing/GC-ing a
        generator that was never advanced does not run its body, so a
        try/finally inside one never executes and the lock would leak
        forever (the refresh keepalive keeps the grant alive).  This
        wrapper unlocks exactly once on exhaustion, error, close(), or
        GC — advanced or not."""
        return _LockedStream(lk, inner, on_close)

    def _batch_charge(self, active_bytes: int, slots: int = 2):
        """Governor charge for one stream's batch working set — only
        when the MESH scaling widened the batch past the base
        ``STREAM_BATCH_BYTES`` (the base bound predates the governor
        and is fenced by the RSS tests; the scaled portion is the new
        pressure ``pipeline.mesh_batch_bytes`` caps but nothing
        previously accounted).  ``slots`` ≈ live copies of one batch
        (framed shards + assembled payload for GET; queued encode
        buffers for PUT).  Returns None when no charge applies; raises
        MemoryPressure past the watermark (the S3 front sheds 503)."""
        batch = self._stream_batch_size()
        if batch <= STREAM_BATCH_BYTES:
            return None
        est = batch if active_bytes < 0 else min(batch, active_bytes)
        if est <= STREAM_BATCH_BYTES:
            return None
        from ..utils.memgov import GOVERNOR
        return GOVERNOR.charge(est * max(1, slots), "pipeline")

    def _hot_fileinfo(self, bucket: str, object_name: str,
                      version_id: Optional[str]):
        """Hot-read plane validation read: one ns-read-locked quorum
        metadata pass, returning ``(fi, info)`` — the identity a cache
        hit compares before serving (diskcache.py ETag-validation
        role, quorum-consistent so a committed overwrite on ANY node
        is always seen)."""
        self._check_bucket(bucket)
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=False)
        try:
            fi, _ = self._read_quorum_fileinfo(bucket, object_name,
                                               version_id)
            return fi, self._to_object_info(fi)
        finally:
            lk.unlock()

    def _hot_read_window(self, bucket: str, object_name: str,
                         version_id: Optional[str], start: int,
                         wlen: int):
        """Hot-read plane leader fetch: ONE ns-read-locked pass
        resolving quorum metadata and decoding the window's plain
        bytes (inline-tiny objects serve straight from the metadata
        quorum read — ``_stream_range`` reads ``inline_data`` without
        any drive data fan-out).  Returns ``(fi, info, data)``; data
        is None for delete markers and out-of-range starts (the
        caller falls through to the reference error path)."""
        self._check_bucket(bucket)
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=False)
        try:
            fi, fis = self._read_quorum_fileinfo(bucket, object_name,
                                                 version_id)
            info = self._to_object_info(fi)
            if fi.deleted:
                return fi, info, None
            size = fi.size
            if size == 0:
                return fi, info, b""
            if start >= size:
                return fi, info, None
            n = min(wlen, size - start)
            data = b"".join(self._stream_range(bucket, object_name,
                                               fi, fis, start, n))
            return fi, info, data
        finally:
            lk.unlock()

    def _hot_invalidate(self, bucket: str, object_name: str) -> None:
        """Write-path fence: called inside every ns-write-locked
        commit section BEFORE the write is acknowledged, so cached
        windows are gone and straddling fills are refused by the time
        any client can observe the new version."""
        plane = getattr(self, "hotread", None)
        if plane is not None:
            plane.invalidate(bucket, object_name)

    def _stream_range(self, bucket: str, object_name: str, fi: FileInfo,
                      fis: list[FileInfo | None], offset: int, length: int):
        """Generator over the requested byte range, block-batch at a time.
        Shard-read failures extend into parity shards (parallelReader,
        cmd/erasure-decode.go:120-188); a failed shard stays dead for the
        remainder of the stream."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        nsh = k + m
        bs = fi.erasure.block_size
        ssize = fi.erasure.shard_size()
        algo = self.bitrot_algo
        hlen = bitrot.digest_size(algo) if bitrot.is_streaming(algo) else 0
        shuffled = meta.shuffle_disks(self.disks, fi.erasure.distribution)
        sfis = meta.shuffle_parts_metadata(fis, fi.erasure.distribution)
        # mesh codecs widen the decode batch with the device count the
        # same way the PUT batch scales (_stream_batch_size): one huge
        # GET's reconstruct dispatches fill the stripe axis
        batch_blocks = max(1, self._stream_batch_size() // bs)
        dead: set[int] = set(
            j for j in range(nsh) if shuffled[j] is None)
        end = offset + length
        part_start = 0
        for part in fi.parts:
            if part_start + part.size <= offset:
                part_start += part.size
                continue
            if part_start >= end:
                break
            p0 = max(0, offset - part_start)
            p1 = min(part.size, end - part_start)
            sfsize = fi.erasure.shard_file_size(part.size)
            b0 = p0 // bs
            bend = -(-p1 // bs)
            for bb0 in range(b0, bend, batch_blocks):
                bb1 = min(bb0 + batch_blocks, bend)
                logical_off = bb0 * ssize
                logical_end = min(bb1 * ssize, sfsize)
                seg_len = logical_end - logical_off
                framed_off = logical_off + bb0 * hlen
                framed_len = seg_len + (bb1 - bb0) * hlen
                covered = min(bb1 * bs, part.size) - bb0 * bs
                with _stages.stage("drive_read"), \
                        _trace.span("read", "get.fanout"):
                    shards = self._read_shard_segments(
                        bucket, object_name, fi, part, shuffled, sfis,
                        dead, framed_off, framed_len, seg_len, ssize,
                        algo)
                with _stages.stage("decode"), \
                        _trace.span("read", "get.assemble"):
                    part_bytes = self._assemble(shards, fi, covered)
                lo = max(p0 - bb0 * bs, 0)
                hi = min(p1 - bb0 * bs, covered)
                # the body's second host copy (after _assemble's)
                with _trace.span("read", "get.copy_out"):
                    chunk = part_bytes[lo:hi].tobytes()
                yield chunk
            part_start += part.size
        # shards that failed mid-stream are heal candidates
        # (on-read heal trigger, cmd/erasure-object.go:330-342)
        if self.mrf is not None and \
                any(shuffled[j] is not None for j in dead):
            self.mrf.add(bucket, object_name, fi.version_id)

    def _read_shard_segments(self, bucket, object_name, fi, part, shuffled,
                             sfis, dead: set[int], framed_off: int,
                             framed_len: int, seg_len: int, ssize: int,
                             algo: str) -> list:
        """Read one block-batch's byte range from k healthy shards,
        extending into parity on failure; returns a length-n list with
        np arrays at the indices read.

        Each round of k candidates is one fan-out.  Its local drives
        (``xl_storage.shard_wave_positions``: local and online, the
        native library loaded, no group collector on this thread, no
        O_DIRECT reads) are read on THIS thread, after the other drives'
        children are submitted, so it overlaps them: their part files
        and packed extents by ONE native call that reads, verifies and
        gathers every window (``xl_storage.read_shard_wave``), their
        inline shards here in Python.  ``mt_read_get_drives_total
        {route=wave|pool}`` counts the drives each route read."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        nsh = k + m
        part_path = f"{object_name}/{fi.data_dir}/part.{part.number}"

        def source(j):
            """Where shard j's window is: None for inline data, else the
            drive call that reads it (xl_storage.read_shard_wave's item)."""
            dfi = sfis[j]
            if dfi is not None and dfi.inline_data is not None:
                return None
            if dfi is not None and getattr(dfi, "seg", None):
                # packed object: the framed shard lives at an extent
                # inside the drive's segment file (storage/commit.py);
                # same window arithmetic, different backing file
                return ("read_segment", dfi.seg["sid"],
                        dfi.seg["off"] + framed_off)
            return ("read_file_stream", bucket, part_path, framed_off)

        def read_one(j):
            disk = shuffled[j]
            if disk is None:
                raise serrors.DiskNotFound("offline")
            src = source(j)
            if src is None:
                framed = sfis[j].inline_data[
                    framed_off:framed_off + framed_len]
                if len(framed) < framed_len:
                    raise serrors.FileCorrupt("short inline data")
            elif src[0] == "read_segment":
                framed = disk.read_segment(src[1], src[2], framed_len)
            else:
                framed = disk.read_file_stream(*src[1:], framed_len)
            # the drive call above is timed where it runs
            # (mt_drive_call_seconds); the verify is the child's other half
            try:
                with _trace.span("read", "get.verify"):
                    # one native verify pass + one strided payload copy
                    fast = bitrot.verify_extract(framed, ssize, seg_len,
                                                 algo)
                    if fast is not None:
                        return fast
                    r = bitrot.StreamingBitrotReader(framed, ssize, algo)
                    return np.frombuffer(r.read_at(0, seg_len),
                                         dtype=np.uint8)
            except bitrot.BitrotError as e:
                raise serrors.FileCorrupt(str(e)) from e

        def wave(js):
            """The round's local shards j in ``js``, on this thread:
            ``(result, error, start_ns, end_ns)`` each, in order."""
            got, files = {}, []
            for j in js:
                if source(j) is None:       # no I/O: read_one, here
                    t0 = time.monotonic_ns()
                    try:
                        r, e = read_one(j), None
                    except Exception as x:  # noqa: BLE001 — per-item
                        r, e = None, x
                    got[j] = r, e, t0, time.monotonic_ns()
                else:
                    files.append(j)
            if files:
                for j, r in zip(files, _xl.read_shard_wave(
                        [shuffled[j] for j in files],
                        [source(j) for j in files], framed_len, seg_len,
                        ssize)):
                    got[j] = r
            return [got[j] for j in js]

        shards: list[np.ndarray | None] = [None] * nsh
        got = 0
        t0 = _critpath.now_ns()
        ends_all = [0] * nsh
        candidates = [j for j in range(nsh) if j not in dead]
        waves = algo == bitrot.HIGHWAYHASH256S
        while got < k and candidates:
            batch, candidates = candidates[:k - got], candidates[k - got:]
            bends = [0] * len(batch)
            here = _xl.shard_wave_positions(
                [shuffled[j] for j in batch]) if waves else []
            res, errs = self._fanout_items(
                read_one, batch, ends=bends, plane="get",
                inline=(here, lambda: wave([batch[p] for p in here]))
                if here else None)
            if here:
                _metrics.inc("mt_read_get_drives_total", {"route": "wave"},
                             len(here))
            if len(here) < len(batch):
                _metrics.inc("mt_read_get_drives_total", {"route": "pool"},
                             len(batch) - len(here))
            for pos, (j, r, e) in enumerate(zip(batch, res, errs)):
                ends_all[j] = bends[pos]
                if e is None:
                    shards[j] = r
                    got += 1
                else:
                    dead.add(j)
        if got < k:
            raise ReadQuorumError(f"only {got} of {k} shards readable")
        _critpath.record("read", k, self._drive_labels(shuffled),
                         ends_all, t0,
                         errs=[True if j in dead else None
                               for j in range(nsh)])
        return shards

    def _assemble(self, shards: list[np.ndarray | None], fi: FileInfo,
                  part_size: int) -> np.ndarray:
        """Reconstruct missing data shards (batched over stripes) and
        concatenate the data blocks (writeDataBlocks analog,
        cmd/erasure-utils.go:40)."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        bs = fi.erasure.block_size
        ssize = fi.erasure.shard_size()
        nfull = part_size // bs
        tail = part_size - nfull * bs
        missing_data = [i for i in range(k) if shards[i] is None]
        if missing_data:
            if m <= 0:
                raise ReadQuorumError("no parity to reconstruct from")
            # the OBJECT's persisted geometry picks the matrix — a
            # storage-class parity differs from the layer default
            present = [i for i in range(k + m) if shards[i] is not None][:k]
            rebuilt = self._codec_for(m).reconstruct_files(
                [shards[i] for i in present], present, missing_data,
                part_size, block_size=bs)
            for i, full in zip(missing_data, rebuilt):
                shards[i] = full
        # concatenate data blocks, trimming per-block padding: one
        # strided copy per shard over ALL blocks (the mirror of
        # encode_object_framed's placement loop) — a per-block
        # np.concatenate costs a second full pass over the data
        out = np.empty(part_size, dtype=np.uint8)
        if nfull:
            dview = out[:nfull * bs].reshape(nfull, bs)
            for i in range(k):
                lo = i * ssize
                ln = min(ssize, max(0, bs - lo))
                if ln:
                    dview[:, lo:lo + ln] = \
                        shards[i][:nfull * ssize].reshape(
                            nfull, ssize)[:, :ln]
        if tail:
            t_ssize = gf8.ceil_frac(tail, k)
            pos = nfull * bs
            for i in range(k):
                lo = i * t_ssize
                ln = min(t_ssize, max(0, tail - lo))
                if ln:
                    out[pos + lo:pos + lo + ln] = shards[i][
                        nfull * ssize: nfull * ssize + ln]
        return out

    # -- DELETE (cmd/erasure-object.go:803-1139) ---------------------------

    def delete_object(self, bucket: str, object_name: str,
                      opts: Optional[ObjectOptions] = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        self._check_bucket(bucket)
        mod_time = opts.mod_time or now_ns()
        # write lock (DeleteObject takes the nsLock, cmd/erasure-object.go
        # delete path): a delete racing a PUT commit must not interleave
        # per-drive version mutations
        lk = self.ns_lock.new_lock(bucket, object_name)
        with _stages.stage("lock_wait"):
            lk.lock(write=True)
        try:
            if opts.versioned and opts.version_id is None:
                # versioned delete without a version: write a delete marker
                dm = FileInfo(volume=bucket, name=object_name,
                              version_id=str(uuid.uuid4()), deleted=True,
                              data_dir="", mod_time=mod_time)
                with _stages.stage("drive_commit"):
                    _, errs = self._fanout(
                        lambda d: d.delete_version(
                            bucket, object_name, dm,
                            force_del_marker=True), plane="delete")
                try:
                    meta.reduce_errs(errs, self._write_quorum(),
                                     WriteQuorumError)
                except serrors.StorageError as e:
                    raise WriteQuorumError(str(e)) from e
                oi = ObjectInfo(bucket=bucket, name=object_name,
                                version_id=dm.version_id,
                                delete_marker=True, mod_time=mod_time)
                self._hot_invalidate(bucket, object_name)
                self.metacache.invalidate(bucket)
                return oi
            # delete a concrete version (or the null version)
            vid = opts.version_id or ""
            fi = FileInfo(volume=bucket, name=object_name, version_id=vid,
                          mod_time=mod_time)
            with _stages.stage("drive_commit"):
                _, errs = self._fanout(
                    lambda d: d.delete_version(bucket, object_name, fi),
                    plane="delete")
            nf = sum(1 for e in errs
                     if isinstance(e, (serrors.FileNotFound,
                                       serrors.FileVersionNotFound)))
            if nf > len(self.disks) // 2:
                # object absent: S3 DELETE is idempotent; return quietly
                return ObjectInfo(bucket=bucket, name=object_name,
                                  version_id=vid)
            try:
                meta.reduce_errs(errs, self._write_quorum(),
                                 WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            self._hot_invalidate(bucket, object_name)
            self.metacache.invalidate(bucket)
            return ObjectInfo(bucket=bucket, name=object_name,
                              version_id=vid)
        finally:
            lk.unlock()

    def put_object_metadata(self, bucket: str, object_name: str,
                            version_id: Optional[str],
                            updates: dict[str, str],
                            removes: tuple[str, ...] = ()) -> ObjectInfo:
        """Update user metadata on an existing version in place
        (cmd/erasure-object.go PutObjectTags / PutObjectMetadata).

        Each drive rewrites its own xl.meta entry so per-shard erasure
        indices and inline data are preserved; write quorum applies.
        """
        self._check_bucket(bucket)
        lk = self.ns_lock.new_lock(bucket, object_name)
        lk.lock(write=True)
        try:
            fi, _ = self._read_quorum_fileinfo(bucket, object_name,
                                               version_id)
            if fi.deleted:
                raise MethodNotAllowed(
                    f"{bucket}/{object_name} is a delete marker")
            # an explicit version_id (including "" = the null version) must
            # be honored as-is; only an unqualified request resolves to the
            # latest version's id
            vid = version_id if version_id is not None else \
                (fi.version_id or None)

            def update_one(disk):
                dfi = disk.read_version(bucket, object_name, vid)
                md = dict(dfi.metadata)
                for k in removes:
                    md.pop(k, None)
                md.update(updates)
                dfi.metadata = md
                disk.write_metadata(bucket, object_name, dfi)

            _, errs = self._fanout(update_one)
            try:
                meta.reduce_errs(errs, self._write_quorum(fi),
                                 WriteQuorumError)
            except serrors.StorageError as e:
                raise WriteQuorumError(str(e)) from e
            for k in removes:
                fi.metadata.pop(k, None)
            fi.metadata.update(updates)
            self._hot_invalidate(bucket, object_name)
            self.metacache.invalidate(bucket)
            return self._to_object_info(fi)
        finally:
            lk.unlock()

    # -- LIST (walk-merge; cmd/metacache-set.go simplified) ----------------

    def list_objects(self, bucket: str, prefix: str = "", marker: str = "",
                     delimiter: str = "", max_keys: int = 1000
                     ) -> ListObjectsInfo:
        """Serve from the streamed metacache blocks; the walk+resolve
        runs once per (bucket, prefix), seals fixed-size blocks as it
        resolves, and continuation pages bisect straight to their
        covering block — one block in memory per page, never the
        namespace (cmd/metacache-server-pool.go listPath +
        cmd/metacache-set.go block persistence)."""
        self._check_bucket(bucket)
        from .metacache import SnapshotGone, paginate
        for _ in range(2):
            snap = self.metacache.list_path_stream(
                bucket, prefix,
                lambda: self._gather_listing_iter(bucket, prefix))
            try:
                return paginate(snap.iter_from(marker), prefix, marker,
                                delimiter, max_keys)
            except SnapshotGone:
                # a persisted block vanished under the snapshot
                # (invalidate race / drive churn): drop it, re-walk
                self.metacache.forget(bucket, prefix)
        # twice unlucky: serve this page straight off a fresh walk
        return paginate(self._gather_listing_iter(bucket, prefix),
                        prefix, marker, delimiter, max_keys)

    def _walk_resolve(self, bucket: str, prefix: str,
                      versions: bool) -> dict[str, list]:
        """One walk stream per drive carries names AND xl.meta metadata
        (cmd/metacache-walk.go); merge into name -> per-drive FileInfo
        lists.  O(drives) streams total — never a per-key quorum read
        (the round-1 O(keys x drives) resolve, cmd/metacache-set.go:544)."""
        # confine the walk to the prefix's directory subtree so listing
        # one tenant of a huge bucket doesn't stream the whole namespace
        base_dir = prefix.rsplit("/", 1)[0] if "/" in prefix else ""
        res, _ = self._fanout(
            lambda d: list(d.walk_entries(bucket, base_dir,
                                          versions=versions)))
        merged: dict[str, list] = {}
        for drive_entries in res:
            if not drive_entries:
                continue
            for e in drive_entries:
                name = e["name"]
                if prefix and not name.startswith(prefix):
                    continue
                merged.setdefault(name, []).append(
                    [FileInfo.from_dict(f) if isinstance(f, dict) else f
                     for f in e["fis"]])
        return merged

    def _gather_listing_iter(self, bucket: str, prefix: str):
        """STREAMED walk+resolve: one lazy walk stream per drive
        (flat key order — xl_storage.walk_dir's contract), k-way
        merged and quorum-resolved entry by entry, so memory stays
        O(drives), never O(namespace) (cmd/metacache-set.go listPath +
        metacache-entries resolve, minus the round-2 full gather)."""
        import heapq
        from itertools import groupby

        base_dir = prefix.rsplit("/", 1)[0] if "/" in prefix else ""

        def drive_stream(d):
            try:
                yield from d.walk_entries(bucket, base_dir,
                                          versions=False)
            except Exception:  # noqa: BLE001 — a dead/unreachable
                return         # drive's entries are simply missing;
                               # quorum below decides per entry

        streams = [drive_stream(d) for d in self.disks if d is not None]
        merged = heapq.merge(*streams, key=lambda e: e["name"])
        quorum = max(1, len(self.disks) // 2)
        for name, group in groupby(merged, key=lambda e: e["name"]):
            if prefix:
                if name < prefix:
                    continue
                if not name.startswith(prefix):
                    break       # sorted streams: nothing later matches
            fis = []
            for e in group:
                f = e["fis"][0]
                fis.append(FileInfo.from_dict(f)
                           if isinstance(f, dict) else f)
            try:
                fi = meta.find_file_info_in_quorum(fis, quorum)
            except ReadQuorumError:
                continue        # disagreement below quorum: skip entry
            if fi.deleted:
                continue
            yield self._to_object_info(fi)

    def list_object_versions(self, bucket: str, prefix: str = ""):
        """All versions of all objects (ListObjectVersions core) — same
        walked-metadata resolve, all versions per entry."""
        self._check_bucket(bucket)
        merged = self._walk_resolve(bucket, prefix, versions=True)
        quorum = max(1, len(self.disks) // 2)
        out: list[ObjectInfo] = []
        for name in sorted(merged):
            per_drive = merged[name]
            # resolve the version SET from the drive agreeing with the
            # quorum pick of the latest version (findFileInfoInQuorum)
            latest = [fis[0] for fis in per_drive if fis]
            try:
                fi = meta.find_file_info_in_quorum(latest, quorum)
            except ReadQuorumError:
                continue
            for fis in per_drive:
                if fis and fis[0].mod_time == fi.mod_time \
                        and fis[0].version_id == fi.version_id:
                    out.extend(self._to_object_info(v) for v in fis)
                    break
        return out

    # -- healing (delegates to objectlayer.healing) -------------------------

    def heal_object(self, bucket, object_name, version_id=None, deep=False,
                    dry_run=False, remove_dangling=False):
        from . import healing
        return healing.heal_object(self, bucket, object_name, version_id,
                                   deep, dry_run, remove_dangling)

    def heal_bucket(self, bucket: str) -> int:
        """Recreate the bucket on any drive missing it
        (healBucket, cmd/erasure-healing.go:56); returns drives touched."""
        healed = 0
        for disk in self.disks:
            if disk is None:
                continue
            try:
                disk.stat_vol(bucket)
            except serrors.StorageError:
                try:
                    disk.make_vol(bucket)
                    healed += 1
                except serrors.StorageError:
                    pass
        return healed

    # -- helpers -----------------------------------------------------------

    def _to_object_info(self, fi: FileInfo) -> ObjectInfo:
        md = dict(fi.metadata)
        return ObjectInfo(
            bucket=fi.volume, name=fi.name, mod_time=fi.mod_time,
            size=fi.size, etag=md.pop(ETAG_KEY, ""),
            version_id=fi.version_id, is_latest=fi.is_latest,
            delete_marker=fi.deleted,
            content_type=md.get("content-type", ""),
            user_defined=md, parity=fi.erasure.parity_blocks,
            data_blocks=fi.erasure.data_blocks,
            num_versions=fi.num_versions,
            parts=[(p.number, p.size) for p in fi.parts])

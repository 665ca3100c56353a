"""Cross-request batching codec service — coalesce concurrent
encode/decode/reconstruct calls into one padded device dispatch.

The kernel north star is met (~52 GiB/s encode) but every PUT/GET used
to dispatch its OWN encode/decode, so under small-object traffic the
device ran at a few percent of roofline: batch depth across requests
was free and nothing claimed it.  This module is the continuous-
batching layer from inference serving applied to the storage data
plane — the same combining shape as the MD5 ``LaneScheduler``
(hashing/md5fast.py), one level up:

  * concurrent callers (the PUT writer plane, GET reconstruction and
    heal) submit ``(rows, (B, k, n) stripes)`` work items;
  * items are **bucketed** by geometry + operation — the full key is
    ``(op, backend, k, m, block_size, n, rows-bytes)`` so everything
    in one bucket is the same matmul over the same coefficient rows
    (stripes are row-independent, so concatenating along the batch
    axis is bit-identical to dispatching them apart);
  * the first caller into an idle bucket becomes the **combiner**: it
    waits up to ``codec.batch_window_us`` for followers (early-out at
    ``codec.max_batch_blocks``), concatenates the batch, runs ONE
    device dispatch through the bucket's shared codec, slices results
    back per waiter, and repeats until the queue drains — followers
    park on an event, their thread yielding to encode/writer work;
  * a window that finds **one** caller takes the strict single-
    dispatch fallback: the caller's own stripes through the exact
    serial engine (``Erasure._apply_matrix``) — the serial path stays
    the reference semantics, like ``pipeline.depth=0``;
  * queues are **bounded** (``codec.queue_depth`` blocks per bucket):
    an arrival past the bound sheds to the serial path immediately
    (counted, latency stays bounded, the queue cannot grow without
    limit), and a caller that dies mid-queue cancels its waiter so the
    combiner never computes or delivers into freed state.

The batcher owns no threads: combiners are borrowed caller threads
(the ``LaneScheduler`` discipline), so there is nothing to leak on
shutdown — tests pin the ``mt-codec-*`` naming rule for their own
worker threads instead.

On a mesh-backend codec the one fused dispatch rides the existing
pjit/shard_map plumbing (parallel/mesh.py + ops/rs_mesh.py), so every
caller in the process shares one device mesh through one combining
queue.

Every dispatch lands in the ``mt_codec_batch_*`` metric families and
is one ``tpu``-type span ``<op>.batch`` (obs/trace.py ``span``: always
in the ring and the leg histogram; with a trace consumer active the
span dict carries the batch detail — occupancy, blocks, geometry).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque

import numpy as np

from ..obs import trace as _trace
from ..ops.codec import Erasure
from ..utils.locktrace import mtlock

# occupancy buckets: requests coalesced per dispatch (1 = the serial
# fallback fired; weight above 1 is the cross-request win)
OCCUPANCY_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)

# fused dispatches in flight per bucket: 2 = one executing on the
# device while the next batch forms and launches (continuous-batching
# pipelining).  Without the cap, every arrival during a dispatch
# elects itself a fresh combiner and occupancy collapses to ~1 — the
# serial dispatch pattern with extra steps; with it, load above the
# pipeline depth accumulates into the next batch instead.
_MAX_INFLIGHT = 2


class CodecConfig:
    """Live-reloadable knobs (``codec`` kvconfig subsystem).  Reads
    env/defaults lazily on first use; the server pushes admin
    SetConfigKV values via S3Server.reload_codec_config (a fresh
    kvconfig.Config cannot see another instance's dynamic layer)."""

    def __init__(self):
        self.enable = True
        self.window_s = 200e-6          # batch_window_us
        self.max_blocks = 256           # max_batch_blocks per dispatch
        self.queue_depth = 1024         # queued blocks per bucket
        self._loaded = False

    def load(self, cfg=None) -> None:
        try:
            if cfg is None:
                from ..utils.kvconfig import Config
                cfg = Config()
            # parse ALL knobs first, assign atomically: a bad value in
            # one key must not leave a silently half-applied config
            enable = str(cfg.get("codec", "enable")
                         ).strip().lower() not in ("off", "0",
                                                   "false", "")
            window_s = max(
                0.0, int(cfg.get("codec", "batch_window_us")) / 1e6)
            max_blocks = max(
                1, int(cfg.get("codec", "max_batch_blocks")))
            queue_depth = max(
                max_blocks, int(cfg.get("codec", "queue_depth")))
            self.enable = enable
            self.window_s = window_s
            self.max_blocks = max_blocks
            self.queue_depth = queue_depth
        except (KeyError, ValueError):
            pass
        self._loaded = True

    def on(self) -> bool:
        if not self._loaded:
            self.load()
        return self.enable


CONFIG = CodecConfig()


# -- shared per-geometry codec registry -------------------------------------
#
# One Erasure instance per (k, m, blockSize, backend) for the whole
# process: the batcher's bucket executors and any direct caller resolve
# here, so a geometry maps to ONE codec (and one compiled-kernel cache
# line) instead of one per call site.

_CODEC_MU = mtlock("codec.registry")
_CODECS: dict[tuple, Erasure] = {}
_CODEC_CAP = 64


def codec_for(data_blocks: int, parity_blocks: int, block_size: int,
              backend: str = "auto") -> Erasure:
    """The process-shared codec for one geometry (bounded registry: a
    pathological parade of one-off geometries evicts oldest)."""
    # normalize BEFORE keying: 'auto' resolves inside Erasure, and
    # keying on the unresolved name would cache a second instance
    # (and a second compiled-kernel cache line) per geometry
    from ..ops.codec import resolve_backend
    backend = resolve_backend(backend)
    key = (int(data_blocks), int(parity_blocks), int(block_size),
           backend)
    with _CODEC_MU:
        c = _CODECS.get(key)
        if c is None:
            c = Erasure(data_blocks, parity_blocks, block_size,
                        backend=backend)
            if len(_CODECS) >= _CODEC_CAP:
                _CODECS.pop(next(iter(_CODECS)))
            _CODECS[key] = c
        return c


def _engine(codec: Erasure, op: str, fn):
    """``fn``, or the codec's serial engine with its legs named for
    ``op``: ``encode.*`` for an encode bucket, ``decode.*`` for
    ``decode`` and ``reconstruct``."""
    return fn or functools.partial(
        codec._apply_matrix, op="encode" if op == "encode" else "decode")


class _Waiter:
    """One caller's work item parked in a bucket queue."""

    __slots__ = ("shards", "blocks", "event", "result", "exc", "done",
                 "cancelled", "enq")

    def __init__(self, shards: np.ndarray):
        self.shards = shards
        self.blocks = shards.shape[0]
        self.event = threading.Event()
        self.result = None
        self.exc: BaseException | None = None
        self.done = False
        self.cancelled = False
        self.enq = time.monotonic()


class _Bucket:
    """One geometry/op combining queue.  ``codec`` is the shared
    executor instance; ``cond`` shares the batcher lock so enqueues
    can wake a window-waiting combiner."""

    __slots__ = ("rows", "codec", "q", "blocks", "combining", "cond",
                 "op", "inflight", "fn")

    def __init__(self, rows: np.ndarray, codec: Erasure, lock, op: str,
                 fn):
        self.rows = rows
        self.codec = codec
        self.q: deque[_Waiter] = deque()
        self.blocks = 0
        self.combining = False
        self.cond = threading.Condition(lock)
        self.op = op
        self.inflight = 0
        self.fn = fn


class CodecBatcher:
    """The process-wide combining queue set (``GLOBAL`` below)."""

    def __init__(self, config: CodecConfig | None = None):
        self._mu = mtlock("codec.batcher")
        self._buckets: dict[tuple, _Bucket] = {}
        self.config = config or CONFIG
        # lifetime totals (bench deltas + the scrape-gauge idle gate)
        self.dispatches = 0
        self.requests = 0
        self.blocks = 0
        self.shed = 0
        self.cancelled = 0

    # -- introspection ------------------------------------------------------

    def snapshot(self) -> dict:
        with self._mu:
            return {"dispatches": self.dispatches,
                    "requests": self.requests,
                    "blocks": self.blocks,
                    "shed": self.shed,
                    "cancelled": self.cancelled}

    def started(self) -> bool:
        return self.dispatches > 0 or self.shed > 0

    def queue_depths(self) -> dict[str, int]:
        """Queued blocks per op, summed over buckets (the
        ``mt_codec_batch_queue_depth`` scrape gauge)."""
        out: dict[str, int] = {}
        with self._mu:
            for b in self._buckets.values():
                out[b.op] = out.get(b.op, 0) + b.blocks
        return out

    # -- submission ---------------------------------------------------------

    def apply(self, codec: Erasure, op: str, rows: np.ndarray, shards,
              timeout: float | None = None) -> np.ndarray:
        """rows (GF) @ shards through the combining queue; bit-identical
        to ``codec._apply_matrix(rows, shards)`` in every path.  Accepts
        (k, n) or (B, k, n); ``timeout`` bounds the parked wait — on
        expiry the waiter cancels out of the queue and the caller's own
        stripes run the serial path (the caller-death escape hatch)."""
        shards = np.asarray(shards, dtype=np.uint8)
        squeeze = shards.ndim == 2
        if squeeze:
            shards = shards[None]
        out = self.submit(codec, op, rows, shards, timeout=timeout)
        return out[0] if squeeze else out

    def submit(self, codec: Erasure, op: str, rows: np.ndarray, shards,
               fn=None, timeout: float | None = None):
        """General combining submission: ``fn(rows, (B, k, n))`` must
        be per-stripe independent along the batch axis and return an
        array — or a TUPLE of arrays (the fused encode+bitrot path
        returns (parity, digests)) — each sliced back per waiter.
        Default fn is the bucket codec's serial engine
        (``Erasure._apply_matrix``).  Callers in one bucket share the
        FIRST caller's fn; the bucket key (op + backend + geometry +
        width + rows bytes) pins the dispatch identity, so equivalent
        keys imply equivalent fns."""
        shards = np.asarray(shards, dtype=np.uint8)
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        cfg = self.config
        if shards.shape[0] >= cfg.max_blocks:
            # already a full dispatch on its own: combining could only
            # add latency.  Runs the same engine, counted as occupancy 1
            return self._direct(codec, op, rows, shards, fn)
        key = (op, codec.backend, codec.data_blocks,
               codec.parity_blocks, codec.block_size, shards.shape[2],
               rows.tobytes())
        # resolve the shared executor codec outside the batcher lock
        exec_codec = codec_for(codec.data_blocks, codec.parity_blocks,
                               codec.block_size, codec.backend)
        w = _Waiter(shards)
        shed = False
        lead = False
        with self._mu:
            bkt = self._buckets.get(key)
            if bkt is None:
                bkt = _Bucket(rows, exec_codec, self._mu, op,
                              _engine(exec_codec, op, fn))
                self._buckets[key] = bkt
            if bkt.blocks + w.blocks > cfg.queue_depth:
                # per-bucket backpressure: the queue never grows past
                # the bound — overflow sheds to the serial path, which
                # is semantically identical and keeps latency bounded
                self.shed += 1
                shed = True
            else:
                bkt.q.append(w)
                bkt.blocks += w.blocks
                lead = not bkt.combining
                if lead:
                    bkt.combining = True
                else:
                    bkt.cond.notify_all()   # feed a waiting window
        if shed:
            from ..admin.metrics import GLOBAL as _mtr
            _mtr.inc("mt_codec_batch_shed_total", {"op": op})
            return self._direct(codec, op, rows, shards, fn)
        if lead:
            self._combine(key, bkt, own=w)
            # our own waiter is normally in our first batch, but a
            # backlog ahead of it plus a role handoff can leave it to
            # ANOTHER combiner — park for the result, never read early
            served = w.done or self._park(w, key, bkt, timeout)
        else:
            served = self._park(w, key, bkt, timeout)
        if not served:
            # cancelled out of the queue: serial fallback
            return self._direct(codec, op, rows, shards, fn)
        if w.exc is not None:
            raise w.exc
        return w.result

    # -- the combiner role --------------------------------------------------

    def _park(self, w: _Waiter, key: tuple, bkt: _Bucket,
              timeout: float | None) -> bool:
        """Wait for the combiner to serve ``w``.  Self-healing: if the
        combiner died (its dispatch raised and unwound) with our item
        still queued, claim the role.  Returns False when the wait
        timed out and the waiter cancelled out of the queue."""
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        # X-ray: the parked wait is the ``batch_wait`` stage — the
        # price one request pays for riding a shared dispatch
        from ..obs import stages as _stages
        t0 = time.monotonic_ns()
        try:
            return self._park_inner(w, key, bkt, deadline)
        finally:
            _stages.add("batch_wait", time.monotonic_ns() - t0)

    def _park_inner(self, w: _Waiter, key: tuple, bkt: _Bucket,
                    deadline: float | None) -> bool:
        while not w.event.wait(0.05):
            lead = False
            with self._mu:
                if w.done:
                    return True
                in_q = w in bkt.q
                if not in_q:
                    # a combiner holds us: the result is coming
                    continue
                if deadline is not None and \
                        time.monotonic() >= deadline:
                    bkt.q.remove(w)
                    bkt.blocks -= w.blocks
                    w.cancelled = True
                    self.cancelled += 1
                    break
                if not bkt.combining:
                    bkt.combining = True
                    lead = True
            if lead:
                self._combine(key, bkt, own=w)
                if w.done:
                    return True
        if w.cancelled:
            from ..admin.metrics import GLOBAL as _mtr
            _mtr.inc("mt_codec_batch_cancelled_total", {"op": bkt.op})
            return False
        return True

    def _combine(self, key: tuple, bkt: _Bucket,
                 own: _Waiter | None = None) -> None:
        """One combining round as the bucket's combiner: window-wait,
        pop a batch, then RELEASE the role before dispatching — a new
        arrival elects a fresh combiner and forms the next batch while
        this one is on the device, so batches pipeline instead of the
        queue serializing behind compute (continuous batching, not
        stop-and-wait).  After the dispatch, re-claim the role only
        while ``own`` (this caller's waiter) is still unserved: once
        our request is done we hand the queue to the next arrival (or
        a parked waiter's self-heal claim) instead of combining other
        requests' batches forever — under sustained load a caller's
        own latency must stay bounded by its batch, not the storm."""
        cfg = self.config
        holding = True                       # we own bkt.combining
        try:
            while True:
                with self._mu:
                    if cfg.window_s > 0 and bkt.blocks < cfg.max_blocks:
                        deadline = time.monotonic() + cfg.window_s
                        while bkt.blocks < cfg.max_blocks:
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            bkt.cond.wait(left)
                    # pipeline-depth gate: with _MAX_INFLIGHT batches
                    # already dispatching, keep combining — arrivals
                    # accumulate into THIS batch instead of racing the
                    # device with another under-full dispatch
                    while bkt.inflight >= _MAX_INFLIGHT and \
                            bkt.blocks < cfg.max_blocks:
                        bkt.cond.wait(0.05)
                    batch: list[_Waiter] = []
                    nblocks = 0
                    while bkt.q:
                        cand = bkt.q[0]
                        if batch and \
                                nblocks + cand.blocks > cfg.max_blocks:
                            break
                        bkt.q.popleft()
                        bkt.blocks -= cand.blocks
                        if cand.cancelled:      # belt and braces: a
                            cand.event.set()    # cancel removes itself
                            continue
                        batch.append(cand)
                        nblocks += cand.blocks
                    bkt.combining = False
                    holding = False
                    if not batch:
                        if not bkt.q and not bkt.inflight:
                            self._buckets.pop(key, None)
                        else:
                            bkt.cond.notify_all()
                        return
                    bkt.inflight += 1
                    bkt.cond.notify_all()
                try:
                    self._dispatch(bkt, batch, nblocks)
                finally:
                    with self._mu:
                        bkt.inflight -= 1
                        bkt.cond.notify_all()
                with self._mu:
                    if bkt.q and not bkt.combining and \
                            own is not None and not own.done:
                        bkt.combining = True
                        holding = True
                        continue
                    if bkt.q and not bkt.combining:
                        # backlog, but our own request is served: wake
                        # a parked waiter to self-heal-claim the role
                        bkt.cond.notify_all()
                    if not bkt.q and not bkt.combining and \
                            not bkt.inflight:
                        self._buckets.pop(key, None)
                    return
        except BaseException:
            # never strand parked waiters behind a dead combiner: the
            # _park self-heal loop re-elects, but only once the role is
            # released
            if holding:
                with self._mu:
                    bkt.combining = False
                    bkt.cond.notify_all()
            raise

    # -- execution ----------------------------------------------------------

    @staticmethod
    def _slice(out, off: int, n: int):
        """Per-waiter view of a batch result (array or tuple of
        batch-axis sequences, e.g. the fused path's (parity, digests);
        the one-chip form's parity is a list of per-stripe arrays)."""
        if isinstance(out, tuple):
            return tuple(o[off:off + n] for o in out)
        return out[off:off + n]

    @staticmethod
    def _span(codec: Erasure, op: str, nwaiters: int, nblocks: int):
        """The ``tpu`` span ``<op>.batch`` round one batched dispatch
        (obs/trace.py); its detail says how full the batch was."""
        return _trace.span(
            "tpu", op + ".batch",
            detail=lambda: {"op": op, "backend": codec.backend,
                            "k": codec.data_blocks,
                            "m": codec.parity_blocks,
                            "blockSize": codec.block_size,
                            "blocks": nblocks, "occupancy": nwaiters,
                            "batched": nwaiters > 1})

    def _direct(self, codec: Erasure, op: str, rows: np.ndarray,
                shards: np.ndarray, fn=None):
        """One caller, one dispatch — the strict serial fallback (and
        the shed/cancel path).  Counted with occupancy 1 so the scrape
        shows how much traffic is NOT coalescing."""
        with self._span(codec, op, 1, shards.shape[0]):
            out = _engine(codec, op, fn)(rows, shards)
        self._account(op, nwaiters=1, nblocks=shards.shape[0],
                      waits=(0.0,))
        return out

    def _dispatch(self, bkt: _Bucket, batch: list[_Waiter],
                  nblocks: int) -> None:
        """One fused device dispatch for the whole batch; results are
        views sliced back per waiter (padding — lane tiles, pow2 batch,
        mesh axes — is the engine's own and stripped there)."""
        t0 = time.monotonic()
        try:
            with self._span(bkt.codec, bkt.op, len(batch), nblocks):
                if len(batch) == 1:
                    # the window found one caller: strict single-
                    # dispatch fallback, the serial reference semantics
                    # verbatim
                    batch[0].result = bkt.fn(bkt.rows, batch[0].shards)
                else:
                    cat = np.concatenate([w.shards for w in batch],
                                         axis=0)
                    out = bkt.fn(bkt.rows, cat)
                    off = 0
                    for w in batch:
                        w.result = self._slice(out, off, w.blocks)
                        off += w.blocks
        except BaseException as e:
            for w in batch:
                w.exc = e
            if not isinstance(e, Exception):
                # KeyboardInterrupt/SystemExit must keep propagating in
                # the thread it hit (the waiters above still fail fast
                # instead of hanging); _combine releases the role on
                # the way out
                raise
        finally:
            for w in batch:
                w.done = True
                w.event.set()
            self._account(bkt.op, nwaiters=len(batch), nblocks=nblocks,
                          waits=tuple(t0 - w.enq for w in batch))

    def _account(self, op: str, *, nwaiters: int, nblocks: int,
                 waits: tuple) -> None:
        from ..admin.metrics import BATCH_BUCKETS, KERNEL_BUCKETS
        from ..admin.metrics import GLOBAL as _mtr
        with self._mu:
            self.dispatches += 1
            self.requests += nwaiters
            self.blocks += nblocks
        labels = {"op": op}
        _mtr.inc("mt_codec_batch_dispatches_total", labels)
        _mtr.observe("mt_codec_batch_blocks", labels, float(nblocks),
                     buckets=BATCH_BUCKETS)
        _mtr.observe("mt_codec_batch_occupancy", labels,
                     float(nwaiters), buckets=OCCUPANCY_BUCKETS)
        for wt in waits:
            _mtr.observe("mt_codec_batch_wait_seconds", labels,
                         max(0.0, wt), buckets=KERNEL_BUCKETS)


GLOBAL = CodecBatcher()


# -- the md5 bucket ---------------------------------------------------------
#
# Device multi-buffer MD5 (hashing/md5_device.py) rides the SAME
# combining discipline as the codec buckets, one queue for the whole
# process: concurrent strict-ETag streams' block advances coalesce
# into one batched device dispatch (states stacked on the batch axis,
# ragged block counts masked in-kernel).  The codec refinements carry
# over verbatim — the combiner releases its role before dispatching so
# the next batch forms while this one is on the device, at most
# _MAX_INFLIGHT dispatches run concurrently, and arrivals past the
# queue bound shed to an uncombined single-lane dispatch (semantically
# identical, latency bounded).  No owned threads: combiners are
# borrowed caller threads, so there is nothing to leak at shutdown —
# test_leaks pins that no md5 bucket state survives a burst.

# widest single dispatch (native/md5mb.cc's MAXL): beyond this the
# padding waste of ragged lane lengths outgrows the batching win
_MD5_MAX_LANES = 64
# queued 64-byte blocks across all waiters; overflow sheds to the
# serial single-lane dispatch (4 MiB of pending message)
_MD5_QUEUE_BLOCKS = 1 << 16


class _MD5Waiter:
    __slots__ = ("h", "words", "event", "result", "exc")

    def __init__(self, h: np.ndarray, words: np.ndarray):
        self.h = h
        self.words = words
        self.event = threading.Event()
        self.result = None
        self.exc: BaseException | None = None


class MD5Batcher:
    """The process-wide ``md5`` combining bucket (``MD5_GLOBAL``)."""

    def __init__(self, config: CodecConfig | None = None):
        self._mu = mtlock("codec.md5-batcher")
        self._cond = threading.Condition(self._mu)
        self._q: deque[_MD5Waiter] = deque()
        self._qblocks = 0
        self._combining = False
        self._inflight = 0
        self.config = config or CONFIG
        # lifetime totals (bench deltas + the test_leaks idle gate)
        self.dispatches = 0
        self.requests = 0
        self.blocks = 0
        self.shed = 0

    def idle(self) -> bool:
        """True when no waiter, combiner or dispatch is outstanding —
        the post-burst/server-stop contract (test_leaks)."""
        with self._mu:
            return (not self._q and not self._combining
                    and self._inflight == 0)

    def snapshot(self) -> dict:
        with self._mu:
            return {"dispatches": self.dispatches,
                    "requests": self.requests,
                    "blocks": self.blocks,
                    "shed": self.shed}

    # -- submission ---------------------------------------------------------

    def advance(self, h: np.ndarray, words: np.ndarray) -> np.ndarray:
        """Advance one digest state by ``words`` (nb, 16) u32 blocks
        through the combining queue; returns the new (4,) u32 state.
        Bit-identical to a lone ``md5_device.advance`` call in every
        path (lanes are independent; the batch is a pure stacking)."""
        nb = int(words.shape[0])
        if nb == 0:
            return np.asarray(h, np.uint32)
        w = _MD5Waiter(np.asarray(h, np.uint32), words)
        with self._mu:
            if self._qblocks + nb > _MD5_QUEUE_BLOCKS:
                self.shed += 1
                shed = True
                lead = False
            else:
                shed = False
                self._q.append(w)
                self._qblocks += nb
                lead = not self._combining
                if lead:
                    self._combining = True
                else:
                    self._cond.notify_all()      # feed a waiting window
        if shed:
            return self._direct(w)
        if lead:
            self._combine(own=w)
        while not w.event.wait(0.05):
            # self-heal: a combiner that died with our item queued
            # released the role on the way out — claim it
            claim = False
            with self._mu:
                if w.event.is_set():
                    break
                if w in self._q and not self._combining:
                    self._combining = True
                    claim = True
            if claim:
                self._combine(own=w)
        if w.exc is not None:
            raise w.exc
        return w.result

    # -- the combiner role --------------------------------------------------

    def _combine(self, own: _MD5Waiter | None = None) -> None:
        cfg = self.config
        holding = True
        try:
            while True:
                with self._mu:
                    if cfg.window_s > 0 and \
                            len(self._q) < _MD5_MAX_LANES:
                        deadline = time.monotonic() + cfg.window_s
                        while len(self._q) < _MD5_MAX_LANES:
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            self._cond.wait(left)
                    while self._inflight >= _MAX_INFLIGHT and \
                            len(self._q) < _MD5_MAX_LANES:
                        self._cond.wait(0.05)
                    batch = []
                    while self._q and len(batch) < _MD5_MAX_LANES:
                        cand = self._q.popleft()
                        self._qblocks -= int(cand.words.shape[0])
                        batch.append(cand)
                    self._combining = False
                    holding = False
                    if not batch:
                        self._cond.notify_all()
                        return
                    self._inflight += 1
                    self._cond.notify_all()
                try:
                    self._dispatch(batch)
                finally:
                    with self._mu:
                        self._inflight -= 1
                        self._cond.notify_all()
                with self._mu:
                    # re-claim only while OUR request is unserved (the
                    # CodecBatcher discipline): once it is done, hand
                    # the queue to the next arrival or a parked
                    # waiter's self-heal claim — a caller's latency
                    # stays bounded by its batch, not the storm
                    if self._q and not self._combining and \
                            own is not None and not own.event.is_set():
                        self._combining = True
                        holding = True
                        continue
                    if self._q and not self._combining:
                        self._cond.notify_all()
                    return
        except BaseException:
            if holding:
                with self._mu:
                    self._combining = False
                    self._cond.notify_all()
            raise

    # -- execution ----------------------------------------------------------

    def _direct(self, w: _MD5Waiter) -> np.ndarray:
        """Uncombined single-lane dispatch (the shed path) — the same
        engine, occupancy 1."""
        from ..hashing import md5_device
        nb = int(w.words.shape[0])
        out = md5_device.advance(
            w.h[None], w.words[None], np.asarray([nb], np.int32))[0]
        self._account(1, nb)
        return out

    def _dispatch(self, batch: list[_MD5Waiter]) -> None:
        from ..hashing import md5_device
        try:
            # group by pow2 block-count bucket before padding: every
            # lane in a dispatch pads to the group max, so one 1 MiB
            # slice batched with 63 one-block tails would otherwise
            # inflate the transfer 64x (zeros are still bytes on a
            # slow H2D link).  Same-bucket lanes waste < 2x; equal
            # slices (the md5_of / _md5_link common case) share one
            # group exactly as before.
            groups: dict[int, list[_MD5Waiter]] = {}
            for w in batch:
                nb = int(w.words.shape[0])
                groups.setdefault(md5_device._pow2(nb), []).append(w)
            for group in groups.values():
                n = len(group)
                nbs = [int(w.words.shape[0]) for w in group]
                nb_max = max(nbs)
                states = np.stack([w.h for w in group])
                words = np.zeros((n, nb_max, 16), dtype=np.uint32)
                for i, w in enumerate(group):
                    words[i, :nbs[i]] = w.words
                out = md5_device.advance(
                    states, words, np.asarray(nbs, np.int32))
                for i, w in enumerate(group):
                    w.result = out[i]
                self._account(n, sum(nbs))
        except BaseException as e:
            for w in batch:
                if w.result is None:
                    w.exc = e
            if not isinstance(e, Exception):
                raise
        finally:
            for w in batch:
                w.event.set()

    def _account(self, lanes: int, nblocks: int) -> None:
        with self._mu:
            self.dispatches += 1
            self.requests += lanes
            self.blocks += nblocks
        from ..admin.metrics import GLOBAL as _mtr
        _mtr.inc("mt_md5_device_batches_total", {"lanes": str(lanes)})
        _mtr.inc("mt_md5_device_bytes_total", value=float(nblocks * 64))


MD5_GLOBAL = MD5Batcher()

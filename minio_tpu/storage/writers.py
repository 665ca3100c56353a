"""Per-drive writer plane — the I/O stage of the pipelined PUT path.

The reference overlaps erasure encode with drive writes by giving every
drive its own goroutine + io.Pipe pair for the lifetime of a stream
(cmd/erasure-encode.go:80-107 parallelWriter, cmd/bitrot-streaming.go
newStreamingBitrotWriter).  The Python analog here is ONE persistent
writer thread per drive with a bounded in-order queue:

  * enqueue is non-blocking until the per-drive depth bound (the
    ``pipeline.queue_depth`` kvconfig knob, read live per enqueue), so
    batch N+1's encode overlaps batch N's create/append fan-out;
  * per-drive ordering is strict FIFO — one thread per drive consumes
    one queue, so a stream's create always lands before its appends and
    its appends before its commit, locally and across an RPC (the
    remote client's calls are synchronous, storage/remote.py);
  * errors latch per (stream, drive): once a drive fails a stream's op,
    the stream's later ops for that drive are skipped (a later append
    after a failed one would corrupt the staged file) and quorum is
    re-checked as completions drain;
  * the plane is shared by streaming PUT, the overlapped bytes-PUT
    commit, multipart part uploads, and heal writes — concurrent
    streams interleave on the per-drive queues without ordering
    hazards because each stream only ever appends to its own files;
  * with the ``commit`` kvconfig subsystem on, each drive's drain is
    GROUPED (storage/commit.py): up to commit.max_batch queued ops run
    their bodies, one after another, with a GroupCollector armed; ONE
    flush settles the whole batch, and every stream's durability is
    acknowledged (quorum re-checked) only after its covering fsync
    landed.  The flush runs in rounds of two waves — the round's file
    fsyncs issued together, then its deduplicated parent-dir fsyncs
    issued together, each wave ONE call that does not hold the
    interpreter lock (commit.sync_files / sync_dirs over
    native/syncwave.c) — followed by the round's continuations (the
    xl.meta replaces) on the drive's writer thread.  An op body lands
    its part file and its xl.meta tmp file the same way, one such call
    each (commit.land_part / land_file).  Op bodies,
    continuations and settlement all stay on the one thread per drive,
    so the FIFO contract above is untouched.  A drive op's time is
    queue + body + flush (``mt_commit_{queue,body,flush}_seconds``).

Shutdown: ``close()`` wakes blocked enqueuers (they see PlaneClosed and
abort their PUT, which cleans its tmp files), fails every queued op so
stream ``drain()`` calls return, and joins the worker threads.  The
plane restarts lazily on the next enqueue, so a layer shared across
server start/stop cycles (tests, embedded use) keeps working.
"""

from __future__ import annotations

import itertools
import threading
import time

from ..obs import critpath as _critpath
from ..obs import stages as _stages
from ..obs import trace as _trace
from . import commit as _commit
from . import errors as serrors
from ..utils.locktrace import mtlock, mtrlock


class PlaneClosed(serrors.StorageError):
    """The writer plane shut down while ops were queued or submitting."""


class _Batch:
    """Refcount across one batch's per-drive ops; fires ``release``
    exactly once when the last op settles (the framed-buffer recycle
    hook) and exposes an event the put loop bounds its depth on."""

    __slots__ = ("_n", "_release", "_mu", "done")

    def __init__(self, n: int, release=None):
        self._n = n
        self._release = release
        self._mu = mtlock("putw.quorum-latch")
        self.done = threading.Event()
        if n <= 0:
            self._fire()

    def _fire(self) -> None:
        rel, self._release = self._release, None
        if rel is not None:
            try:
                rel()
            except Exception:  # noqa: BLE001 — recycle is best-effort
                pass
        self.done.set()

    def done_one(self) -> None:
        with self._mu:
            self._n -= 1
            if self._n > 0:
                return
        self._fire()


class _Op:
    __slots__ = ("stream", "idx", "fn", "batch", "rid", "clock",
                 "parent", "t_enq")

    def __init__(self, stream, idx, fn, batch, rid, clock=None,
                 parent=""):
        self.stream = stream
        self.idx = idx
        self.fn = fn
        self.batch = batch
        self.rid = rid
        self.clock = clock
        self.parent = parent
        self.t_enq = 0.0         # perf_counter when it joined its queue

    def bind(self) -> None:
        """Make this thread's trace context the op's: per-drive spans
        must carry the originating request ID even though the worker
        thread outlives any one request; the X-ray clock rides along so
        a remote drive's RPC leg is attributed (async detail) to the
        right request, and the span parent so this op's storage spans
        land under the submitting span in the request's causal tree."""
        _trace.set_request_id(self.rid)
        _trace.set_span_parent(self.parent)
        _stages.set_clock(self.clock)

    def run_body(self, disk) -> tuple:
        """Execute the op body WITHOUT settling; returns ``(err, dt)``.
        Group commit splits body from settlement so a whole batch's
        bodies run before the shared flush, and every stream's quorum
        is re-checked (via settle) only after its covering fsync
        landed.  An error still latches into the stream's ``errs``
        immediately — a same-stream batch-mate later in the batch must
        skip, not append after a failure."""
        st = self.stream
        if st.cancelled or st.errs[self.idx] is not None:
            return (None, 0.0)
        self.bind()
        t0 = time.perf_counter()
        try:
            self.fn(self.idx, disk)
            return (None, time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 — latched, quorum decides
            st._latch_err(self.idx, e)
            return (e, time.perf_counter() - t0)

    def settle(self, err: Exception | None, dt: float) -> None:
        self.stream._op_done(self.idx, err, self.batch, dt)

    def run(self, disk) -> None:
        err, dt = self.run_body(disk)
        self.settle(err, dt)

    def fail(self, err: Exception) -> None:
        self.stream._op_done(self.idx, err, self.batch, 0.0)


class _DriveWriter:
    """One persistent thread + bounded FIFO queue for one drive."""

    def __init__(self, disk, name: str):
        self.disk = disk
        self._q: list[_Op] = []
        self._cv = threading.Condition(mtrlock("putw.drive-queue"))
        self._closed = False
        self.stalls = 0          # enqueues that hit the depth bound
        self.ops = 0             # ops completed (incl. skipped/failed)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    def depth(self) -> int:
        return len(self._q)

    def put(self, op: _Op, bound: int) -> None:
        with self._cv:
            if len(self._q) >= bound and not self._closed:
                self.stalls += 1
                while len(self._q) >= bound and not self._closed:
                    self._cv.wait()
            if self._closed:
                raise PlaneClosed("writer plane closed")
            op.t_enq = time.perf_counter()
            self._q.append(op)
            self._cv.notify_all()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._closed:
                    self._cv.wait()
                if not self._q:          # closed and drained
                    return
                grouped = not self._closed and _commit.CONFIG.on()
                if grouped:
                    limit = max(1, _commit.CONFIG.max_batch)
                    window = _commit.CONFIG.group_window_s
                    if window > 0 and len(self._q) < limit:
                        # linger briefly for batch-mates still in
                        # encode; already-queued ops coalesce for free
                        self._cv.wait(window)
                ops = [self._q.pop(0)]
                if grouped:
                    while self._q and len(ops) < limit:
                        ops.append(self._q.pop(0))
                self._cv.notify_all()    # wake putters at the bound
            if self._closed:
                for op in ops:
                    op.fail(PlaneClosed("writer plane closed"))
                    self.ops += 1
            elif not grouped:
                ops[0].run(self.disk)
                self.ops += 1
            else:
                self._group_commit(ops)

    def _group_commit(self, ops: list[_Op]) -> None:
        """One group commit: run every op body with the collector armed
        (bodies defer their fsyncs / visibility flips into it), flush
        once — rounds of a file wave, a directory wave and the round's
        continuations settle the whole batch — THEN settle each op so
        per-stream quorum is re-checked only after its covering fsync
        landed.  A body that reaches a gate someone else opens (an
        overlapped PUT's digest) hands its second half to the collector
        and the thread goes on to the next body: the halves run, in op
        order, when the bodies have, and count as their ops' body
        time."""
        col = _commit.GroupCollector()
        _commit.arm(col)
        settles: list[tuple] = []
        t_batch = time.perf_counter()
        try:
            for op in ops:
                _commit.observe_stage("queue", t_batch - op.t_enq)
                col.current_op = op
                settles.append(op.run_body(self.disk))
            col.run_tails()
            col.current_op = None
            for op, (_, dt) in zip(ops, settles):
                _commit.observe_stage("body", dt + col.tail_s.get(op, 0.0))
            t_flush = time.perf_counter()
            col.flush()
            _commit.observe_stage("flush", time.perf_counter() - t_flush)
        except Exception as e:  # noqa: BLE001 — flush must not kill us
            for op in ops:
                try:
                    op.stream._latch_err(op.idx, e)
                except Exception:  # noqa: BLE001 — stream already
                    pass           # dead/settled; flush error stands
        finally:
            _commit.disarm()
            col.publish(len(ops))
            while len(settles) < len(ops):
                settles.append((None, 0.0))
            for op, (err, dt) in zip(ops, settles):
                # flush-time failures latched into stream errs; settle
                # re-reads nothing — _op_done only adds err if unset
                op.settle(err, dt + col.tail_s.get(op, 0.0))
                self.ops += 1

    def close(self, timeout: float) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout)
        # a worker stuck inside a hung drive op cannot drain its queue;
        # fail the leftovers here so stream drain()s return (popping is
        # lock-safe against the stuck worker resuming later)
        while True:
            with self._cv:
                if not self._q:
                    return
                op = self._q.pop(0)
                self._cv.notify_all()
            op.fail(PlaneClosed("writer plane closed"))
            self.ops += 1

    def is_alive(self) -> bool:
        return self._thread.is_alive()


class StreamWriter:
    """One stream's view of the plane: positional drives (the PUT's
    shuffled order), per-drive latched errors, pending-op accounting."""

    def __init__(self, plane: "WriterPlane", disks: list,
                 gen: int = 0):
        self._plane = plane
        self._gen = gen          # plane generation at stream birth
        self.disks = list(disks)
        self.errs: list[Exception | None] = [
            None if d is not None else serrors.DiskNotFound("offline")
            for d in self.disks]
        self.drive_busy = [0.0] * len(self.disks)   # seconds in drive ops
        # monotonic ns of each drive's LAST op settlement — the
        # completion vector the quorum critical-path engine reduces at
        # drain (obs/critpath.py); 0 = never settled anything
        self.settle_ns = [0] * len(self.disks)
        self.cancelled = False
        self._pending = 0
        self._drive_pending = [0] * len(self.disks)
        self._on_idle: dict[int, list] = {}
        self._cv = threading.Condition(mtrlock("putw.stream"))

    # -- submission --------------------------------------------------------

    def _latch_err(self, idx: int, err: Exception) -> None:
        """Latch a drive error AHEAD of the op's settlement — group
        commit needs it visible the moment a body or flush-time fsync
        fails, so a same-stream batch-mate later in the batch skips
        instead of appending after the failure.  ``_op_done``'s
        only-if-unset guard makes the later settlement a no-op."""
        with self._cv:
            if self.errs[idx] is None:
                self.errs[idx] = err

    def submit(self, idx: int, fn, batch: _Batch | None = None,
               bound: int | None = None) -> bool:
        """Queue ``fn(idx, disk)`` on drive idx's writer (in-order per
        drive).  Returns False (settling ``batch``) for drives already
        dead for this stream.  Blocks only at the queue-depth bound
        (``bound`` overrides the plane's — commit-class ops widen it to
        the group-commit batch size so whole-object commits coalesce);
        raises PlaneClosed if the plane shuts down meanwhile."""
        disk = self.disks[idx]
        if disk is None or self.errs[idx] is not None or self.cancelled:
            if batch is not None:
                batch.done_one()
            return False
        op = _Op(self, idx, fn, batch, _trace.get_request_id(),
                 _stages.current(), _trace.get_span_parent())
        with self._cv:
            self._pending += 1
            self._drive_pending[idx] += 1
        try:
            # the enqueue may park at the per-drive queue bound — that
            # wait is the ``write_enqueue`` X-ray stage
            t0 = time.perf_counter()
            self._plane._enqueue(disk, op, bound)
            dt = time.perf_counter() - t0
            if dt > 0.0005:
                _stages.add("write_enqueue", int(dt * 1e9))
        except BaseException:
            with self._cv:
                self._pending -= 1
                self._drive_pending[idx] -= 1
                cbs = (self._on_idle.pop(idx, [])
                       if self._drive_pending[idx] == 0 else [])
                self._cv.notify_all()
            self._run_idle_cbs(cbs)
            if batch is not None:
                batch.done_one()
            raise
        return True

    def submit_batch(self, fn, release=None) -> _Batch:
        """Queue one batch of ``fn(idx, disk)`` across all live drives;
        ``release`` fires once every drive's op settled (framed-buffer
        recycle).  Dead drives settle immediately."""
        idxs = [i for i in range(len(self.disks))
                if self.disks[i] is not None and self.errs[i] is None
                and not self.cancelled]
        batch = _Batch(len(idxs), release)
        done = 0
        try:
            for i in idxs:
                self.submit(i, fn, batch)
                done += 1
        except BaseException:
            for _ in range(len(idxs) - done - 1):
                batch.done_one()   # never-submitted ops settle here
            raise
        return batch

    # -- progress / settlement --------------------------------------------

    def _op_done(self, idx: int, err: Exception | None,
                 batch: _Batch | None, busy_s: float) -> None:
        with self._cv:
            if err is not None and self.errs[idx] is None:
                self.errs[idx] = err
            self.drive_busy[idx] += busy_s
            self.settle_ns[idx] = time.monotonic_ns()
            self._pending -= 1
            self._drive_pending[idx] -= 1
            cbs = (self._on_idle.pop(idx, [])
                   if self._drive_pending[idx] == 0 else [])
            self._cv.notify_all()
        self._run_idle_cbs(cbs)
        if batch is not None:
            batch.done_one()

    @staticmethod
    def _run_idle_cbs(cbs) -> None:
        for cb in cbs:
            try:
                cb()
            except Exception:  # noqa: BLE001 — cleanup is best-effort
                pass

    def when_drive_idle(self, idx: int, fn) -> None:
        """Run ``fn()`` once drive idx has no unsettled ops from this
        stream — immediately when already idle, otherwise on the
        settling thread (the drive's writer after a hung op completes,
        or whatever thread fails the queue at plane close).  Tmp-dir
        cleanup after a timed-out ``drain`` rides this: removing a
        staging dir while a stuck append could still resume would let
        its makedirs(exist_ok=True) resurrect the dir as an orphan."""
        with self._cv:
            if self._drive_pending[idx] > 0:
                self._on_idle.setdefault(idx, []).append(fn)
                return
        self._run_idle_cbs([fn])

    def alive(self) -> int:
        return sum(1 for i, d in enumerate(self.disks)
                   if d is not None and self.errs[i] is None)

    def abort(self) -> None:
        """Cancel this stream: queued ops become no-ops (their slots
        still drain, so per-drive FIFO order is preserved for other
        streams sharing the queues)."""
        self.cancelled = True

    def drain(self, timeout: float | None = None) -> bool:
        """Wait for every submitted op to settle; True when idle."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._pending:
                if end is None:
                    self._cv.wait()
                else:
                    left = end - time.monotonic()
                    if left <= 0:
                        return False
                    self._cv.wait(left)
        return True

    def max_busy_s(self) -> float:
        return max(self.drive_busy, default=0.0)

    def record_gating(self, plane: str, k: int,
                      t0_ns: int) -> tuple | None:
        """One quorum critical-path row for this stream's fan-out (the
        writer-plane reduction point, called by the PUT path right
        after a successful ``drain``): each drive's child completion is
        its last op settlement; drives that latched an error are
        excluded — a failed drive cannot have been the quorum
        decider."""
        labels = [_critpath.drive_label(d) if d is not None
                  else "offline" for d in self.disks]
        return _critpath.record(plane, k, labels, list(self.settle_ns),
                                t0_ns, errs=self.errs)


class WriterPlane:
    """The per-layer registry of drive writers (lazily started)."""

    _NAMES = itertools.count()

    def __init__(self, queue_depth=2):
        # int or zero-arg callable: the kvconfig knob is read per
        # enqueue so admin SetConfigKV retunes a live plane
        self._depth = queue_depth
        self._writers: dict[int, _DriveWriter] = {}
        self._mu = mtlock("putw.plane")
        self._closed = False
        self._gen = 0            # bumped by close(); stale streams die
        self.used = False        # ever carried an op (metrics idle gate)

    def stream(self, disks: list) -> StreamWriter:
        with self._mu:
            gen = self._gen
        return StreamWriter(self, disks, gen)

    def queue_bound(self) -> int:
        d = self._depth() if callable(self._depth) else self._depth
        try:
            return max(1, int(d))
        except (TypeError, ValueError):
            return 2

    def _enqueue(self, disk, op: _Op, bound: int | None = None) -> None:
        key = id(disk)
        with self._mu:
            if self._closed or op.stream._gen != self._gen:
                # a stream born before the last close() must not respawn
                # writers after server stop — its PUT aborts instead
                raise PlaneClosed("writer plane closed")
            w = self._writers.get(key)
            if w is None or not w.is_alive():
                w = _DriveWriter(
                    disk, f"mt-putw-{next(WriterPlane._NAMES)}")
                self._writers[key] = w
            self.used = True
        w.put(op, bound if bound is not None else self.queue_bound())

    def stats(self) -> dict[str, dict]:
        """Per-drive {endpoint: {queue_depth, stalls, ops}} snapshot."""
        with self._mu:
            writers = list(self._writers.values())
        out: dict[str, dict] = {}
        for w in writers:
            try:
                ep = w.disk.endpoint()
            except Exception:  # noqa: BLE001 — dead drive still counts
                ep = f"drive-{id(w.disk):x}"
            out[ep] = {"queue_depth": w.depth(), "stalls": w.stalls,
                       "ops": w.ops}
        return out

    def close(self, timeout: float = 10.0) -> None:
        """Stop every writer: wake blocked enqueuers with PlaneClosed,
        fail queued ops so drains return, join the threads.  The plane
        reopens lazily for streams created AFTER the close (shared
        layers outlive one server's lifecycle); streams already in
        flight get PlaneClosed on their next enqueue — mid-stream PUTs
        abort rather than respawning writers past server stop."""
        with self._mu:
            self._closed = True
            self._gen += 1
            writers = list(self._writers.values())
            self._writers.clear()
        per = timeout / max(1, len(writers))
        for w in writers:
            w.close(per)
        with self._mu:
            self._closed = False


def planes_of(layer) -> list[WriterPlane]:
    """Every writer plane under an object-layer topology."""
    from ..objectlayer.metacache import leaf_layers_of
    out = []
    for leaf in leaf_layers_of(layer):
        p = getattr(leaf, "_write_plane", None)
        if p is not None:
            out.append(p)
    return out


def close_write_planes(layer, timeout: float = 10.0) -> None:
    """Server-stop hook: join every writer thread under ``layer`` (the
    test_leaks contract — no mt-putw-* thread survives stop, even with
    a blocked queue mid-stream)."""
    for p in planes_of(layer):
        try:
            p.close(timeout)
        except Exception:  # noqa: BLE001 — shutdown must proceed
            pass

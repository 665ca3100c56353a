"""Per-drive group-commit plane + packed small-object segments.

The fourth application of the combining discipline (md5 LaneScheduler →
CodecBatcher → SingleFlight hot reads → commit plane): concurrent
streams' create/append/fsync/rename ops queued on the same _DriveWriter
(storage/writers.py) coalesce into batched group commits — one flush
(files + deduplicated parent dirs) settles many streams' writes, with
durability acknowledged per stream only AFTER its covering fsync landed
and quorum re-checked per stream as completions drain.

Three pieces live here:

  * :class:`GroupCollector` — the thread-local deferred-durability
    ledger a drive writer arms around one batch of ops.  Drive op
    bodies (xl_storage.py) register dup'd file descriptors and parent
    dir paths instead of fsyncing eagerly, and defer their
    visibility-flipping os.replace into an ``after_flush``
    continuation; :meth:`GroupCollector.flush` then runs rounds until
    quiescent.  A round is two waves and its continuations: the
    round's file fsyncs, issued together; then its dedup'd directory
    fsyncs, issued together; then, on the drive's writer thread and in
    registration order, the continuations, which register the next
    round.  The crash-atomicity
    contract is preserved exactly: a version's xl.meta replace only
    runs after every fsync registered before it (its part/segment
    bytes and its meta tmp file) has landed — the same
    tmp→fsync→rename visibility order the eager path enforces, just
    batched.  Registering DUP'D fds (not paths) is load-bearing: the
    op body closes its own fd and may rename the file before the
    flush, and an fd fsync is immune to both.  A body that reaches a
    gate someone else opens (an overlapped PUT's digest) before it is
    open hands over its second half (``yield_tail``) instead of parking
    the drive's only writer thread; the halves run in op order before
    the flush, and before any op that cannot queue behind them.

  * the writer thread's syscalls (``native/syncwave.c``, loaded on
    first use), each group of them one call that never holds the
    interpreter lock: a flush wave's fsyncs, issued together
    (:func:`sync_files`, :func:`sync_dirs`), and the landing of an op
    body's files (:func:`land_part`: the two mkdirs, then the part
    file created, written, dup'd or fsynced, closed; :func:`land_file`:
    the same for the ``xl.meta`` tmp file).  Under a loaded interpreter
    every ``os.*`` call of a thread ends with a wait for the GIL, and a
    batch's ~40 fsyncs and an op body's ~10 calls, issued one ``os.*``
    call at a time, made the drive's writer thread wait for the
    interpreter, not for the drive.  Without a compiler (or
    ``MT_NATIVE=0``) the same calls run one by one from Python.

  * :class:`SegmentStore` — per-drive journaled append-only segment
    files under ``<root>/.mt.sys/seg/`` that pack many small objects'
    framed shards behind ONE fsync, with xl.meta pointing into the
    segment (the ``seg`` version field — the inline-data precedent
    extended past the single-object boundary).  The journal is
    append-only add/free records with the owning object identity, so
    recovery is a pure idempotent replay (a torn tail record is
    truncated away, matching the manifest-written-last discipline of
    metacache blocks) and the compactor can rewrite live extents'
    owner metadata when reclaiming dead segment space.

Knobs ride the live-reloadable ``commit`` kvconfig subsystem
(S3Server.reload_commit_config pushes admin SetConfigKV into
:data:`CONFIG`, same pattern as the codec batcher).
"""

from __future__ import annotations

import ctypes
import functools
import os
import threading
import time

import msgpack
import numpy as np

from ..admin.metrics import GLOBAL as _metrics
from ..utils.locktrace import mtlock
from . import errors

# mirrors xl_storage._FSYNC (import would be circular: xl_storage
# imports this module for the collector hooks)
_FSYNC = os.environ.get("MT_FSYNC", "1") != "0"

# mt_commit_{queue,body,flush}_seconds: a 9p or spinning drive's op runs
# tens of ms and a saturated drive queue holds an op for a second
STAGE_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5)
STAGE_FAMILIES = {"queue": "mt_commit_queue_seconds",
                  "body": "mt_commit_body_seconds",
                  "flush": "mt_commit_flush_seconds"}


def observe_stage(stage: str, seconds: float) -> None:
    """One sample of the three walls a drive op's ``drive_commit`` time
    is made of on a one-thread-per-drive server: ``queue`` (enqueue to
    its batch's start), ``body`` (the op body; an overlapped PUT's md5
    gate park is inside it) and ``flush`` (one batch's flush, all
    rounds)."""
    _metrics.observe(STAGE_FAMILIES[stage], {}, seconds,
                     buckets=STAGE_BUCKETS)


class CommitConfig:
    """Live-reloadable knobs (``commit`` kvconfig subsystem).  Reads
    env/defaults lazily on first use; the server pushes admin
    SetConfigKV values via S3Server.reload_commit_config (a fresh
    kvconfig.Config cannot see another instance's dynamic layer)."""

    def __init__(self):
        self.enable = True
        self.group_window_s = 0.0       # extra wait for batch-mates
        self.max_batch = 16             # ops coalesced per group commit
        self.pack_threshold = 1 << 20   # pack objects up to this size
        self.segment_max_bytes = 64 << 20   # segment rotation point
        self._loaded = False

    def load(self, cfg=None) -> None:
        try:
            if cfg is None:
                from ..utils.kvconfig import Config
                cfg = Config()
            # parse ALL knobs first, assign atomically: a bad value in
            # one key must not leave a silently half-applied config
            enable = str(cfg.get("commit", "enable")
                         ).strip().lower() not in ("off", "0",
                                                   "false", "")
            window_s = max(
                0.0, int(cfg.get("commit", "group_window_us")) / 1e6)
            max_batch = max(1, int(cfg.get("commit", "max_batch")))
            pack = max(0, int(cfg.get("commit", "pack_threshold")))
            seg_max = max(1 << 20,
                          int(cfg.get("commit", "segment_max_bytes")))
            self.enable = enable
            self.group_window_s = window_s
            self.max_batch = max_batch
            self.pack_threshold = pack
            self.segment_max_bytes = seg_max
        except (KeyError, ValueError):
            pass
        self._loaded = True

    def on(self) -> bool:
        if not self._loaded:
            self.load()
        return self.enable


CONFIG = CommitConfig()


# -- the per-batch collector ------------------------------------------------

_TLS = threading.local()


def collector() -> "GroupCollector | None":
    """The GroupCollector armed on THIS thread (a drive writer running
    a grouped batch), or None — drive op bodies branch on this to defer
    durability work instead of fsyncing eagerly."""
    return getattr(_TLS, "collector", None)


def arm(col: "GroupCollector") -> None:
    _TLS.collector = col


def disarm() -> None:
    _TLS.collector = None


def _note_calls(n: int) -> None:
    """A landing on a drive's writer thread made ``n`` blocking calls
    into the OS (1 for the native form, the ``os.*`` sequence's own
    count for the other): each ends in a wait for the interpreter lock,
    which is what a loaded writer thread's wall is made of.  Tallied on
    the batch's collector (``mt_commit_body_calls_total``); a landing
    with none armed is no writer-thread body and counts nothing."""
    col = collector()
    if col is not None:
        col.body_calls += n


_WAVE_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native", "syncwave.c")
_WAVE_SO = os.path.join(os.path.dirname(_WAVE_SRC), "build",
                        "libmtsyncwave.so")


class _Landed(ctypes.Structure):
    """syncwave.c ``mt_land_t``: how a landing ended."""
    _fields_ = [("step", ctypes.c_int), ("err", ctypes.c_int),
                ("fresh", ctypes.c_int), ("fd", ctypes.c_int)]


@functools.cache
def _wave_lib():
    """native/syncwave.c, built and loaded on first use; None when it
    cannot be (utils/nativelib.status() says why).  Its read waves
    (``mt_read_files``, ``mt_read_verify_ranges``) serve
    xl_storage.read_version_wave and read_shard_wave."""
    from ..utils import nativelib
    lib = nativelib.load(_WAVE_SRC, _WAVE_SO)
    if lib is not None:
        ints = ctypes.POINTER(ctypes.c_int)
        lib.mt_sync_files.argtypes = [ints, ctypes.c_int, ints]
        lib.mt_sync_files.restype = None
        lib.mt_sync_dirs.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                     ctypes.c_int]
        lib.mt_sync_dirs.restype = None
        lib.mt_land_file.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_int, ctypes.POINTER(_Landed)]
        lib.mt_land_file.restype = ctypes.c_int
        lib.mt_land_part.argtypes = [ctypes.c_char_p] * 3 \
            + lib.mt_land_file.argtypes[1:]
        lib.mt_land_part.restype = ctypes.c_int
        i64s = ctypes.POINTER(ctypes.c_longlong)
        lib.mt_read_files.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_void_p,
            ctypes.c_size_t, i64s, ints, i64s, i64s]
        lib.mt_read_files.restype = None
        lib.mt_read_verify_ranges.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), i64s, ctypes.c_int,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p, i64s]
        lib.mt_read_verify_ranges.restype = None
    return lib


def _fsync_close(fd: int) -> int:
    """fsync + close one fd; 0 or the fsync's errno."""
    try:
        os.fsync(fd)
        return 0
    except OSError as e:
        return e.errno or 5
    finally:
        os.close(fd)


def sync_files(fds: list[int]) -> list[int]:
    """One wave: fsync + close every fd, all issued together; per fd 0
    or the errno of its fsync.  Returns when every one has returned."""
    lib = _wave_lib()
    if lib is None:
        return [_fsync_close(fd) for fd in fds]
    n = len(fds)
    errs = (ctypes.c_int * n)()
    lib.mt_sync_files((ctypes.c_int * n)(*fds), n, errs)
    return list(errs)


def sync_dirs(paths: list[str]) -> None:
    """One wave: open + fsync + close every directory, all issued
    together; errors tolerated as ``_fsync_dir`` tolerates them."""
    lib = _wave_lib()
    if lib is None:
        for path in paths:
            try:
                _fsync_close(os.open(
                    path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0)))
            except OSError:
                pass
        return
    n = len(paths)
    lib.mt_sync_dirs((ctypes.c_char_p * n)(*map(os.fsencode, paths)), n)


# -- landing an op body's files ---------------------------------------------

def write_full(fd: int, data) -> None:
    """write(2) until the buffer is drained (short writes are legal on
    signal delivery even for regular files)."""
    mv = data
    if not isinstance(data, bytes):
        mv = memoryview(data)
        # a view of the caller's bytes; only a strided one is gathered
        mv = mv.cast("B") if mv.c_contiguous else mv.tobytes()
    written = os.write(fd, mv)
    while written < len(mv):
        written += os.write(fd, mv[written:])


def mkdir_fresh(path: str) -> bool:
    """mkdir; False where it was there already."""
    try:
        os.mkdir(path)
        return True
    except FileExistsError:
        return False


# syncwave.c's MT_SYNC_*: what follows a landed file's write
_SYNC_NONE, _SYNC_DUP, _SYNC_NOW = 0, 1, 2


def _land_native(fn, paths: list[str], data, storage) -> bool:
    """One native landing (``fn`` = ``mt_land_part`` / ``mt_land_file``
    over ``paths``, the last of them the file).  The caller's buffer
    goes down by address, no copy made; a failing step comes back as
    the ``OSError`` (subclass) the same ``os.*`` call would have
    raised, with that step's path as its ``filename``."""
    _note_calls(1)
    col = collector()
    mv = memoryview(data)
    # a view of the caller's bytes; only a strided one is gathered first
    arr = np.frombuffer(mv if mv.c_contiguous else mv.tobytes(),
                        dtype=np.uint8)
    sync = _SYNC_NONE if not _FSYNC \
        else _SYNC_NOW if col is None else _SYNC_DUP
    out = _Landed()
    if fn(*map(os.fsencode, paths), arr.ctypes.data_as(ctypes.c_void_p),
          arr.size, sync, ctypes.byref(out)) != 0:
        # steps 1, 2 are land_part's mkdirs (paths[0], paths[1]); every
        # later step is the file's
        raise OSError(out.err, os.strerror(out.err),
                      paths[min(out.step, len(paths)) - 1])
    if out.fd >= 0:
        col.defer_fd(out.fd, storage=storage)
    return bool(out.fresh)


def land_file(path: str, data, storage=None) -> None:
    """Create (or truncate) ``path``, write ``data`` until drained, then
    the durability step, then close: with a collector armed on this
    thread a dup'd fd is registered for the batch's flush, without one
    the fsync happens here before the return.  ``_write_file_atomic``'s
    body up to, not including, its ``os.replace``."""
    lib = _wave_lib()
    if lib is not None:
        _land_native(lib.mt_land_file, [path], data, storage)
        return
    _note_calls(4)              # open, write, dup | fsync, close
    col = collector()
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        write_full(fd, data)
        if _FSYNC:
            if col is not None:
                col.defer_fd(os.dup(fd), storage=storage)
            else:
                os.fsync(fd)
    finally:
        os.close(fd)


def land_part(obj: str, ddir: str, part: str, data, storage=None) -> bool:
    """The one-shot landing of a version's part file: ``mkdir(obj)``
    (there already: not fresh, no error), ``mkdir(ddir)``, then ``part``
    as :func:`land_file` lands it.  Returns whether ``obj`` was created
    here.  Raises what the ``os.*`` sequence raises, the failing step's
    path as ``filename``: ``FileNotFoundError`` on ``obj`` (its parent
    is missing: the caller's nested-name / wiped-volume case),
    ``FileExistsError`` on ``ddir``."""
    lib = _wave_lib()
    if lib is not None:
        return _land_native(lib.mt_land_part, [obj, ddir, part], data,
                            storage)
    _note_calls(2)              # the mkdirs
    fresh = mkdir_fresh(obj)
    os.mkdir(ddir)
    land_file(part, data, storage)
    return fresh


class GroupCollector:
    """Deferred-durability ledger for ONE drive-writer batch.

    Runs entirely on the drive's single writer thread — no lock needed
    (a flush wave's fsyncs fan out below Python, inside
    :func:`sync_files` / :func:`sync_dirs`, and are joined there).
    Every registration is tagged with the op currently executing
    (``current_op``) so a flush-time fsync failure latches onto exactly
    the streams whose writes it covered, and per-stream quorum is
    re-checked from those latched errors as completions drain."""

    def __init__(self):
        self.current_op = None      # the _Op whose body is running
        # (fd, storage, [ops], dedup_key): fds are DUP'D — the op body
        # already closed its own, and fd fsync survives a later rename
        self._fds: list = []
        self._dirs: dict[str, list] = {}    # path -> registering ops
        self._after: list = []              # (fn, op) continuations
        # (fn, op): second halves of bodies that reached a gate before
        # what it waits for was there (yield_tail), in op order
        self._tails: list = []
        self.tail_s: dict = {}      # op -> seconds its tail took
        # read-after-deferred-write map: final_path -> bytes for
        # xl.meta replaces still parked in ``_after`` — a batch-mate's
        # read-merge-write of the SAME object (or a heal riding the
        # plane, which takes no ns_lock) must see the pending content
        self._pending: dict[str, bytes] = {}
        self.deferred = 0           # eager fsyncs this batch replaced
        self.synced = 0             # fsync syscalls actually issued
        self.waves = 0              # flush waves that issued any
        self.seg_bytes = 0          # bytes packed into segments
        self.body_calls = 0         # blocking calls the bodies made
        self.streams: set = set()

    # -- registration (op bodies) ------------------------------------------

    def _note_stream(self) -> None:
        if self.current_op is not None:
            self.streams.add(id(self.current_op.stream))

    def defer_fd(self, fd: int, storage=None, key=None) -> None:
        """Take ownership of dup'd ``fd``; fsync it at flush.  A
        non-None ``key`` dedups — many packed writes in one batch
        register the same segment fd once (that dedup IS the saved
        fsync the mt_commit_group_fsyncs_saved_total family counts)."""
        self.deferred += 1
        self._note_stream()
        if key is not None:
            for rec in self._fds:
                if rec[3] == key:
                    os.close(fd)
                    rec[2].append(self.current_op)
                    return
        self._fds.append((fd, storage, [self.current_op], key))

    def defer_dir(self, path: str) -> None:
        """Defer a parent-directory entry fsync; identical paths across
        the batch (the shared bucket dir of a fresh-object fan-in)
        collapse to one syscall."""
        self.deferred += 1
        self._note_stream()
        self._dirs.setdefault(path, []).append(self.current_op)

    def after_flush(self, fn) -> None:
        """Run ``fn`` after every fsync registered so far has landed —
        the slot for visibility flips (xl.meta os.replace) and for old
        data-dir purges that must not precede the commit point."""
        self._after.append((fn, self.current_op))

    def yield_tail(self, fn) -> None:
        """The running body has reached a gate that someone else opens
        (an overlapped PUT's digest) before it is open, and hands over
        its second half instead of parking this drive's only writer
        thread on it: the thread goes on to the batch's next body, and
        :meth:`run_tails` runs the halves in the order they were handed
        over."""
        self._tails.append((fn, self.current_op))

    def tails(self) -> bool:
        """Second halves are waiting: a body that reaches its own gate
        now queues behind them, whatever its gate says (op order)."""
        return bool(self._tails)

    def run_tails(self) -> None:
        """Run the bodies' second halves, in op order, each as its op
        (trace context, error latched onto its own stream's drive, its
        wall added to the op's body time).  Called once a batch's bodies
        have run, and before any body that could not queue behind them:
        no drive op ever starts with an earlier op's half still owed."""
        if not self._tails:
            return
        tails, self._tails = self._tails, []
        interrupted = self.current_op
        for fn, op in tails:
            self._enter(op)
            t0 = time.perf_counter()
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — latched per op
                self._latch([op], e)
            self.tail_s[op] = time.perf_counter() - t0
        self._enter(interrupted)

    def _enter(self, op) -> None:
        self.current_op = op
        if op is not None:
            op.bind()

    def pending_put(self, path: str, data: bytes) -> None:
        self._pending[path] = data

    def pending_get(self, path: str) -> bytes | None:
        return self._pending.get(path)

    # -- flush (the group commit) ------------------------------------------

    @staticmethod
    def _latch(ops, err: Exception) -> None:
        for op in ops:
            if op is not None:
                try:
                    op.stream._latch_err(op.idx, err)
                except Exception:  # noqa: BLE001 — latch best-effort
                    pass

    def flush(self) -> None:
        """Rounds until quiescent.  A round is two waves and then its
        continuations: every registered file fsync, issued together;
        then every dedup'd directory fsync, issued together; then, on
        this thread and in registration order, the continuations (which
        may register more of both — a deferred xl.meta replace
        re-registers its parent dir for the next round).  A
        continuation therefore runs only after every fsync registered
        before it has RETURNED, exactly as when they ran one by one;
        order inside a wave is arbitrary, as it always was (fds by
        storage, dirs in dict order).  Second halves still owed
        (:meth:`yield_tail`) run first: they register what is flushed."""
        self.run_tails()
        while self._fds or self._dirs or self._after:
            fds, self._fds = self._fds, []
            dirs, self._dirs = self._dirs, {}
            if fds:
                t0 = time.monotonic_ns()
                self.synced += len(fds)
                self.waves += 1
                for rec, err in zip(fds, sync_files(
                        [rec[0] for rec in fds])):
                    if err:
                        self._latch(rec[2], errors.FaultyDisk(
                            str(OSError(err, os.strerror(err)))))
                # the wave's wall is charged once to each drive's
                # commit micro-profiler, not lost
                for storage in {id(rec[1]): rec[1] for rec in fds
                                if rec[1] is not None}.values():
                    storage._prof("fsync", t0)
            if dirs:
                self.synced += len(dirs)
                self.waves += 1
                sync_dirs(list(dirs))
            after, self._after = self._after, []
            for fn, op in after:
                self.current_op = op
                try:
                    fn()
                except Exception as e:  # noqa: BLE001 — latched per op
                    self._latch([op], e)
            self.current_op = None
        self._pending.clear()

    def publish(self, n_ops: int) -> None:
        """Tick the mt_commit_group_* families for one flushed batch —
        only when the plane actually engaged (grouped ops or deferred
        durability work), so an idle or disabled plane emits nothing.
        ``mt_commit_body_calls_total`` rides beside
        ``mt_commit_body_seconds`` instead (the two divide per op): one
        add per batch, not one more lock per op."""
        if self.body_calls:
            _metrics.inc("mt_commit_body_calls_total", {},
                         self.body_calls)
        if n_ops <= 1 and self.deferred == 0:
            return
        _metrics.inc("mt_commit_group_batches_total", {})
        _metrics.inc("mt_commit_group_streams_total", {},
                     max(1, len(self.streams)))
        saved = self.deferred - self.synced
        if saved > 0:
            _metrics.inc("mt_commit_group_fsyncs_saved_total", {}, saved)
        if self.synced:
            _metrics.inc("mt_commit_fsyncs_total", {}, self.synced)
            _metrics.inc("mt_commit_flush_waves_total", {}, self.waves)
        if self.seg_bytes:
            _metrics.inc("mt_commit_group_segment_bytes_total", {},
                         self.seg_bytes)


# -- packed small-object segments -------------------------------------------

SEG_DIR = "seg"                      # under <root>/.mt.sys/
_JOURNAL = "journal"


def _seg_name(sid: int) -> str:
    return f"seg.{sid:08x}.dat"


class SegmentStore:
    """Journaled append-only segment files packing many small objects'
    framed shards on one drive.

    Layout under ``dir_path`` (= ``<root>/.mt.sys/seg``):

        journal            msgpack add/free/seal/drop records, append-only
        seg.<sid>.dat      framed shards back to back, append-only

    Crash safety is manifest-written-last, twice over: the journal
    record and segment bytes are fsynced in the same flush round BEFORE
    the owner's xl.meta replace runs (GroupCollector ordering), so a
    version never points at bytes that could vanish; and recovery is a
    pure journal replay — duplicate adds and frees are idempotent, a
    torn tail record is truncated away, and an extent whose owner
    xl.meta never landed is reclaimed by the compactor's owner check.
    """

    def __init__(self, dir_path: str):
        self.dir = dir_path
        self._mu = mtlock("commit.segstore")
        # sid -> {"size": int, "sealed": bool,
        #         "live": {off: (length, vol, name, vid)}}
        self._segs: dict[int, dict] = {}
        self._cur = 0
        self._cur_fd = -1
        self._jfd = -1
        self._loaded = False

    # -- journal -----------------------------------------------------------

    def _jpath(self) -> str:
        return os.path.join(self.dir, _JOURNAL)

    def _replay(self) -> None:
        """Idempotent journal replay; truncates a torn tail record."""
        try:
            f = open(self._jpath(), "rb")
        except FileNotFoundError:
            return
        good = 0
        with f:
            unp = msgpack.Unpacker(f, raw=False, strict_map_key=False)
            try:
                for rec in unp:
                    self._apply(rec)
                    good = unp.tell()
            except Exception:  # noqa: BLE001 — torn tail ends replay
                pass
            end = f.seek(0, 2)
        if good < end:
            with open(self._jpath(), "r+b") as f:
                f.truncate(good)

    def _apply(self, rec: dict) -> None:
        op = rec.get("op")
        if op == "add":
            s = self._segs.setdefault(
                rec["sid"], {"size": 0, "sealed": False, "live": {}})
            s["live"][rec["off"]] = (rec["len"], rec.get("vol", ""),
                                     rec.get("name", ""),
                                     rec.get("vid", ""))
            s["size"] = max(s["size"], rec["off"] + rec["len"])
        elif op == "free":
            s = self._segs.get(rec["sid"])
            if s is not None:
                s["live"].pop(rec["off"], None)
        elif op == "seal":
            s = self._segs.get(rec["sid"])
            if s is not None:
                s["sealed"] = True
        elif op == "drop":
            self._segs.pop(rec["sid"], None)

    def _journal(self, rec: dict) -> None:
        os.write(self._jfd, msgpack.packb(rec, use_bin_type=True))

    def _ensure(self) -> None:
        if self._loaded:
            return
        os.makedirs(self.dir, exist_ok=True)
        self._replay()
        self._jfd = os.open(self._jpath(),
                            os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        open_sids = [sid for sid, s in self._segs.items()
                     if not s["sealed"]]
        self._cur = max(open_sids) if open_sids \
            else (max(self._segs) + 1 if self._segs else 1)
        self._cur_fd = os.open(
            os.path.join(self.dir, _seg_name(self._cur)),
            os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        self._segs.setdefault(
            self._cur, {"size": 0, "sealed": False, "live": {}})
        # a crash may have left appended-but-unjournaled bytes at the
        # segment tail; append past them (extents are journal-defined)
        self._segs[self._cur]["size"] = max(
            self._segs[self._cur]["size"],
            os.fstat(self._cur_fd).st_size)
        self._loaded = True

    # -- extents -----------------------------------------------------------

    def append(self, framed, vol: str, name: str,
               vid: str) -> tuple[int, int]:
        """Append one framed shard; returns (sid, off).  Durability is
        the CALLER's job: fsync via :meth:`sync` (eager) or
        :meth:`defer_sync` (grouped) before any xl.meta references the
        extent."""
        data = bytes(framed) if not isinstance(framed, bytes) else framed
        with self._mu:
            self._ensure()
            s = self._segs[self._cur]
            if s["size"] and s["size"] + len(data) \
                    > CONFIG.segment_max_bytes:
                self._rotate()
                s = self._segs[self._cur]
            sid, off = self._cur, s["size"]
            write_full(self._cur_fd, data)
            s["size"] = off + len(data)
            s["live"][off] = (len(data), vol, name, vid)
            self._journal({"op": "add", "sid": sid, "off": off,
                           "len": len(data), "vol": vol, "name": name,
                           "vid": vid})
            return sid, off

    def _rotate(self) -> None:
        # caller holds self._mu
        self._journal({"op": "seal", "sid": self._cur})
        self._segs[self._cur]["sealed"] = True
        os.close(self._cur_fd)
        self._cur += 1
        self._cur_fd = os.open(
            os.path.join(self.dir, _seg_name(self._cur)),
            os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        self._segs[self._cur] = {"size": 0, "sealed": False, "live": {}}

    def sync(self) -> None:
        """Eager durability (no collector armed): fsync the open
        segment + journal now."""
        if not _FSYNC:
            return
        with self._mu:
            if self._cur_fd >= 0:
                os.fsync(self._cur_fd)
            if self._jfd >= 0:
                os.fsync(self._jfd)

    def defer_sync(self, col: GroupCollector, storage=None) -> None:
        """Grouped durability: register dup'd segment + journal fds
        with the batch collector, dedup'd per store — N packed writes
        in one batch cost ONE segment fsync + ONE journal fsync."""
        if not _FSYNC:
            return
        with self._mu:
            if self._cur_fd >= 0:
                col.defer_fd(os.dup(self._cur_fd), storage=storage,
                             key=("seg", id(self), self._cur))
            if self._jfd >= 0:
                col.defer_fd(os.dup(self._jfd), storage=storage,
                             key=("segj", id(self)))

    def file(self, sid: int) -> str:
        """The path of segment ``sid``, the store loaded first: what
        :meth:`read` opens (xl_storage.read_shard_wave reads it too)."""
        with self._mu:
            self._ensure()
        return os.path.join(self.dir, _seg_name(sid))

    def read(self, sid: int, off: int, length: int) -> bytes:
        path = self.file(sid)
        try:
            fd = os.open(path, os.O_RDONLY)
        except FileNotFoundError:
            raise errors.FileNotFound(f"segment {sid}") from None
        try:
            data = os.pread(fd, length, off)
        finally:
            os.close(fd)
        if len(data) < length:
            raise errors.FileCorrupt(
                f"segment {sid}: short read {len(data)} < {length} "
                f"at +{off}")
        return data

    def stat(self, sid: int, off: int, length: int) -> int:
        """Extent length check (check_parts leg): FileNotFound when the
        segment is gone, FileCorrupt when it is too short."""
        with self._mu:
            self._ensure()
        try:
            size = os.stat(
                os.path.join(self.dir, _seg_name(sid))).st_size
        except FileNotFoundError:
            raise errors.FileNotFound(f"segment {sid}") from None
        if size < off + length:
            raise errors.FileCorrupt(
                f"segment {sid}: {size} < {off + length}")
        return length

    def free(self, sid: int, off: int) -> None:
        """Drop one extent; a sealed segment with zero live extents is
        unlinked on the spot (the degenerate compaction)."""
        unlink = False
        with self._mu:
            self._ensure()
            s = self._segs.get(sid)
            if s is None or off not in s["live"]:
                return
            s["live"].pop(off, None)
            self._journal({"op": "free", "sid": sid, "off": off})
            if s["sealed"] and not s["live"]:
                self._journal({"op": "drop", "sid": sid})
                self._segs.pop(sid, None)
                unlink = True
        if unlink:
            try:
                os.unlink(os.path.join(self.dir, _seg_name(sid)))
            except OSError:
                pass

    # -- compaction --------------------------------------------------------

    def compact(self, rewrite, min_dead_ratio: float = 0.5) -> dict:
        """Reclaim dead segment space: for every SEALED segment whose
        dead ratio crossed ``min_dead_ratio``, move each live extent
        through ``rewrite(vol, name, vid, sid, off, length) -> bool``
        (the drive rewrites the owner's xl.meta to a fresh extent and
        returns True, or False when the owner no longer references the
        extent — then it is simply freed).  Invariants: new bytes are
        durable before any owner meta moves (rewrite appends + syncs),
        an old extent is freed only once its owner stopped referencing
        it, and a segment file is unlinked only at zero live extents.
        Returns {"segments", "moved", "freed", "reclaimed_bytes"}."""
        with self._mu:
            self._ensure()
            candidates = []
            for sid, s in list(self._segs.items()):
                if not s["sealed"] or not s["size"]:
                    continue
                live = sum(ln for ln, *_ in s["live"].values())
                if not s["live"] or \
                        (s["size"] - live) / s["size"] >= min_dead_ratio:
                    candidates.append(
                        (sid, dict(s["live"]), s["size"] - live))
        moved = freed = segments = reclaimed = 0
        for sid, live, dead_bytes in candidates:
            for off, (length, vol, name, vid) in live.items():
                ok = False
                try:
                    ok = rewrite(vol, name, vid, sid, off, length)
                except Exception:  # noqa: BLE001 — next sweep retries
                    continue
                if ok:
                    moved += 1
                else:
                    freed += 1
                self.free(sid, off)
            segments += 1
            reclaimed += dead_bytes
        return {"segments": segments, "moved": moved, "freed": freed,
                "reclaimed_bytes": reclaimed}

    def stats(self) -> dict:
        with self._mu:
            if not self._loaded:
                return {"segments": 0, "live_bytes": 0, "dead_bytes": 0}
            live = dead = 0
            for s in self._segs.values():
                lb = sum(ln for ln, *_ in s["live"].values())
                live += lb
                dead += s["size"] - lb
            return {"segments": len(self._segs), "live_bytes": live,
                    "dead_bytes": dead}

    def close(self) -> None:
        with self._mu:
            if self._cur_fd >= 0:
                os.close(self._cur_fd)
                self._cur_fd = -1
            if self._jfd >= 0:
                os.close(self._jfd)
                self._jfd = -1
            self._loaded = False
            self._segs.clear()

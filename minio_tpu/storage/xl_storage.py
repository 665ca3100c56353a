"""xlStorage — local posix drive backend (cmd/xl-storage.go).

Layout per drive root:

    <root>/.mt.sys/format.json          drive identity (cmd/format-erasure.go)
    <root>/.mt.sys/tmp/<uuid>/...       staging area for in-flight writes
    <root>/<bucket>/<object>/xl.meta    version journal (xl_meta.py)
    <root>/<bucket>/<object>/<ddir>/part.N   erasure shard files (bitrot framed)

Write path is stage-then-commit: shard files land in tmp, ``rename_data``
atomically renames the data dir into place and rewrites xl.meta via
tmp+rename (the reference's CreateFile + RenameData contract,
cmd/xl-storage.go:1568,1965).  Durability: every commit path fsyncs the
file contents before the rename and fsyncs the parent directory after it
(the reference fdatasyncs CreateFile, cmd/xl-storage.go:1568, and relies
on O_DIRECT; the batched TPU pipeline writes whole shard files at once so
page-cache writeback, not alignment, is the governing factor).  Set
``MT_FSYNC=0`` to trade durability for throughput (benchmarks only).
"""

from __future__ import annotations

import ctypes
import errno
import functools
import itertools
import os
import shutil
import stat as stat_mod
import threading
import time
import uuid
from typing import Iterable

import numpy as np

from ..admin.metrics import GLOBAL as _metrics
from ..admin.metrics import KERNEL_BUCKETS
from ..obs import lastminute as _lastminute
from ..obs import trace as _trace
from . import commit as _commit
from . import errors
from .api import DiskInfo, StorageAPI, VolInfo
from .datatypes import FileInfo
from .xl_meta import XLMeta

SYS_DIR = ".mt.sys"
TMP_DIR = os.path.join(SYS_DIR, "tmp")
META_FILE = "xl.meta"
_RESERVED = {SYS_DIR}

# acknowledged writes must survive a crash; MT_FSYNC=0 is for benchmarks
_FSYNC = os.environ.get("MT_FSYNC", "1") != "0"

# commit micro-profiler op catalog — the syscall phases that compose a
# drive commit, decomposing ``drive_fanout_commit`` the way
# mt_s3_stage_seconds decomposed the request (ISSUE 17; docs drift rule
# checks each appears in docs/observability.md)
DRIVE_OPS = ("create", "append", "fsync", "rename", "meta_merge")
# tmpfs phases run single-digit microseconds; a sick spindle's fsync
# runs hundreds of ms — the buckets must resolve both ends
DRIVE_OP_BUCKETS = (0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001,
                    0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25)

# O_DIRECT on the drive hot path (cmd/xl-storage.go:1400-1568
# odirectReader / aligned writes): bypasses the page cache so bench
# numbers measure the drives, not RAM, and large objects are not
# double-buffered.  Env-gated (default off): requires 4 KiB-aligned
# buffers (mmap allocations) and falls back to buffered IO on
# filesystems without support (tmpfs returns EINVAL).
_ODIRECT = os.environ.get("MT_ODIRECT", "0") not in ("0", "", "off")
_ALIGN = 4096


def _read_odirect(full: str, offset: int, length: int) -> bytes | None:
    """Aligned O_DIRECT read; None = unsupported here (caller falls
    back to buffered)."""
    import mmap
    flags = os.O_RDONLY | getattr(os, "O_DIRECT", 0)
    try:
        fd = os.open(full, flags)
    except OSError as e:
        if e.errno == 22:           # EINVAL: fs without O_DIRECT
            return None
        raise
    try:
        a_off = offset - (offset % _ALIGN)
        a_len = ((offset + length + _ALIGN - 1) // _ALIGN) * _ALIGN \
            - a_off
        buf = mmap.mmap(-1, a_len)   # page-aligned, O_DIRECT-safe
        try:
            got = 0
            while got < a_len:
                n = os.preadv(fd, [memoryview(buf)[got:]], a_off + got)
                if n <= 0:
                    break            # EOF (tail block short is fine)
                got += n
            lo = offset - a_off
            return bytes(buf[lo:lo + length]) \
                if got >= lo + length else bytes(buf[lo:got])
        finally:
            buf.close()
    except OSError as e:
        if e.errno == 22:
            return None
        raise
    finally:
        os.close(fd)


_TMP_SEQ = itertools.count()


def _write_file_atomic(final_path: str, data, storage=None) -> None:
    """THE tmp -> fsync -> os.replace atomic-visibility recipe,
    raw-fd flavor — shared by write_all and the commit hot path so the
    durability protocol lives in exactly one place.  Tmp names use a
    pid+counter (unique within the machine); uuid4 costs ~14us a call
    and the 16-drive commit fan-out runs this per drive.

    Under a group commit (a collector armed on this writer thread) the
    SAME protocol runs batched: the tmp fd's fsync defers into the
    batch flush, and the visibility-flipping os.replace parks as an
    after-flush continuation — so the replace still happens only after
    THIS file's bytes (and every batch-mate's) are durable, and the
    parent-dir entry fsync re-registers behind the replace.  Pending
    content is published so a batch-mate's read-merge-write of the
    same path (two versions of one object in one batch) sees it."""
    tmp = final_path + f".tmp.{os.getpid():x}.{next(_TMP_SEQ):x}"
    col = _commit.collector()
    # create, write, dup (collector armed) or fsync (none), close: one
    # call below the interpreter where the library is (commit.land_file)
    _commit.land_file(tmp, data, storage=storage)
    if col is None:
        os.replace(tmp, final_path)
        return
    col.pending_put(final_path,
                    data if isinstance(data, bytes) else bytes(data))

    def _flip():
        os.replace(tmp, final_path)
        # the rename's directory entry needs its own fsync AFTER the
        # replace — re-register so the next flush round persists it
        col.defer_dir(os.path.dirname(final_path))
    col.after_flush(_flip)


def _fsync_fileobj(f, storage=None) -> None:
    if not _FSYNC:
        return
    f.flush()
    col = _commit.collector()
    if col is not None:
        # dup: the caller closes its own fd right after, and an fd
        # fsync at flush is immune to a rename in between
        col.defer_fd(os.dup(f.fileno()), storage=storage)
    else:
        os.fsync(f.fileno())


def _fsync_dir(path: str) -> None:
    """Persist directory entries (renames/creates) the way the reference's
    commit contract requires (cmd/xl-storage.go:1965 RenameData).  Under
    a group commit the fsync defers into the batch flush, where
    identical paths across the batch (the shared bucket dir of a
    fresh-object fan-in) collapse to one syscall."""
    if not _FSYNC:
        return
    col = _commit.collector()
    if col is not None:
        col.defer_dir(path)
        return
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _read_file(full: str, path: str) -> bytes:
    """The whole file ``full``; a missing file or a directory is
    ``FileNotFound(path)``, a refused one ``FileAccessDenied(path)``, any
    other error the ``OSError`` the open or read raised."""
    try:
        with open(full, "rb") as f:
            return f.read()
    except FileNotFoundError:
        raise errors.FileNotFound(path) from None
    except IsADirectoryError:
        raise errors.FileNotFound(path) from None
    except PermissionError as e:
        raise errors.FileAccessDenied(path) from e


def _is_valid_volname(volume: str) -> bool:
    return (len(volume) >= 3 if not volume.startswith(".mt.sys")
            else True) and "/" not in volume and volume not in ("", ".", "..")


class XLStorage(StorageAPI):
    """One local drive."""

    def __init__(self, root: str, endpoint: str | None = None):
        self.root = os.path.abspath(root)
        self._endpoint = endpoint or self.root
        self._disk_id = ""
        # last-minute latency windows (obs/lastminute.py): every traced
        # storage op records here; slow-drive detection and the
        # mt_node_disk_latency_* scrape read them
        self.latency = _lastminute.OpWindows(self._endpoint)
        # commit micro-profiler (ISSUE 17): per-op last-minute windows
        # for the syscall phases inside a commit (DRIVE_OPS) — always
        # on, same discipline as self.latency; the scrape-side twin is
        # the mt_drive_op_seconds{op} histogram
        self.commit_profile = _lastminute.OpWindows(self._endpoint)
        if not os.path.isdir(self.root):
            raise errors.DiskNotFound(self.root)
        os.makedirs(os.path.join(self.root, TMP_DIR), exist_ok=True)
        # volumes seen to exist: spares one stat per storage op on the
        # PUT hot path (invalidated on delete_vol; an externally wiped
        # drive surfaces as FileNotFound from the op itself, and the
        # DriveMonitor reformat path recreates volumes via make_vol)
        self._vols_seen: set[str] = set()
        # packed small-object segments (storage/commit.py): journaled
        # append-only files under .mt.sys/seg — lazily opened, journal
        # replayed on first packed op after a restart/crash
        self.segments = _commit.SegmentStore(
            os.path.join(self.root, SYS_DIR, _commit.SEG_DIR))

    # -- identity / health -------------------------------------------------

    def is_online(self) -> bool:
        return os.path.isdir(self.root)

    def endpoint(self) -> str:
        return self._endpoint

    def is_local(self) -> bool:
        return True

    def get_disk_id(self) -> str:
        return self._disk_id

    def set_disk_id(self, disk_id: str) -> None:
        self._disk_id = disk_id

    def disk_info(self) -> DiskInfo:
        st = os.statvfs(self.root)
        total = st.f_blocks * st.f_frsize
        free = st.f_bavail * st.f_frsize
        return DiskInfo(total=total, free=free, used=total - free,
                        free_inodes=st.f_favail, endpoint=self._endpoint,
                        mount_path=self.root, disk_id=self._disk_id)

    def close(self) -> None:
        pass

    def _prof(self, op: str, t0_ns: int, nbytes: int = 0) -> int:
        """One commit micro-profiler sample: charge the interval since
        ``t0_ns`` (monotonic) to ``op`` and return a fresh timestamp so
        callers chain phases: ``t = self._prof("create", t)``."""
        t1 = time.monotonic_ns()
        self.commit_profile.record(op, t1 - t0_ns, nbytes)
        _metrics.observe("mt_drive_op_seconds", {"op": op},
                         (t1 - t0_ns) / 1e9, buckets=DRIVE_OP_BUCKETS)
        return t1

    # -- path helpers ------------------------------------------------------

    def _vol_path(self, volume: str) -> str:
        if not _is_valid_volname(volume):
            raise errors.VolumeNotFound(volume)
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        vol = self._vol_path(volume)
        full = os.path.normpath(os.path.join(vol, path))
        if not full.startswith(vol + os.sep) and full != vol:
            raise errors.FileAccessDenied(path)  # path traversal guard
        return full

    def _check_vol(self, volume: str) -> str:
        p = self._vol_path(volume)
        if volume in self._vols_seen:
            return p
        if not os.path.isdir(p):
            raise errors.VolumeNotFound(volume)
        self._vols_seen.add(volume)
        return p

    # -- volume ops --------------------------------------------------------

    def make_vol(self, volume: str) -> None:
        p = self._vol_path(volume)
        if os.path.isdir(p):
            raise errors.VolumeExists(volume)
        try:
            os.makedirs(p)
        except PermissionError as e:
            raise errors.DiskAccessDenied(str(e)) from e

    def list_vols(self) -> list[VolInfo]:
        out = []
        for name in sorted(os.listdir(self.root)):
            if name in _RESERVED or not os.path.isdir(
                    os.path.join(self.root, name)):
                continue
            st = os.stat(os.path.join(self.root, name))
            out.append(VolInfo(name, int(st.st_ctime * 1e9)))
        return out

    def stat_vol(self, volume: str) -> VolInfo:
        p = self._check_vol(volume)
        try:
            st = os.stat(p)
        except FileNotFoundError:
            self._vols_seen.discard(volume)   # wiped under the cache
            raise errors.VolumeNotFound(volume) from None
        return VolInfo(volume, int(st.st_ctime * 1e9))

    def delete_vol(self, volume: str, force: bool = False) -> None:
        p = self._check_vol(volume)
        self._vols_seen.discard(volume)
        if force:
            try:
                shutil.rmtree(p)
            except FileNotFoundError:
                raise errors.VolumeNotFound(volume) from None
            return
        try:
            os.rmdir(p)
        except FileNotFoundError:      # wiped under the cache
            raise errors.VolumeNotFound(volume) from None
        except OSError as e:
            raise errors.VolumeNotEmpty(volume) from e

    # -- plain file ops ----------------------------------------------------

    def list_dir(self, volume: str, dir_path: str, count: int = -1) -> list[str]:
        base = self._file_path(volume, dir_path)
        self._check_vol(volume)
        try:
            names = []
            with os.scandir(base) as it:
                for e in it:
                    names.append(e.name + "/" if e.is_dir() else e.name)
                    if 0 < count <= len(names):
                        break
            return sorted(names)
        except FileNotFoundError:
            raise errors.FileNotFound(dir_path) from None
        except NotADirectoryError:
            raise errors.FileNotFound(dir_path) from None

    def read_all(self, volume: str, path: str) -> bytes:
        full = self._file_path(volume, path)
        self._check_vol(volume)
        return _read_file(full, path)

    def _open_create(self, volume: str, full: str):
        """Open for write, creating parents on the rare miss — but a
        missing VOLUME (wiped drive) must surface as VolumeNotFound,
        never be silently recreated (drive-death detection relies on
        writes failing, storage/health.py DriveMonitor)."""
        try:
            return open(full, "wb")
        except FileNotFoundError:
            if not os.path.isdir(self._vol_path(volume)):
                self._vols_seen.discard(volume)
                raise errors.VolumeNotFound(volume) from None
            os.makedirs(os.path.dirname(full), exist_ok=True)
            return open(full, "wb")

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        full = self._file_path(volume, path)
        self._check_vol(volume)
        try:
            _write_file_atomic(full, data, storage=self)
        except FileNotFoundError:
            # parent missing: create it (never a silently-wiped volume,
            # same contract as _open_create)
            if not os.path.isdir(self._vol_path(volume)):
                self._vols_seen.discard(volume)
                raise errors.VolumeNotFound(volume) from None
            os.makedirs(os.path.dirname(full), exist_ok=True)
            _write_file_atomic(full, data, storage=self)
        _fsync_dir(os.path.dirname(full))

    def create_file(self, volume: str, path: str, data: bytes,
                    file_size: int = -1) -> None:
        """Whole shard-file write (batched pipeline hands us the complete
        framed file; the reference streams through O_DIRECT,
        cmd/xl-storage.go:1568).  Writes DIRECTLY (no tmp+replace):
        every caller targets a staging path that rename_data later
        moves as a unit, so the inner rename would be a second level of
        the same atomicity."""
        if file_size >= 0 and len(data) != file_size:
            raise errors.FileCorrupt(
                f"size mismatch: {len(data)} != {file_size}")
        full = self._file_path(volume, path)
        self._check_vol(volume)
        t0 = time.monotonic_ns()
        if _ODIRECT:
            try:
                if self._create_file_odirect(full, data):
                    self._prof("create", t0, len(data))
                    return
            except FileNotFoundError:
                pass                 # parent missing: buffered path
                                     # below creates it and retries
        with self._open_create(volume, full) as f:
            f.write(data)
            t0 = self._prof("create", t0, len(data))
            _fsync_fileobj(f, storage=self)
            self._prof("fsync", t0)

    def append_file(self, volume: str, path: str, data: bytes) -> None:
        full = self._file_path(volume, path)
        self._check_vol(volume)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        t0 = time.monotonic_ns()
        with open(full, "ab") as f:
            f.write(data)
            t0 = self._prof("append", t0, len(data))
            _fsync_fileobj(f, storage=self)
            self._prof("fsync", t0)

    def write_stream(self, volume: str, path: str, chunks,
                     op: str = "create", file_size: int = -1) -> int:
        """Incremental create/append from an iterator of chunks — the
        landing side of the framed internode streaming mode
        (parallel/rpc.py): each chunk hits the file AS IT ARRIVES, one
        fsync at the end, so a streamed shard never materializes and
        the whole transfer is byte-identical to the equivalent
        create_file/append_file of the concatenation.  A mid-stream
        source failure (truncated frame, peer reset) removes a
        partially CREATED file — a later retry must never observe a
        half-written shard — while a partial APPEND leaves the file for
        the caller's staging-dir cleanup (the writer plane latches the
        drive error and the stream's tmp dir is dropped at settlement).
        Returns the byte count written."""
        full = self._file_path(volume, path)
        self._check_vol(volume)
        total = 0
        created = op != "append"
        try:
            if created:
                f = self._open_create(volume, full)
            else:
                os.makedirs(os.path.dirname(full), exist_ok=True)
                f = open(full, "ab")
            with f:
                for chunk in chunks:
                    f.write(chunk)
                    total += len(chunk)
                if file_size >= 0 and total != file_size:
                    raise errors.FileCorrupt(
                        f"size mismatch: {total} != {file_size}")
                _fsync_fileobj(f, storage=self)
        except BaseException:
            if created:
                try:
                    os.remove(full)
                except OSError:
                    pass
            raise
        return total

    def _create_file_odirect(self, full: str, data) -> bool:
        """Aligned O_DIRECT shard-file write (pad to 4 KiB, truncate to
        the real size — the reference's aligned writer does the same);
        False = unsupported filesystem, caller falls back."""
        import mmap
        flags = os.O_WRONLY | os.O_CREAT | os.O_TRUNC \
            | getattr(os, "O_DIRECT", 0)
        try:
            fd = os.open(full, flags, 0o644)
        except OSError as e:
            if e.errno == 22:
                return False
            raise
        buf = None
        try:
            mv = memoryview(data).cast("B")
            n = len(mv)
            a_len = max(((n + _ALIGN - 1) // _ALIGN) * _ALIGN, _ALIGN)
            buf = mmap.mmap(-1, a_len)
            buf[:n] = mv
            written = 0
            while written < a_len:
                w = os.pwritev(fd, [memoryview(buf)[written:a_len]],
                               written)
                if w <= 0:
                    raise OSError("short O_DIRECT write")
                written += w
            if a_len != n:
                os.ftruncate(fd, n)
            if _FSYNC:
                os.fsync(fd)
            return True
        except OSError as e:
            if getattr(e, "errno", None) == 22:
                return False
            raise
        finally:
            if buf is not None:
                buf.close()
            os.close(fd)

    def read_file_stream(self, volume: str, path: str, offset: int,
                         length: int) -> bytes:
        full = self._file_path(volume, path)
        try:
            data = None
            if _ODIRECT:
                data = _read_odirect(full, offset, length)
            if data is None:        # buffered path / O_DIRECT fallback
                with open(full, "rb") as f:
                    f.seek(offset)
                    data = f.read(length)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except PermissionError as e:
            raise errors.FileAccessDenied(path) from e
        if len(data) < length:
            raise errors.FileCorrupt(
                f"short read {len(data)} < {length} at {path}")
        return data

    def read_stream(self, volume: str, path: str, offset: int,
                    length: int, chunk: int):
        """Generator over ``[offset, offset+length)`` in ``chunk``-sized
        slices — ONE open/seek for the whole window (the serving side
        of a streamed raw GET reply; per-chunk read_file_stream calls
        would reopen the shard file for every frame).  The file is
        opened — and typed open errors raised — EAGERLY; short files
        surface as FileCorrupt from whichever slice hits EOF."""
        full = self._file_path(volume, path)
        try:
            f = open(full, "rb")
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except PermissionError as e:
            raise errors.FileAccessDenied(path) from e

        def gen():
            with f:
                f.seek(offset)
                left = length
                while left > 0:
                    b = f.read(min(chunk, left))
                    if not b:
                        raise errors.FileCorrupt(
                            f"short read {length - left} < {length} "
                            f"at {path}")
                    left -= len(b)
                    yield b

        return gen()

    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None:
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        self._check_vol(src_volume)
        self._check_vol(dst_volume)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        try:
            os.replace(src, dst)
        except FileNotFoundError:
            raise errors.FileNotFound(src_path) from None
        _fsync_dir(os.path.dirname(dst))

    def delete(self, volume: str, path: str, recursive: bool = False) -> None:
        full = self._file_path(volume, path)
        self._check_vol(volume)
        try:
            if os.path.isdir(full):
                if recursive:
                    shutil.rmtree(full)
                else:
                    os.rmdir(full)
            else:
                os.remove(full)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except OSError as e:
            raise errors.PathNotEmpty(path) from e
        # prune now-empty parent dirs up to the volume root (deleteFile)
        parent = os.path.dirname(full)
        vol = self._vol_path(volume)
        while parent != vol:
            try:
                os.rmdir(parent)
            except OSError:
                break
            parent = os.path.dirname(parent)

    def stat_info_file(self, volume: str, path: str) -> int:
        full = self._file_path(volume, path)
        try:
            st = os.stat(full)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        if not stat_mod.S_ISREG(st.st_mode):
            raise errors.IsNotRegular(path)
        return st.st_size

    # -- xl.meta ops -------------------------------------------------------

    def _meta_path(self, volume: str, path: str) -> str:
        return self._file_path(volume, os.path.join(path, META_FILE))

    def _read_meta(self, volume: str, path: str) -> XLMeta:
        col = _commit.collector()
        if col is not None:
            # read-after-deferred-write: a batch-mate's xl.meta replace
            # may still be parked behind the flush — merge against the
            # pending content, not the stale on-disk file
            pending = col.pending_get(self._meta_path(volume, path))
            if pending is not None:
                return XLMeta.load(pending)
        try:
            buf = self.read_all(volume, os.path.join(path, META_FILE))
        except errors.FileNotFound:
            raise errors.FileNotFound(f"{volume}/{path}") from None
        return XLMeta.load(buf)

    def _write_meta(self, volume: str, path: str, meta: XLMeta) -> None:
        self.write_all(volume, os.path.join(path, META_FILE), meta.dump())

    @staticmethod
    def _purge_later(path: str) -> None:
        """Purge a replaced version's dead payload — but never before
        the replacing xl.meta is DURABLE: under a group commit the
        rmtree parks TWO continuation rounds out (past the deferred
        meta replace, past the replace's re-registered dir fsync), so a
        crash mid-flush can resurrect the old xl.meta yet still find
        its data dir intact, exactly like the eager order."""
        col = _commit.collector()
        if col is None:
            shutil.rmtree(path, ignore_errors=True)
            return
        col.after_flush(lambda: col.after_flush(
            lambda: shutil.rmtree(path, ignore_errors=True)))

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Atomic commit (cmd/xl-storage.go:1965): move staged data dir from
        tmp into the object path and merge the new version into xl.meta."""
        src_dir = self._file_path(src_volume, src_path)
        self._check_vol(src_volume)
        self._check_vol(dst_volume)
        dst_obj_dir = self._file_path(dst_volume, dst_path)
        try:
            meta = self._read_meta(dst_volume, dst_path)
        except (errors.FileNotFound, errors.FileCorrupt):
            meta = XLMeta()
        # replaced version with an unshared data dir gets purged
        old_ddir = ""
        try:
            old = meta.find(fi.version_id)
            old_ddir = old.get("ddir", "")
        except errors.FileVersionNotFound:
            pass
        meta.add_version(fi)
        if fi.data_dir:
            t_op = time.monotonic_ns()
            dst_data_dir = os.path.join(dst_obj_dir, fi.data_dir)
            if not os.path.isdir(src_dir):
                raise errors.FileNotFound(src_path)
            os.makedirs(dst_obj_dir, exist_ok=True)
            if os.path.isdir(dst_data_dir):
                shutil.rmtree(dst_data_dir)
            os.replace(src_dir, dst_data_dir)
            t_op = self._prof("rename", t_op)
            _fsync_dir(dst_obj_dir)
            self._prof("fsync", t_op)
        else:
            os.makedirs(dst_obj_dir, exist_ok=True)
        # xl.meta write fsyncs itself + the object dir (write_all); the
        # parent entry for a freshly created object dir needs one more
        t_meta = time.monotonic_ns()
        self._write_meta(dst_volume, dst_path, meta)
        _fsync_dir(os.path.dirname(dst_obj_dir))
        self._prof("meta_merge", t_meta)
        if old_ddir and old_ddir != fi.data_dir \
                and meta.shared_data_dir_count(fi.version_id, old_ddir) == 0:
            self._purge_later(os.path.join(dst_obj_dir, old_ddir))

    def _in_obj_dir(self, volume: str, dst_obj: str, land=None) -> bool:
        """Make the object directory and say whether it is fresh.
        ``land`` is a step that begins with that mkdir and goes on from
        there (commit.land_part); None is the mkdir alone.  A missing
        parent is a nested object name, made here and the step run
        again, unless it is the VOLUME that is gone: a wiped volume must
        NOT be resurrected."""
        try:
            return land() if land else _commit.mkdir_fresh(dst_obj)
        except FileNotFoundError as e:
            if e.filename != dst_obj:
                raise
            if not os.path.isdir(self._vol_path(volume)):
                self._vols_seen.discard(volume)
                raise errors.VolumeNotFound(volume) from None
            os.makedirs(dst_obj, exist_ok=True)   # nested object name
            if land:
                land()
            return True

    def write_data_commit(self, volume: str, path: str, fi: FileInfo,
                          data, shard_index: int | None = None,
                          version_dict: dict | None = None,
                          meta_gate=None) -> None:
        """Direct single-part PUT commit (hot path): part file written
        straight into its final data-dir location, version merged into
        xl.meta last.  Crash mid-write leaves an orphan uuid data dir the
        scanner purges as dangling — the object version is only visible
        once the xl.meta replace lands (same contract as rename_data,
        minus one tmp mkdir + rename round per drive).

        ``shard_index``/``version_dict``: the 16-drive fan-out serializes
        the FileInfo ONCE and patches only the per-drive erasure index
        here, instead of deep-cloning two dataclasses per drive
        (cmd/erasure-object.go:614 writes a per-disk FileInfo the same
        way, varying Erasure.Index only).

        ``meta_gate`` (overlapped PUT): the part bytes — the GIL-free
        bulk of this call — land FIRST, then the gate blocks until the
        object's md5 resolved and yields the final version dict; the
        merge uses it.  A gate abort (BadDigest) raises before
        any version becomes visible, leaving only an orphan data dir
        the caller purges.  Under a group commit the drive's one writer
        thread does not wait at a gate that says it is not ``ready()``:
        the merge is handed to the collector and runs, in op order,
        once the batch's other bodies have landed their bytes."""
        self._check_vol(volume)
        dst_obj = self._file_path(volume, path)
        stream_ddir = None
        col = _commit.collector()
        streaming = hasattr(data, "__next__")
        ddir = dst_obj + "/" + fi.data_dir
        if not fi.data_dir:
            fresh = self._in_obj_dir(volume, dst_obj)
        elif not (streaming or _ODIRECT):
            # one-shot: both mkdirs and the part file (create, write,
            # dup or fsync, close) in one call below the interpreter
            # where the library is — the 16-drive commit fan-out runs
            # this per drive (commit.land_part)
            t_op = time.monotonic_ns()
            fresh = self._in_obj_dir(
                volume, dst_obj, lambda: _commit.land_part(
                    dst_obj, ddir, ddir + "/part.1", data, storage=self))
            t_op = self._prof("create", t_op, len(data))
            _fsync_dir(ddir)
            self._prof("fsync", t_op)
        else:
            fresh = self._in_obj_dir(volume, dst_obj)
            os.mkdir(ddir)
            part = ddir + "/part.1"
            t_op = time.monotonic_ns()
            try:
                if streaming:
                    # framed internode streaming: part bytes land chunk
                    # by chunk as the frames arrive (O(chunk) memory);
                    # a mid-stream death removes the partial data dir
                    # below so no half-written shard survives
                    stream_ddir = ddir
                    fd = os.open(part,
                                 os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                                 0o644)
                    try:
                        for chunk in data:
                            _commit.write_full(fd, chunk)
                        t_op = self._prof("create", t_op)
                        if _FSYNC:
                            if col is not None:
                                col.defer_fd(os.dup(fd), storage=self)
                            else:
                                os.fsync(fd)
                    finally:
                        os.close(fd)
                else:
                    # O_DIRECT lands the part whole; a filesystem
                    # without it gets the buffered landing
                    if not self._create_file_odirect(part, data):
                        _commit.land_file(part, data, storage=self)
                    t_op = self._prof("create", t_op, len(data))
                _fsync_dir(ddir)
                self._prof("fsync", t_op)
            except BaseException:
                if stream_ddir is not None:
                    shutil.rmtree(stream_ddir, ignore_errors=True)
                raise
        merge = functools.partial(
            self._merge_version, volume, path, fi, dst_obj, fresh,
            shard_index, version_dict, meta_gate, stream_ddir)
        if col is not None:
            ready = getattr(meta_gate, "ready", None)
            if ready is not None and stream_ddir is None \
                    and (col.tails() or not ready()):
                # the digest is not there yet (or an earlier op of this
                # batch waits for its own): the drive's one writer
                # thread goes on to the batch's next body instead of
                # parking here, and the merge runs when the bodies have
                col.yield_tail(merge)
                return
            col.run_tails()      # owed by earlier ops: theirs go first
        merge()

    def _merge_version(self, volume: str, path: str, fi: FileInfo,
                       dst_obj: str, fresh: bool, shard_index,
                       version_dict, meta_gate, stream_ddir) -> None:
        """write_data_commit's second half: pass the gate, merge the
        version into xl.meta."""
        if meta_gate is not None:
            # md5 beside the write above; the park is caller-side work,
            # not drive time — keep it out of the latency windows that
            # feed slow-drive detection (_traced_op subtracts it)
            t_gate = time.monotonic_ns()
            try:
                version_dict = meta_gate()
            except BaseException:
                if stream_ddir is not None:
                    # streamed gate abort (BadDigest trailer): discard
                    # the part NOW — the aborting client may be gone
                    # before its purge fan-out reaches this drive
                    shutil.rmtree(stream_ddir, ignore_errors=True)
                raise
            _IN_TRACED_OP.exclude_ns = getattr(
                _IN_TRACED_OP, "exclude_ns", 0) \
                + (time.monotonic_ns() - t_gate)
        t_meta = time.monotonic_ns()   # gate park excluded: not drive time
        meta = XLMeta()
        old_ddir = ""
        if not fresh:
            try:
                meta = self._read_meta(volume, path)
                try:
                    old_ddir = meta.find(fi.version_id).get("ddir", "")
                except errors.FileVersionNotFound:
                    pass
            except (errors.FileNotFound, errors.FileCorrupt):
                pass
        vd = dict(version_dict) if version_dict is not None \
            else fi.to_dict()
        if shard_index is not None:
            vd["ec"] = dict(vd["ec"], index=shard_index)
        meta.add_version_dict(vd)
        _write_file_atomic(dst_obj + "/" + META_FILE, meta.dump(),
                           storage=self)
        _fsync_dir(dst_obj)
        if fresh:
            _fsync_dir(os.path.dirname(dst_obj))
        self._prof("meta_merge", t_meta)
        if old_ddir and old_ddir != fi.data_dir \
                and meta.shared_data_dir_count(fi.version_id, old_ddir) == 0:
            self._purge_later(os.path.join(dst_obj, old_ddir))

    def write_packed(self, volume: str, path: str, fi: FileInfo,
                     data, shard_index: int | None = None,
                     version_dict: dict | None = None) -> None:
        """Packed small-object commit: the framed shard appends into
        this drive's open segment file (one journaled ``add`` record)
        instead of its own part file, and xl.meta points into the
        segment via the per-drive ``seg`` version field.  Under a group
        commit, durability rides the batch flush where the segment and
        journal fds DEDUPLICATE — N tiny commits on a drive fold into
        one segment fsync + one journal fsync — and the xl.meta replace
        parks behind those fsyncs (write-ahead: a version is never
        visible before its extent is durable).  Saves the per-object
        data-dir mkdir, part-file create+fsync, and data-dir fsync the
        write_data_commit path pays."""
        self._check_vol(volume)
        dst_obj = self._file_path(volume, path)
        fresh = self._in_obj_dir(volume, dst_obj)
        col = _commit.collector()
        nbytes = len(data)
        t_op = time.monotonic_ns()
        sid, off = self.segments.append(data, volume, path,
                                        fi.version_id)
        t_op = self._prof("create", t_op, nbytes)
        if col is not None:
            self.segments.defer_sync(col, storage=self)
            col.seg_bytes += nbytes
        else:
            self.segments.sync()
            self._prof("fsync", t_op)
        t_meta = time.monotonic_ns()
        meta = XLMeta()
        old_ddir, old_seg = "", None
        if not fresh:
            try:
                meta = self._read_meta(volume, path)
                try:
                    old = meta.find(fi.version_id)
                    old_ddir = old.get("ddir", "")
                    old_seg = old.get("seg")
                except errors.FileVersionNotFound:
                    pass
            except (errors.FileNotFound, errors.FileCorrupt):
                pass
        vd = dict(version_dict) if version_dict is not None \
            else fi.to_dict()
        if shard_index is not None:
            vd["ec"] = dict(vd["ec"], index=shard_index)
        vd["ddir"] = ""
        vd["seg"] = {"sid": sid, "off": off, "len": nbytes}
        meta.add_version_dict(vd)
        _write_file_atomic(dst_obj + "/" + META_FILE, meta.dump(),
                           storage=self)
        _fsync_dir(dst_obj)
        if fresh:
            _fsync_dir(os.path.dirname(dst_obj))
        self._prof("meta_merge", t_meta)
        # replaced version's payload released only after the new meta
        # is durable (same two-rounds-out discipline as _purge_later)
        if old_ddir \
                and meta.shared_data_dir_count(fi.version_id,
                                               old_ddir) == 0:
            self._purge_later(os.path.join(dst_obj, old_ddir))
        if old_seg:
            osid, ooff = old_seg["sid"], old_seg["off"]
            if col is None:
                self.segments.free(osid, ooff)
            else:
                col.after_flush(lambda: col.after_flush(
                    lambda: self.segments.free(osid, ooff)))

    def read_segment(self, sid: int, off: int, length: int) -> bytes:
        """Read one packed extent (the GET-side of the ``seg``
        indirection)."""
        return self.segments.read(sid, off, length)

    def compact_segments(self, min_dead_ratio: float = 0.5) -> dict:
        """Background segment compaction (ridden by the heal sweep):
        live extents of mostly-dead SEALED segments are re-appended and
        their owners' xl.meta rewritten to the fresh extent; extents
        whose owner version is gone (or moved on) are simply freed.
        Order per extent: new bytes durable first, then the owner meta
        flip, then the old extent free — a crash anywhere leaves a
        readable object plus at worst a leaked extent the next sweep
        reclaims."""
        def rewrite(vol: str, name: str, vid: str, sid: int, off: int,
                    length: int) -> bool:
            try:
                meta = self._read_meta(vol, name)
                v = meta.find(vid)
            except errors.StorageError:
                return False
            seg = v.get("seg")
            if not seg or seg["sid"] != sid or seg["off"] != off:
                return False
            data = self.segments.read(sid, off, length)
            nsid, noff = self.segments.append(data, vol, name, vid)
            self.segments.sync()
            nv = dict(v)
            nv["seg"] = {"sid": nsid, "off": noff, "len": length}
            meta.add_version_dict(nv)
            self._write_meta(vol, name, meta)
            return True
        return self.segments.compact(rewrite, min_dead_ratio)

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        # the INLINE-object commit path (erasure_object._commit_put for
        # sizes under the inline threshold): this read-merge-write IS
        # the whole drive-side commit, so it charges meta_merge
        t0 = time.monotonic_ns()
        try:
            meta = self._read_meta(volume, path)
        except errors.FileNotFound:
            meta = XLMeta()
        meta.add_version(fi)
        os.makedirs(self._file_path(volume, path), exist_ok=True)
        self._write_meta(volume, path, meta)
        self._prof("meta_merge", t0)

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        t0 = time.monotonic_ns()
        meta = self._read_meta(volume, path)
        meta.find(fi.version_id)  # must exist
        meta.add_version(fi)
        self._write_meta(volume, path, meta)
        self._prof("meta_merge", t0)

    def read_version(self, volume: str, path: str,
                     version_id: str | None = None,
                     read_data: bool = False) -> FileInfo:
        meta = self._read_meta(volume, path)
        fi = meta.to_fileinfo(volume, path, version_id)
        return fi

    def list_versions(self, volume: str, path: str) -> list[FileInfo]:
        meta = self._read_meta(volume, path)
        return meta.list_versions(volume, path)

    def delete_version(self, volume: str, path: str, fi: FileInfo,
                       force_del_marker: bool = False) -> None:
        """Remove one version; delete markers write a new version instead
        (cmd/xl-storage.go DeleteVersion semantics)."""
        try:
            meta = self._read_meta(volume, path)
        except errors.FileNotFound:
            if fi.deleted and force_del_marker:
                self.write_metadata(volume, path, fi)
                return
            raise
        if fi.deleted:
            meta.add_version(fi)
            self._write_meta(volume, path, meta)
            return
        old_seg = None
        try:
            old_seg = meta.find(fi.version_id).get("seg")
        except errors.FileVersionNotFound:
            pass
        ddir = meta.delete_version(fi.version_id)
        obj_dir = self._file_path(volume, path)
        if ddir and meta.shared_data_dir_count(fi.version_id, ddir) == 0:
            shutil.rmtree(os.path.join(obj_dir, ddir), ignore_errors=True)
        if meta.versions:
            self._write_meta(volume, path, meta)
        else:
            # last version gone: remove xl.meta and prune the object path
            self.delete(volume, os.path.join(path, META_FILE))
        if old_seg:
            # packed extent freed AFTER the meta stopped referencing it
            # (journaled; a sealed segment at zero live extents unlinks)
            self.segments.free(old_seg["sid"], old_seg["off"])

    # -- integrity ---------------------------------------------------------

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        from ..hashing import bitrot
        ec = fi.erasure
        seg = getattr(fi, "seg", None)
        for part in fi.parts:
            if seg:
                # packed object (single part): the framed shard lives
                # in the segment; bitrot framing verifies the same way
                pf = f"seg.{seg['sid']:08x}+{seg['off']}"
                data = self.segments.read(seg["sid"], seg["off"],
                                          seg["len"])
                ck = ec.get_checksum_info(part.number)
            else:
                pf = os.path.join(path, fi.data_dir,
                                  f"part.{part.number}")
                ck = ec.get_checksum_info(part.number)
                data = self.read_all(volume, pf)
            shard_size = ec.shard_size()
            if bitrot.is_streaming(ck.algorithm):
                want = bitrot.bitrot_shard_file_size(
                    ec.shard_file_size(part.size), shard_size, ck.algorithm)
                if len(data) != want:
                    raise errors.FileCorrupt(
                        f"{pf}: size {len(data)} != {want}")
                r = bitrot.StreamingBitrotReader(data, shard_size,
                                                 ck.algorithm)
                try:
                    r.read_at(0, ec.shard_file_size(part.size))
                except bitrot.BitrotError as e:
                    raise errors.FileCorrupt(f"{pf}: {e}") from e
            else:
                if not bitrot.BitrotVerifier(ck.algorithm, ck.hash).verify(data):
                    raise errors.FileCorrupt(pf)

    def check_parts(self, volume: str, path: str, fi: FileInfo) -> None:
        from ..hashing import bitrot
        ec = fi.erasure
        seg = getattr(fi, "seg", None)
        for part in fi.parts:
            if seg:
                pf = f"seg.{seg['sid']:08x}+{seg['off']}"
                size = self.segments.stat(seg["sid"], seg["off"],
                                          seg["len"])
            else:
                pf = os.path.join(path, fi.data_dir,
                                  f"part.{part.number}")
                size = self.stat_info_file(volume, pf)
            ck = ec.get_checksum_info(part.number)
            want = bitrot.bitrot_shard_file_size(
                ec.shard_file_size(part.size), ec.shard_size(), ck.algorithm)
            if size != want:
                raise errors.FileCorrupt(f"{pf}: size {size} != {want}")

    # -- walking -----------------------------------------------------------

    def walk_dir(self, volume: str, base_dir: str = "",
                 recursive: bool = True) -> Iterable[str]:
        """Yield object paths (dirs containing xl.meta) under base_dir
        in FLAT key order — the UTF-8 binary order S3 listings promise
        (cmd/metacache-walk.go WalkDir, which sorts dir entries with a
        trailing-slash key for the same reason): a subtree "x" emits
        keys "x/...", which must sort AFTER a sibling object "x-1"
        ('-' < '/'), so siblings order by ``name + "/"`` for subtrees
        and plain ``name`` for leaf objects.  Per-drive streams being
        globally sorted is what lets the listing layer k-way-merge
        them lazily instead of materializing the namespace."""
        vol = self._check_vol(volume)
        base = self._file_path(volume, base_dir) if base_dir else vol

        def walk(d: str):
            try:
                entries = sorted(os.scandir(d), key=lambda e: e.name)
            except (FileNotFoundError, NotADirectoryError):
                return
            names = {e.name for e in entries}
            if META_FILE in names:
                yield os.path.relpath(d, vol).replace(os.sep, "/")
                return
            keyed = []
            for e in entries:
                if not e.is_dir():
                    continue
                leaf = os.path.isfile(os.path.join(e.path, META_FILE))
                keyed.append((e.name if leaf else e.name + "/", e.path))
            for _, path in sorted(keyed):
                if recursive:
                    yield from walk(path)

        yield from walk(base)

    def walk_entries(self, volume: str, base_dir: str = "",
                     recursive: bool = True,
                     versions: bool = False) -> Iterable[dict]:
        """Walk objects AND their xl.meta-derived metadata in one pass
        (cmd/metacache-walk.go WalkDir streams raw xl.meta per entry):
        yields {"name", "fis": [FileInfo dicts]} — latest version only,
        or every version with ``versions``.  Listing resolve consumes
        these walked streams instead of issuing a quorum read per key
        (cmd/metacache-set.go:544,834)."""
        for name in self.walk_dir(volume, base_dir, recursive):
            try:
                meta = self._read_meta(volume, name)
                if versions:
                    fis = meta.list_versions(volume, name)
                else:
                    fis = [meta.to_fileinfo(volume, name, None)]
            except errors.StorageError:
                continue            # torn/missing meta: other drives win
            yield {"name": name, "fis": [fi.to_dict() for fi in fis]}

    # -- staging helpers (used by the erasure object layer) ---------------

    def tmp_dir(self) -> str:
        """New unique staging dir; returned path is relative to the SYS_DIR
        volume (use with volume=SYS_DIR in create_file/rename_data)."""
        d = os.path.join("tmp", uuid.uuid4().hex)
        leaf = os.path.join(self.root, SYS_DIR, d)
        try:                       # tmp root exists since __init__ —
            os.mkdir(leaf)         # one syscall, not a makedirs walk
        except FileNotFoundError:  # SYS_DIR gone = drive wiped under us;
            # recreating it would mask drive death from the monitor
            raise errors.DiskNotFound(self.root) from None
        return d

    def clean_tmp(self, rel_dir: str) -> None:
        shutil.rmtree(os.path.join(self.root, SYS_DIR, rel_dir),
                      ignore_errors=True)


# -- per-op instrumentation (deep tracing plane) ---------------------------
# Every data-plane method records into the drive's last-minute latency
# window (always on — slow-drive detection and mt_node_disk_latency_*
# need it) and, from the same ``dt``, into the cumulative family
# ``mt_drive_call_seconds{op,kind="local"}`` (a scrape pair can
# difference it; the window's gauges it cannot) and, only when a trace
# consumer is active, publishes a
# ``storage``-type span to the HTTP_TRACE hub (`mc admin trace -a`
# storage calls, cmd/xl-storage-disk-id-check.go trace wrappers).  With
# zero subscribers and an idle peer ring the per-op cost beyond the
# window update is a single predicate — no dict is ever built.

_TRACED_OPS = ("read_all", "read_file_stream", "write_all",
               "create_file", "append_file", "write_data_commit",
               "write_packed", "read_segment",
               "rename_data", "rename_file", "write_metadata",
               "update_metadata", "read_version", "list_versions",
               "delete_version", "delete", "stat_info_file", "list_dir",
               "verify_file", "check_parts")
# payload position in the post-self positional args for write-side ops;
# read-side ops report the returned byte count instead
_OP_IN_ARG = {"write_all": 2, "create_file": 2, "append_file": 2,
              "write_data_commit": 3, "write_packed": 3}

# re-entrancy guard: traced ops call each other internally (verify_file
# reads parts via read_all, delete_version rewrites xl.meta via
# write_metadata, every meta op goes through read_all/write_all) — only
# the OUTERMOST call records, like the reference's disk-id-check proxy
# where inner self-calls bypass the wrapper; otherwise one logical op
# double-counts latency and emits nested duplicate spans
_IN_TRACED_OP = threading.local()


def _publish_call(drive: XLStorage, op: str, start_ns: int, dt: int,
                  err: str, where, in_bytes: int = 0,
                  out_bytes: int = 0) -> None:
    """One drive call's ``storage.<op>`` span: the full span to the trace
    hub when a consumer is active (``where()`` gives its volume and
    path), else, with a request in context, one compact tuple in the
    idle causal ring (make_span rings on the active branch): requests
    keep their drive-op children for trace-tree assembly with zero
    subscribers, no dict built (the idle contract)."""
    if _trace.active():
        vol, path = where()
        _trace.publish_span(_trace.make_span(
            "storage", f"storage.{op}", start_ns=start_ns, duration_ns=dt,
            input_bytes=in_bytes, output_bytes=out_bytes, error=err,
            detail={"drive": drive._endpoint, "volume": vol,
                    "path": path}))
        return
    rid = _trace.get_request_id()
    if rid:
        _trace.ring_append(rid, _trace.new_span_id(),
                           _trace.get_span_parent(), "storage",
                           f"storage.{op}", start_ns, dt, err,
                           drive._endpoint)


def _traced_op(op: str, fn, in_arg: int | None):
    call_labels = {"op": op, "kind": "local"}

    def traced(self, *a, **kw):
        if getattr(_IN_TRACED_OP, "depth", 0):
            return fn(self, *a, **kw)
        col = _commit.collector()
        if col is not None and op != "write_data_commit":
            # earlier ops of this batch that yielded at their gate are
            # owed their second half before any op that cannot queue
            # behind them starts (write_data_commit sees to its own)
            col.run_tails()
        _IN_TRACED_OP.depth = 1
        _IN_TRACED_OP.exclude_ns = 0
        # monotonic for the duration (an NTP step must not corrupt the
        # latency windows feeding slow-drive detection); the wall clock
        # is read only when a span is actually published
        t0 = time.monotonic_ns()
        err = ""
        out = None
        try:
            out = fn(self, *a, **kw)
            return out
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            raise
        finally:
            _IN_TRACED_OP.depth = 0
            # an op may park on caller-side work mid-call (the
            # overlapped commit's etag gate in write_data_commit);
            # that wait is not drive time
            dt = max(0, time.monotonic_ns() - t0
                     - getattr(_IN_TRACED_OP, "exclude_ns", 0))
            nbytes = 0
            if in_arg is not None:
                data = a[in_arg] if len(a) > in_arg \
                    else kw.get("data")
                try:
                    nbytes = len(data) if data is not None else 0
                except TypeError:
                    nbytes = 0
            elif isinstance(out, (bytes, bytearray)):
                nbytes = len(out)
            self.latency.record(op, dt, nbytes)
            _metrics.observe("mt_drive_call_seconds", call_labels,
                             dt / 1e9, buckets=KERNEL_BUCKETS)
            _publish_call(
                self, op, time.time_ns() - dt, dt, err,
                lambda: (a[0] if a and isinstance(a[0], str)
                         else kw.get("volume", ""),
                         a[1] if len(a) > 1 and isinstance(a[1], str)
                         else kw.get("path", "")),
                nbytes if in_arg is not None else 0,
                0 if in_arg is not None else nbytes)
    traced.__name__ = op
    traced.__qualname__ = f"XLStorage.{op}"
    traced.__wrapped__ = fn
    return traced


for _op in _TRACED_OPS:
    setattr(XLStorage, _op,
            _traced_op(_op, getattr(XLStorage, _op),
                       _OP_IN_ARG.get(_op)))


# -- a quorum metadata read's local drives, in one native wave --------------
# read_version on 16 drives was 16 pool children: each waited for a pool
# thread and the interpreter to start, then for the interpreter again
# after each of its open / read / close (PERF.md, meta_queue_ms).  The
# local drives' xl.meta files are read instead by ONE call on the
# calling thread that never holds the interpreter lock
# (native/syncwave.c mt_read_files); the decode and every per-drive rule
# of read_version stay here.

# syncwave.c MT_READ_TOOBIG: the file is larger than its slot
_READ_TOOBIG = -1
# bytes per drive in a thread's arena: from _SLOT_MIN, doubled where the
# thread meets a larger xl.meta (an inline object's shard lives in it),
# up to _SLOT_MAX; a file over that is read again by the plain path
_SLOT_MIN, _SLOT_MAX = 8 << 10, 256 << 10
_WAVE_TLS = threading.local()
_CALL_LABELS = {"op": "read_version", "kind": "local"}


def wave_target(disk) -> XLStorage | None:
    """The local drive a read wave reads for ``disk``: a plain
    ``XLStorage``, or the one under an online ``HealthDisk``
    (``wave_storage``); None for every other drive (remote, offline,
    wrapped otherwise), which is called as always."""
    if type(disk) is XLStorage:
        return disk
    under = getattr(type(disk), "wave_storage", None)
    return under(disk) if under is not None else None


def wave_positions(disks) -> list[int]:
    """Positions of the drives of ``disks`` that :func:`read_version_wave`
    reads in its native wave: none where the library cannot be loaded or
    a group collector is armed on this thread (a read must see its
    ``pending_get``)."""
    if _commit._wave_lib() is None or _commit.collector() is not None:
        return []
    return [i for i, d in enumerate(disks) if wave_target(d) is not None]


def _slot_arena(n: int):
    """This thread's arena for ``n`` slots, and the slot size."""
    cap = getattr(_WAVE_TLS, "cap", _SLOT_MIN)
    arena = getattr(_WAVE_TLS, "arena", None)
    if arena is None or len(arena) < n * cap:
        arena = _WAVE_TLS.arena = ctypes.create_string_buffer(n * cap)
    return arena, cap


def _grow_slot(size: int) -> None:
    cap = getattr(_WAVE_TLS, "cap", _SLOT_MIN)
    while cap < size and cap < _SLOT_MAX:
        cap *= 2
    _WAVE_TLS.cap = cap


def _read_error(err: int, full: str, path: str) -> Exception:
    """What :func:`_read_file` raises for the errno ``err``."""
    if err in (errno.ENOENT, errno.EISDIR):
        return errors.FileNotFound(path)
    e = OSError(err, os.strerror(err), full)
    if isinstance(e, PermissionError):
        return errors.FileAccessDenied(path)
    return e


def read_version_wave(disks, volume: str, path: str,
                      version_id: str | None = None) -> list[tuple]:
    """``read_version`` on every drive of ``disks``, each one that
    :func:`wave_target` reads (the caller picks them with
    :func:`wave_positions`), from the calling thread: their ``xl.meta``
    files are read by ONE native call (at most 8 threads, joined before
    it returns) that never holds the interpreter lock.
    Per drive, in order: ``(FileInfo | None, error | None, start_ns,
    end_ns)`` on the monotonic clock.

    Each drive keeps ``read_version``'s semantics: the path passes the
    traversal guard (``FileAccessDenied``) and the volume check
    (``VolumeNotFound``); a missing file or a directory is
    ``FileNotFound``, a refused one ``FileAccessDenied``, any other errno
    the ``OSError`` a read would raise; a bad file ``FileCorrupt``; a
    missing version ``FileVersionNotFound``.  What follows the native
    read (the error, the decode) runs as the drive's call under its
    breaker (``HealthDisk.guarded``), so a failure is judged as any call
    of the drive is.  A drive is observed as ``_traced_op`` observes the
    call: its last-minute window, ``mt_drive_call_seconds{op=
    read_version,kind=local}`` (the native read's own time) and its
    ``storage.read_version`` span."""
    rel = os.path.join(path, META_FILE)
    xls = [wave_target(d) for d in disks]
    got: list = []      # per drive: its file, or what refused its path
    t0s, t1s = [], []
    wave = []           # positions whose file the native call reads
    for i, xl in enumerate(xls):
        t0s.append(time.monotonic_ns())
        try:
            full = xl._file_path(volume, rel)
            xl._check_vol(volume)
        except Exception as e:  # noqa: BLE001 — raised as the drive's call
            full = e
        else:
            wave.append(i)
        got.append(full)
        t1s.append(time.monotonic_ns())
    if wave:
        _read_files(wave, got, t0s, t1s)
    out, dts = [], []
    for i, d in enumerate(disks):
        try:
            fi, err = _as_call(d, _decode, got[i], volume, path, rel,
                               version_id), None
        except Exception as e:  # noqa: BLE001 — per-drive isolation
            fi, err = None, e
        if isinstance(got[i], tuple) and got[i][1] == _READ_TOOBIG:
            t1s[i] = time.monotonic_ns()     # read again by the plain path
        out.append((fi, err, t0s[i], t1s[i]))
        dts.append(_observe_wave(xls[i], "read_version", (volume, path),
                                 err, t0s[i], t1s[i]))
    _metrics.observe_many("mt_drive_call_seconds", _CALL_LABELS,
                          [dt / 1e9 for dt in dts], buckets=KERNEL_BUCKETS)
    return out


def _read_files(wave: list, got: list, t0s: list, t1s: list) -> None:
    """``mt_read_files`` over the files ``got`` names at the positions
    ``wave``: each becomes ``(file, 0 | errno | _READ_TOOBIG, its bytes
    or None)``, its start and end the native read's own."""
    n = len(wave)
    arena, cap = _slot_arena(n)
    lens, s0, s1 = ((ctypes.c_longlong * n)() for _ in range(3))
    errs = (ctypes.c_int * n)()
    _commit._wave_lib().mt_read_files(
        (ctypes.c_char_p * n)(*(os.fsencode(got[i]) for i in wave)), n,
        arena, cap, lens, errs, s0, s1)
    base = ctypes.addressof(arena)
    for j, i in enumerate(wave):
        err = errs[j]
        if err == _READ_TOOBIG:
            _grow_slot(lens[j])
        got[i] = (got[i], err,
                  None if err else ctypes.string_at(base + j * cap, lens[j]))
        t0s[i], t1s[i] = s0[j], s1[j]


def _as_call(disk, fn, *args):
    """``fn(*args)`` as a call of ``disk``: under its breaker
    (``HealthDisk.guarded``), or plainly on a bare ``XLStorage``."""
    guarded = getattr(type(disk), "guarded", None)
    return guarded(disk, fn, *args) if guarded is not None else fn(*args)


def _decode(got, volume: str, path: str, rel: str, version_id) -> FileInfo:
    """The rest of one drive's ``read_version`` after the wave: what
    refused its path before the read, the errno the read met, or its
    file decoded; a file over its slot is read again whole."""
    if isinstance(got, Exception):
        raise got
    full, err, buf = got
    try:
        if err == _READ_TOOBIG:
            buf = _read_file(full, rel)
        elif err:
            raise _read_error(err, full, rel)
    except errors.FileNotFound:
        raise errors.FileNotFound(f"{volume}/{path}") from None
    return XLMeta.load(buf).to_fileinfo(volume, path, version_id)


def _observe_wave(xl: XLStorage, op: str, where: tuple, e, t0: int,
                  t1: int, nbytes: int = 0) -> int:
    """One drive call a wave made, observed as ``_traced_op`` observes
    ``op``: its window and its span (``where``: its volume and path);
    the caller folds the returned ns into ``mt_drive_call_seconds``."""
    dt = t1 - t0
    err = f"{type(e).__name__}: {e}" if e is not None else ""
    xl.latency.record(op, dt, nbytes)
    _publish_call(xl, op, t0 + time.time_ns() - time.monotonic_ns(), dt,
                  err, lambda: where, 0, nbytes)
    return dt


# -- a GET's local shard reads, in one native wave ---------------------------
# A round of a GET's shard read was k pool children: each waited for a
# pool thread and the interpreter to start, then for the interpreter
# again after each of its open / seek / read / close and around its
# verify (PERF.md, get_io_ms).  The local drives' windows are read,
# verified and gathered instead by ONE call on the calling thread that
# never holds the interpreter lock (native/syncwave.c
# mt_read_verify_ranges, checking frames with highwayhash.c's own
# mt_hh256_verify_framed); the error each drive raises and how it is
# observed stay here.

# syncwave.c MT_SHARD_*: res[0] of a window read short, of one whose
# frames did not verify, of one whose frames hold less payload than asked
_SHORT, _BITROT, _TRUNC = -2, -3, -4
_OPENED = 1         # MT_SHARD_OPEN: res[1] of an errno its open met
_SHARD_RES = 6      # MT_SHARD_RES: int64s per item
_SHARD_OPS = ("read_file_stream", "read_segment")


def shard_wave_positions(disks) -> list[int]:
    """Positions of the drives of ``disks`` whose shard windows
    :func:`read_shard_wave` reads: those of :func:`wave_positions`, and
    none where O_DIRECT reads are on (``MT_ODIRECT``: the drive call's
    aligned reader) or the native frame check cannot be loaded."""
    from ..hashing import highwayhash
    if _ODIRECT or highwayhash.verify_framed_address() is None:
        return []
    return wave_positions(disks)


def read_shard_wave(disks, items, framed_len: int, seg_len: int,
                    shard_size: int) -> list[tuple]:
    """One round of a GET's shard read on every drive of ``disks`` (the
    caller picks them with :func:`shard_wave_positions`), from the
    calling thread: each drive's window of ``framed_len`` framed bytes is
    read, every frame's HighwayHash-256S digest checked, and its first
    ``seg_len`` payload bytes gathered, by ONE native call (at most 8
    threads, joined before it returns) that never holds the interpreter
    lock.  ``items``, per drive: ``("read_file_stream", volume, path,
    offset)`` for a part file, ``("read_segment", sid, offset)`` for a
    packed extent.  Per drive, in order: ``(payload | None, error |
    None, start_ns, end_ns)`` on the monotonic clock; the payloads are
    rows of one array made for this call.

    Each drive raises what the pool route raises, type and message: the
    drive call's errors (``read_file_stream``: a missing file
    ``FileNotFound``, a refused one ``FileAccessDenied``, a short one
    ``FileCorrupt("short read ...")``; ``read_segment``: its own), raised
    as the drive's call under its breaker (``HealthDisk.guarded``) where
    that call is guarded; then a frame that does not verify, or a
    payload short of ``seg_len``, ``FileCorrupt`` with the message
    ``bitrot.verify_extract`` gives.  A drive is observed as
    ``_traced_op`` observes its call (its window,
    ``mt_drive_call_seconds{op,kind=local}`` = the native read's own
    time, its ``storage.<op>`` span), and its verify as the ``get.verify``
    span observes it (wall, and thread CPU for one in
    ``trace.CPU_SAMPLE_EVERY``)."""
    from ..hashing import highwayhash
    n = len(disks)
    xls = [wave_target(d) for d in disks]
    files: list = []        # per drive: its file, or what refused it
    t0s, t1s = [0] * n, [0] * n
    for i, (xl, item) in enumerate(zip(xls, items)):
        t0s[i] = time.monotonic_ns()
        try:
            files.append(xl._file_path(item[1], item[2])
                         if item[0] == "read_file_stream"
                         else xl.segments.file(item[1]))
        except Exception as e:  # noqa: BLE001 — raised as the drive's call
            files.append(e)
        t1s[i] = time.monotonic_ns()
    wave = [i for i, f in enumerate(files) if isinstance(f, str)]
    m = len(wave)
    rows = np.empty((m, framed_len), dtype=np.uint8)
    res = np.zeros((m, _SHARD_RES), dtype=np.int64)
    res[:, 5] = [_trace.cpu_sampled("get.verify") for _ in wave]
    if m:
        _commit._wave_lib().mt_read_verify_ranges(
            (ctypes.c_char_p * m)(*(os.fsencode(files[i]) for i in wave)),
            (ctypes.c_longlong * m)(*(items[i][-1] for i in wave)), m,
            framed_len, shard_size, seg_len,
            highwayhash.verify_framed_address(), highwayhash.MAGIC_KEY,
            rows.ctypes.data,
            res.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)))
    wall = time.time_ns() - time.monotonic_ns()
    slot = {i: j for j, i in enumerate(wave)}
    dts: dict = {op: [] for op in _SHARD_OPS}
    out = []
    for i, d in enumerate(disks):
        op, item, j = items[i][0], items[i], slot.get(i)
        if j is None:       # refused before the read
            code, info, t0, t_read, t_end, cpu = 0, 0, t0s[i], t1s[i], \
                t1s[i], -1
            call_err = files[i]
        else:
            code, info, t0, t_read, t_end, cpu = (int(v) for v in res[j])
            call_err = _shard_read_error(code, info, op, files[i], item,
                                         framed_len)
        try:
            if op == "read_file_stream":
                _as_call(d, _raise_any, call_err)
            else:
                _raise_any(call_err)
        except Exception as e:  # noqa: BLE001 — per-drive isolation
            row, err = None, e
        else:
            row, err = _verified(code, info, seg_len, rows[j],
                                 t_read + wall, t_end - t_read, cpu)
        dts[op].append(_observe_wave(
            xls[i], op, item[1:3] if op == "read_file_stream" else ("", ""),
            call_err, t0, t_read, 0 if call_err is not None else framed_len))
        out.append((row, err, t0, t_end))
    for op, got in dts.items():
        if got:
            _metrics.observe_many("mt_drive_call_seconds",
                                  {"op": op, "kind": "local"},
                                  [dt / 1e9 for dt in got],
                                  buckets=KERNEL_BUCKETS)
    return out


def _raise_any(err) -> None:
    if err is not None:
        raise err


def _os_error(err: int, full: str | None) -> OSError:
    """The ``OSError`` subclass Python raises for ``err``, naming
    ``full`` as an ``open`` does (a read names no file)."""
    return OSError(err, os.strerror(err), full) if full \
        else OSError(err, os.strerror(err))


def _shard_read_error(code: int, info: int, op: str, full: str, item,
                      length: int) -> Exception | None:
    """What the drive call ``op`` raises where the native read of its
    window met ``code`` (syncwave.c res[0], res[1] = ``info``); None
    where it read the whole window."""
    if code in (0, _BITROT, _TRUNC):
        return None
    if op == "read_file_stream":        # XLStorage.read_file_stream
        path = item[2]
        if code == _SHORT:
            return errors.FileCorrupt(
                f"short read {info} < {length} at {path}")
        if code == errno.ENOENT:
            return errors.FileNotFound(path)
        if code in (errno.EACCES, errno.EPERM):
            return errors.FileAccessDenied(path)
        # open() refuses a directory itself, naming it
        return _os_error(code, full if info == _OPENED
                         or code == errno.EISDIR else None)
    sid, off = item[1], item[2]         # commit.SegmentStore.read
    if code == _SHORT:
        return errors.FileCorrupt(
            f"segment {sid}: short read {info} < {length} at +{off}")
    if code == errno.ENOENT and info == _OPENED:
        return errors.FileNotFound(f"segment {sid}")
    return _os_error(code, full if info == _OPENED else None)


def _verified(code: int, info: int, seg_len: int, row, start_ns: int,
              dur_ns: int, cpu: int) -> tuple:
    """``(payload, None)`` of a window whose frames verified, or
    ``(None, FileCorrupt)`` with ``bitrot.verify_extract``'s message; the
    verify observed as its ``get.verify`` span."""
    msg = ""
    if code == _BITROT:
        msg = f"content hash mismatch (block {info})"
    elif code == _TRUNC:
        msg = (f"truncated frame: {info} payload bytes present, "
               f"{seg_len} declared")
    _trace.observe_span("read", "get.verify", start_ns, dur_ns,
                        cpu if cpu >= 0 else None,
                        f"BitrotError: {msg}" if msg else "")
    if msg:
        return None, errors.FileCorrupt(msg)
    return row[:seg_len], None

"""Per-drive group commit + packed small-object segments
(storage/commit.py + the packed band in objectlayer/erasure_object.py).

Contracts pinned here:
  * bit-identity — with packing out of reach (object above the pack
    threshold) the grouped commit leaves byte-identical xl.meta + part
    files vs the ungrouped commit;
  * packed round-trip — PUT/GET/range-GET/overwrite/delete through the
    segment indirection, extents freed when versions stop referencing
    them;
  * crash matrix — a commit that dies between the segment append and
    the xl.meta flip leaves NO visible version, only an orphan extent
    the compactor reclaims; a torn journal tail truncates on replay and
    the store keeps working; replay is idempotent across reopens;
  * heal — a packed object heals onto a wiped drive as a packed object
    (re-packed into the target's own segment), bytes intact;
  * isolation — BadDigest aborts ONE stream of a group without
    poisoning batch-mates; a dead drive mid-group still commits at
    quorum;
  * observability — mt_commit_group_* families tick when groups form;
  * flush waves — a round's fsyncs are issued together (file wave, then
    directory wave; one native call each, or the os.* loop that stands
    in for it) and nothing the protocol orders is relaxed: every fsync
    registered before a version's os.replace has RETURNED before it,
    the post-rename directory fsync comes after it, a wave's fsync
    error latches onto its own stream's drive only, and the same fsyncs
    land the same bytes as with grouping off;
  * landing — an op body's part file and xl.meta tmp file each land in
    one call (commit.land_part / land_file): the native form and the
    os.* form leave the same tree, the same ``fresh`` answer, the same
    registered fds and the same errors; a buffer goes down without a
    copy; the fsync is deferred exactly when a collector is armed.
"""

import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from minio_tpu.admin.metrics import GLOBAL as metrics
from minio_tpu.objectlayer import erasure_object as eo
from minio_tpu.objectlayer import healing
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.objectlayer.interface import (ObjectNotFound,
                                             PutObjectOptions,
                                             WriteQuorumError)
from minio_tpu.storage import commit
from minio_tpu.storage import errors as serrors
from minio_tpu.storage import xl_storage
from minio_tpu.storage.datatypes import ErasureInfo, FileInfo
from minio_tpu.storage.writers import close_write_planes
from minio_tpu.storage.xl_storage import XLStorage

from tests.writer_plane import (BS, det_uuids, disk_state, mk_layer,
                                pattern)


@pytest.fixture(autouse=True)
def commit_config():
    """Snapshot/restore the live commit config; pin _loaded so on()
    can't lazily reload env values over a test's knob settings."""
    keys = ("enable", "group_window_s", "max_batch", "pack_threshold",
            "segment_max_bytes", "_loaded")
    saved = {k: getattr(commit.CONFIG, k) for k in keys}
    commit.CONFIG._loaded = True
    commit.CONFIG.enable = True
    yield commit.CONFIG
    for k, v in saved.items():
        setattr(commit.CONFIG, k, v)


def seg_refs(lay, obj):
    """Per-drive seg extents for an object's latest version."""
    refs = []
    for d in lay.disks:
        fi = d.read_version("pbkt", obj)
        refs.append(getattr(fi, "seg", None))
    return refs


# -- bit-identity (regular objects, above the pack band) ---------------------

def test_grouped_commit_bit_identical_for_regular_objects(tmp_path,
                                                          monkeypatch):
    """Group commit only changes WHEN durability happens, never what
    lands: the same 2 MiB PUT with grouping off vs on must leave
    byte-equal xl.meta and part files on every drive."""
    body = os.urandom(2 * (1 << 20))        # above pack_threshold
    states = {}
    for mode, enable in (("eager", False), ("grouped", True)):
        det_uuids(monkeypatch)
        commit.CONFIG.enable = enable
        lay = mk_layer(tmp_path / mode)
        oi = lay.put_object("pbkt", "obj", body,
                            PutObjectOptions(mod_time=1_234_567_890))
        assert oi.etag == hashlib.md5(body).hexdigest()
        states[mode] = disk_state(lay, "obj")
        close_write_planes(lay)
    assert states["eager"] == states["grouped"]
    assert all(meta and parts for meta, parts in states["grouped"].values())


# -- packed round-trip -------------------------------------------------------

@pytest.mark.parametrize("size", [513, 8 * 1024, 100_000, 256 * 1024])
def test_packed_put_get_roundtrip(tmp_path, size):
    """Bodies in (inline_threshold, pack_threshold] commit through the
    segment: every drive's version carries a seg extent and no data
    dir, and GET decodes the original bytes."""
    lay = mk_layer(tmp_path)
    body = pattern(size)
    lay.put_object("pbkt", "obj", body)
    refs = seg_refs(lay, "obj")
    assert all(r is not None and r["len"] > 0 for r in refs), refs
    # packed objects own no per-object shard files
    for d in lay.disks:
        assert not glob.glob(os.path.join(d.root, "pbkt", "obj", "**",
                                          "part.*"), recursive=True)
    _, got = lay.get_object("pbkt", "obj")
    assert got == body
    close_write_planes(lay)


def test_packed_range_get(tmp_path):
    lay = mk_layer(tmp_path)
    body = pattern(3 * BS + 100)
    lay.put_object("pbkt", "obj", body)
    assert seg_refs(lay, "obj")[0] is not None
    for off, ln in [(0, 10), (BS - 5, 10), (BS, BS), (2 * BS + 7, 93),
                    (0, len(body)), (len(body) - 1, 1)]:
        _, got = lay.get_object("pbkt", "obj", offset=off, length=ln)
        assert got == body[off:off + ln], (off, ln)
    close_write_planes(lay)


def test_packed_overwrite_frees_old_extent_and_delete_frees_last(tmp_path):
    """Overwrite must retire the replaced extent (dead bytes grow, old
    offset eventually unreferenced); deleting the last version frees
    its extent too."""
    lay = mk_layer(tmp_path)
    lay.put_object("pbkt", "obj", pattern(64 * 1024))
    first = seg_refs(lay, "obj")
    lay.put_object("pbkt", "obj", pattern(64 * 1024 + 7))
    second = seg_refs(lay, "obj")
    assert all(a != b for a, b in zip(first, second))
    close_write_planes(lay)   # settle deferred frees before inspecting
    _, got = lay.get_object("pbkt", "obj")
    assert got == pattern(64 * 1024 + 7)
    stats = [d.segments.stats() for d in lay.disks]
    assert all(s["dead_bytes"] > 0 for s in stats), stats
    live_before = sum(s["live_bytes"] for s in stats)
    lay.delete_object("pbkt", "obj")
    with pytest.raises(ObjectNotFound):
        lay.get_object("pbkt", "obj")
    live_after = sum(d.segments.stats()["live_bytes"]
                     for d in lay.disks)
    assert live_after < live_before
    close_write_planes(lay)


# -- crash matrix ------------------------------------------------------------

def test_crash_between_extent_and_meta_leaves_no_version(tmp_path,
                                                         monkeypatch):
    """Write-ahead discipline: if the commit dies after the segment
    append but before the xl.meta flip, no version is visible — the
    extent is an orphan, and the compactor's owner check reclaims it
    once the segment seals."""
    lay = mk_layer(tmp_path)
    lay.put_object("pbkt", "keeper", pattern(32 * 1024))

    def boom(*a, **kw):
        raise serrors.FaultyDisk("crash before meta flip")
    monkeypatch.setattr(xl_storage, "_write_file_atomic", boom)
    with pytest.raises(WriteQuorumError):
        lay.put_object("pbkt", "ghost", pattern(32 * 1024))
    monkeypatch.undo()
    close_write_planes(lay)
    with pytest.raises(ObjectNotFound):
        lay.get_object("pbkt", "ghost")

    # seal the open segments (rotation point below the next append),
    # then compact: ghost extents have no owning meta -> freed
    commit.CONFIG.segment_max_bytes = 1
    lay.put_object("pbkt", "sealer", pattern(16 * 1024))
    reclaimed = sum(d.compact_segments(min_dead_ratio=0.0)["freed"]
                    for d in lay.disks)
    assert reclaimed > 0
    # survivors stay intact through the reclaim
    assert lay.get_object("pbkt", "keeper")[1] == pattern(32 * 1024)
    assert lay.get_object("pbkt", "sealer")[1] == pattern(16 * 1024)
    close_write_planes(lay)


def test_torn_journal_tail_truncates_and_recovers(tmp_path):
    """A torn write at the journal tail (crash mid-record) must not
    poison replay: the good prefix loads, the tail is truncated, and
    the store journals new records after it."""
    lay = mk_layer(tmp_path)
    body = pattern(48 * 1024)
    lay.put_object("pbkt", "obj", body)
    close_write_planes(lay)
    roots = [d.root for d in lay.disks]
    del lay
    for root in roots:
        jp = os.path.join(root, ".mt.sys", "seg", "journal")
        with open(jp, "ab") as f:
            f.write(b"\xc1\xff torn half-record \xc1")
    lay2 = ErasureObjects([XLStorage(r) for r in roots], parity=2,
                          block_size=BS, backend="numpy",
                          inline_threshold=512)
    lay2._pipe_depth = 2
    assert lay2.get_object("pbkt", "obj")[1] == body
    lay2.put_object("pbkt", "after", pattern(9000))
    assert lay2.get_object("pbkt", "after")[1] == pattern(9000)
    assert all(d.segments.stats()["live_bytes"] > 0 for d in lay2.disks)
    close_write_planes(lay2)


def test_journal_replay_idempotent_across_reopens(tmp_path):
    lay = mk_layer(tmp_path)
    for i in range(4):
        lay.put_object("pbkt", f"o{i}", pattern(10_000 + i))
    lay.put_object("pbkt", "o0", pattern(11_111))   # one overwrite
    close_write_planes(lay)
    roots = [d.root for d in lay.disks]
    stats0 = [d.segments.stats() for d in lay.disks]
    del lay
    for _ in range(2):                               # reopen twice
        disks = [XLStorage(r) for r in roots]
        lay = ErasureObjects(disks, parity=2, block_size=BS,
                             backend="numpy", inline_threshold=512)
        lay._pipe_depth = 2
        assert lay.get_object("pbkt", "o0")[1] == pattern(11_111)
        assert lay.get_object("pbkt", "o3")[1] == pattern(10_003)
        # replay is lazy: the GETs above forced it; the journal must
        # reduce to the same live/dead map on every reopen
        assert [d.segments.stats() for d in disks] == stats0
        close_write_planes(lay)
        del lay


# -- heal --------------------------------------------------------------------

def test_heal_packed_object_onto_fresh_drive(tmp_path):
    """A wiped drive heals a packed object by RE-PACKING it into its
    own segment (no mixed packed/part state), bytes intact."""
    lay = mk_layer(tmp_path)
    body = pattern(200 * 1024)
    lay.put_object("pbkt", "obj", body)
    close_write_planes(lay)
    victim = lay.disks[2]
    root = victim.root
    shutil.rmtree(root)
    os.makedirs(root)
    lay.disks[2] = XLStorage(root)
    res = healing.heal_object(lay, "pbkt", "obj")
    assert lay.disks[2].endpoint() in res.healed_disks
    fi = lay.disks[2].read_version("pbkt", "obj")
    assert getattr(fi, "seg", None) is not None     # re-packed
    assert lay.disks[2].segments.stats()["live_bytes"] > 0
    assert lay.get_object("pbkt", "obj")[1] == body
    close_write_planes(lay)


# -- group isolation ---------------------------------------------------------

def test_bad_digest_mid_group_spares_batch_mates(tmp_path, monkeypatch):
    """One stream failing its digest aborts THAT stream with no trace;
    concurrent batch-mates in the same group window commit intact."""
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    commit.CONFIG.group_window_s = 0.02      # let groups actually form
    lay = mk_layer(tmp_path)
    bodies = {f"good{i}": pattern(40_000 + i) for i in range(4)}
    errs = {}

    def put(name, body, opts=None):
        try:
            lay.put_object("pbkt", name, body, opts)
        except Exception as e:        # noqa: BLE001 — asserted below
            errs[name] = e
    ts = [threading.Thread(target=put, args=(n, b))
          for n, b in bodies.items()]
    ts.append(threading.Thread(
        target=put, args=("bad", pattern(40_000),
                          PutObjectOptions(content_md5="0" * 32))))
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert set(errs) == {"bad"}
    assert "BadDigest" in str(errs["bad"])
    with pytest.raises(ObjectNotFound):
        lay.get_object_info("pbkt", "bad")
    for d in lay.disks:
        assert not os.path.exists(os.path.join(d.root, "pbkt", "bad",
                                               "xl.meta"))
    for name, body in bodies.items():
        assert lay.get_object("pbkt", name)[1] == body
    close_write_planes(lay)


def test_drive_death_mid_group_commits_at_quorum(tmp_path):
    """A drive failing its packed write latches only that drive; the
    group flush settles the survivors and the PUT acks at quorum."""
    class DeadPackDisk:
        def __init__(self, inner):
            self._inner = inner

        @property
        def root(self):
            return self._inner.root

        def write_packed(self, *a, **kw):
            raise serrors.FaultyDisk("packed write died")

        def __getattr__(self, name):
            return getattr(self._inner, name)

    lay = mk_layer(tmp_path,
                   wrap=lambda i, d: DeadPackDisk(d) if i == 1 else d)
    body = pattern(50_000)
    lay.put_object("pbkt", "obj", body)
    assert lay.get_object("pbkt", "obj")[1] == body
    assert not os.path.exists(os.path.join(lay.disks[1].root, "pbkt",
                                           "obj", "xl.meta"))
    alive = sum(os.path.exists(os.path.join(d.root, "pbkt", "obj",
                                            "xl.meta"))
                for d in lay.disks)
    assert alive == 5
    close_write_planes(lay)


# -- observability -----------------------------------------------------------

def test_group_metrics_tick_when_groups_form(tmp_path):
    commit.CONFIG.group_window_s = 0.02
    lay = mk_layer(tmp_path)
    before = metrics.snapshot()

    def put(i):
        lay.put_object("pbkt", f"m{i}", pattern(30_000 + i))
    ts = [threading.Thread(target=put, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    close_write_planes(lay)
    after = metrics.snapshot()

    def delta(name):
        k = (name, ())
        return after.get(k, 0) - before.get(k, 0)
    assert delta("mt_commit_group_batches_total") > 0
    assert delta("mt_commit_group_streams_total") > \
        delta("mt_commit_group_batches_total")
    assert delta("mt_commit_group_segment_bytes_total") > 0
    assert delta("mt_commit_group_fsyncs_saved_total") > 0


# -- flush waves -------------------------------------------------------------

LAND_FORMS = ("native", "python")


def land_form(monkeypatch, form):
    """Select one form of the writer thread's syscalls (flush waves,
    commit.land_part / land_file): the calls into native/syncwave.c, or
    the os.* sequence that stands in for them without a compiler."""
    if form == "python":
        monkeypatch.setattr(commit, "_wave_lib", lambda: None)
    elif commit._wave_lib() is None:
        pytest.skip("native/syncwave.c did not build here")


@pytest.fixture(params=LAND_FORMS)
def wave_impl(request, monkeypatch):
    """Both forms of a flush wave: the one call into native/syncwave.c,
    and the os.* loop (the only form whose fsyncs a wrapped ``os.fsync``
    can see)."""
    land_form(monkeypatch, request.param)
    return request.param


class SyncLog:
    """``os.fsync`` and ``os.replace`` wrapped to log (and, on demand,
    to fail an fsync): fsync rows are (path, start, end), replace rows
    (src, dst, time); ``on_replace(src, dst)`` runs just before a
    replace."""

    def __init__(self, monkeypatch, fail=None, on_replace=None):
        self.fsyncs: list = []
        self.replaces: list = []
        self._mu = threading.Lock()
        real_fsync, real_replace = os.fsync, os.replace

        def fsync(fd):
            path = os.readlink(f"/proc/self/fd/{fd}")
            t0 = time.monotonic()
            try:
                if fail is not None and fail(path):
                    raise OSError(5, "injected fsync failure", path)
                real_fsync(fd)
            finally:
                with self._mu:
                    self.fsyncs.append((path, t0, time.monotonic()))

        def replace(src, dst, **kw):
            if on_replace is not None:
                on_replace(str(src), str(dst))
            real_replace(src, dst, **kw)
            with self._mu:
                self.replaces.append((str(src), str(dst),
                                      time.monotonic()))
        monkeypatch.setattr(os, "fsync", fsync)
        monkeypatch.setattr(os, "replace", replace)


def gated_puts(lay, puts, timeout=20.0):
    """Run ``puts`` [(name, body, opts)] so that every drive's writer
    thread takes all of them as ONE group-commit batch, in this order:
    a gate op parks each drive's writer, the PUTs start one at a time
    (each once its predecessor sits on every drive queue, so uuid
    minting and queue order are deterministic), then the gate opens.
    Returns {name: exception} for the PUTs that failed."""
    plane = lay._write_plane
    gate = threading.Event()
    parked = [threading.Event() for _ in lay.disks]

    def hold(idx, disk):
        parked[idx].set()
        gate.wait(timeout)
    sw = plane.stream(lay.disks)
    for i in range(len(lay.disks)):
        sw.submit(i, hold)
    assert all(ev.wait(timeout) for ev in parked)
    errs: dict = {}

    def put(name, body, opts):
        try:
            lay.put_object("pbkt", name, body, opts)
        except Exception as e:        # noqa: BLE001 — asserted by caller
            errs[name] = e
    threads = []
    try:
        for j, (name, body, opts) in enumerate(puts):
            t = threading.Thread(target=put, args=(name, body, opts))
            t.start()
            threads.append(t)
            end = time.monotonic() + timeout
            while any(st["queue_depth"] < j + 1
                      for st in plane.stats().values()):
                assert time.monotonic() < end and t.is_alive(), \
                    (name, errs, plane.stats())
                time.sleep(0.002)
    finally:
        gate.set()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive()
    assert sw.drain(timeout)
    return errs


def hist(name):
    """(sum, count) of an unlabelled mt_commit_*_seconds histogram."""
    for (fam, _labels, _b), h in metrics.hist_snapshot().items():
        if fam == name:
            return h[-1], h[-2]
    return 0.0, 0


def counter(name):
    return metrics.snapshot().get((name, ()), 0)


def test_sync_waves_fsync_and_close_every_item(tmp_path, wave_impl):
    """The wave helper itself: every fd is fsynced and CLOSED when the
    call returns, an fd that cannot be fsynced reports its errno at its
    own index, a directory that is not there is tolerated."""
    fds = [os.open(tmp_path / f"f{i}", os.O_CREAT | os.O_WRONLY)
           for i in range(20)]
    r, w = os.pipe()                  # fsync(pipe) -> EINVAL
    try:
        inodes = [os.fstat(fd).st_ino for fd in fds]
        errs = commit.sync_files(fds[:7] + [os.dup(w)] + fds[7:])
        assert errs[7] != 0 and not any(errs[:7] + errs[8:]), errs
        for fd, ino in zip(fds, inodes):
            try:
                assert os.fstat(fd).st_ino != ino     # number reused
            except OSError:
                pass                                  # closed
        commit.sync_dirs([str(tmp_path), str(tmp_path / "gone")])
        assert commit.sync_files([]) == []
    finally:
        os.close(r)
        os.close(w)


def test_flush_issues_a_rounds_fsyncs_in_waves(tmp_path, monkeypatch,
                                               wave_impl):
    """A batch of N regular PUTs flushes in three waves per drive — 2N
    file fds; 2N + 1 directories (the bucket dir once); the N object
    dirs again behind the renames — and the counters behind
    commit_flush_width / commit_fsyncs_per_put / commit_*_ms /
    commit_body_calls_per_op tick."""
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    commit.CONFIG.pack_threshold = 0          # regular objects only
    lay = mk_layer(tmp_path)
    n = 6
    log = SyncLog(monkeypatch)
    f0, w0 = counter("mt_commit_fsyncs_total"), \
        counter("mt_commit_flush_waves_total")
    c0 = counter("mt_commit_body_calls_total")
    flush0, body0, queue0 = (hist(f"mt_commit_{s}_seconds")
                             for s in ("flush", "body", "queue"))
    assert not gated_puts(lay, [(f"r{i}", pattern(20_000 + i), None)
                                for i in range(n)])
    flush1, body1, queue1 = (hist(f"mt_commit_{s}_seconds")
                             for s in ("flush", "body", "queue"))
    issued = counter("mt_commit_fsyncs_total") - f0
    waves = counter("mt_commit_flush_waves_total") - w0
    assert issued == len(lay.disks) * (5 * n + 1)
    assert waves == len(lay.disks) * 3
    assert issued / waves > 2                 # commit_flush_width
    # commit_body_calls_per_op: a body lands its part and its xl.meta
    # tmp file in one call each, or in ten os.* calls (the gate op's
    # body makes none)
    assert counter("mt_commit_body_calls_total") - c0 \
        == len(lay.disks) * n * (2 if wave_impl == "native" else 10)
    # the os.* loop issues them where the wrapper sees them; the native
    # wave issues none through os.fsync
    assert len(log.fsyncs) == (issued if wave_impl == "python" else 0)
    # the gate op is a batch and a drive op too: 2 flushes, n + 1 ops
    assert flush1[1] - flush0[1] == 2 * len(lay.disks)
    assert body1[1] - body0[1] == queue1[1] - queue0[1] \
        == len(lay.disks) * (n + 1)
    assert queue1[0] > queue0[0] and body1[0] > body0[0] \
        and flush1[0] > flush0[0]
    for i in range(n):
        assert lay.get_object("pbkt", f"r{i}")[1] == pattern(20_000 + i)
    close_write_planes(lay)


MIXED_PUTS = [("reg0", (1 << 20) + 4097), ("pack0", 50_000), ("inl0", 300),
              ("reg1", (1 << 20) + 5), ("pack1", 9_000), ("inl1", 17)]


def test_every_fsync_returns_before_its_replace(tmp_path, monkeypatch):
    """Regular, packed and inline ops in ONE batch, the os.* form of
    the waves (the one a wrapped os.fsync sees): for every version, on
    every drive, the fsyncs of its part file (or the segment and its
    journal), of its xl.meta tmp file, of its data dir and of its
    object dir have all RETURNED before its os.replace runs, and the
    object dir is fsynced again after it (the next round)."""
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    monkeypatch.setattr(commit, "_wave_lib", lambda: None)
    lay = mk_layer(tmp_path)
    log = SyncLog(monkeypatch)
    puts = [(name, pattern(size), None) for name, size in MIXED_PUTS]
    assert not gated_puts(lay, puts)
    flips = [(src, dst, t) for src, dst, t in log.replaces
             if dst.endswith("/xl.meta") and "/pbkt/" in dst]
    assert len(flips) == len(puts) * len(lay.disks)
    for src, dst, t in flips:
        obj_dir = os.path.dirname(dst)
        root = obj_dir[:obj_dir.index("/pbkt/")]
        name = os.path.basename(obj_dir)

        def landed(path):
            return [e for p, _s, e in log.fsyncs if p == path and e <= t]
        assert landed(src), (name, "xl.meta tmp file")
        assert landed(obj_dir), (name, "object dir before the flip")
        if name.startswith("reg"):
            (ddir,) = [d for d in glob.glob(obj_dir + "/*")
                       if os.path.isdir(d)]
            assert landed(ddir + "/part.1"), (name, "part file")
            assert landed(ddir), (name, "data dir")
        if name.startswith("pack"):
            seg = os.path.join(root, ".mt.sys", "seg")
            assert landed(seg + "/journal"), (name, "segment journal")
            assert any(landed(f) for f in glob.glob(seg + "/seg.*.dat")), \
                (name, "segment file")
        if not name.startswith("inl"):       # fresh object: bucket dir
            assert landed(os.path.dirname(obj_dir)), (name, "bucket dir")
        # ... and the rename's own directory entry persisted after it
        assert [s for p, s, _e in log.fsyncs
                if p == obj_dir and s >= t], (name, "round-2 dir fsync")
    for name, body, _ in puts:
        assert lay.get_object("pbkt", name)[1] == body
    close_write_planes(lay)


def test_every_fd_is_synced_and_closed_before_its_replace(tmp_path,
                                                          monkeypatch):
    """The same batch through the native waves, whose fsyncs no wrapper
    sees: a wave closes an fd only after its fsync returned, so at every
    version's os.replace each fd the drive's batch registered before it
    — part files, segment, journal, xl.meta tmp files — must already be
    closed; the replace's own tmp file among them."""
    if commit._wave_lib() is None:
        pytest.skip("native/syncwave.c did not build here")
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    lay = mk_layer(tmp_path)
    registered: dict = {}           # drive root -> [(fd, ino, path)]
    mu = threading.Lock()
    real_defer = commit.GroupCollector.defer_fd

    def defer_fd(self, fd, storage=None, key=None):
        path = os.readlink(f"/proc/self/fd/{fd}")
        with mu:
            registered.setdefault(storage.root, []).append(
                (fd, os.fstat(fd).st_ino, path))
        real_defer(self, fd, storage=storage, key=key)
    monkeypatch.setattr(commit.GroupCollector, "defer_fd", defer_fd)
    checked = []

    def on_replace(src, dst):
        if not dst.endswith("/xl.meta") or "/pbkt/" not in dst:
            return
        root = dst[:dst.index("/pbkt/")]
        with mu:
            mine = list(registered.get(root, []))
        assert any(path == src for _fd, _ino, path in mine), src
        for fd, ino, path in mine:
            try:
                still_open = os.fstat(fd).st_ino == ino
            except OSError:
                still_open = False
            assert not still_open, (path, "open across", dst)
        checked.append(dst)
    log = SyncLog(monkeypatch, on_replace=on_replace)
    puts = [(name, pattern(size), None) for name, size in MIXED_PUTS]
    assert not gated_puts(lay, puts)
    assert len(checked) == len(puts) * len(lay.disks)
    assert not log.fsyncs
    for name, body, _ in puts:
        assert lay.get_object("pbkt", name)[1] == body
    close_write_planes(lay)


def test_wave_fsync_error_latches_its_own_stream_only(tmp_path,
                                                      monkeypatch,
                                                      wave_impl):
    """A failing fsync of one fd inside a wave latches FaultyDisk onto
    the stream that registered the fd, on that drive only: the victim
    commits at quorum, batch-mates see no error."""
    from minio_tpu.storage.writers import StreamWriter
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    commit.CONFIG.pack_threshold = 0
    lay = mk_layer(tmp_path)
    bad_root = lay.disks[1].root

    def is_victim(path):
        return path.startswith(bad_root + "/pbkt/victim/") \
            and path.endswith("/part.1")
    SyncLog(monkeypatch, fail=is_victim)      # the os.* form fails here
    r, w = os.pipe()
    real_defer = commit.GroupCollector.defer_fd

    def defer_fd(self, fd, storage=None, key=None):
        # the native form: hand it an fd whose fsync fails (EINVAL)
        if wave_impl == "native" and \
                is_victim(os.readlink(f"/proc/self/fd/{fd}")):
            os.close(fd)
            fd = os.dup(w)
        real_defer(self, fd, storage=storage, key=key)
    monkeypatch.setattr(commit.GroupCollector, "defer_fd", defer_fd)
    latched = []
    real_latch = StreamWriter._latch_err

    def spy(self, idx, err):
        latched.append((self.disks[idx].root, type(err).__name__))
        real_latch(self, idx, err)
    monkeypatch.setattr(StreamWriter, "_latch_err", spy)
    puts = [("mate0", pattern(20_000), None),
            ("victim", pattern(21_000), None),
            ("mate1", pattern(22_000), None),
            ("mate2", pattern(23_000), None)]
    try:
        assert not gated_puts(lay, puts)
    finally:
        os.close(r)
        os.close(w)
    assert latched == [(bad_root, "FaultyDisk")]
    for name, body, _ in puts:
        assert lay.get_object("pbkt", name)[1] == body
    close_write_planes(lay)


def test_batched_flush_same_bytes_and_same_fsyncs_as_eager(tmp_path,
                                                           monkeypatch,
                                                           wave_impl):
    """One batch of regular PUTs leaves xl.meta and shard files
    bit-identical to ``commit.enable=off`` (packing, which only exists
    with grouping on, out of reach), and the fsync syscalls it issued
    are exactly those its op bodies deferred minus the ones
    deduplication saved (the bucket dir the batch-mates share).  What
    the bodies defer is the eager path's five fsyncs per drive op plus
    one: the object dir is registered before the flip (the data dir's
    entry) and again behind it (the rename's)."""
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    commit.CONFIG.pack_threshold = 0
    deferred = []
    for meth in ("defer_fd", "defer_dir"):
        real = getattr(commit.GroupCollector, meth)

        def spy(self, *a, _real=real, **kw):
            deferred.append(1)
            return _real(self, *a, **kw)
        monkeypatch.setattr(commit.GroupCollector, meth, spy)
    puts = [(f"o{i}", pattern(size), PutObjectOptions(
        mod_time=1_234_567_890 + i))
        for i, size in enumerate([(1 << 20) + 3, 70_000,
                                  (1 << 20) + 4096, 30_000])]
    states, calls, grouped = {}, {}, {}
    for mode, enable in (("eager", False), ("grouped", True)):
        det_uuids(monkeypatch)
        commit.CONFIG.enable = enable
        lay = mk_layer(tmp_path / mode)
        log = SyncLog(monkeypatch)
        before = {k: counter(k) for k in (
            "mt_commit_fsyncs_total",
            "mt_commit_group_fsyncs_saved_total")}
        with monkeypatch.context() as mp:
            if mode == "eager":
                # the reference count: an eager body's own fsyncs are
                # seen only where os.fsync makes them (commit.land_*)
                mp.setattr(commit, "_wave_lib", lambda: None)
            assert not gated_puts(lay, puts)
        grouped[mode] = {k: counter(k) - v for k, v in before.items()}
        calls[mode] = len(log.fsyncs)
        states[mode] = {name: disk_state(lay, name)
                        for name, _, _ in puts}
        for name, body, _ in puts:
            assert lay.get_object("pbkt", name)[1] == body
        close_write_planes(lay)
    assert states["eager"] == states["grouped"]
    assert all(meta and parts for st in states["grouped"].values()
               for meta, parts in st.values())
    assert grouped["eager"]["mt_commit_fsyncs_total"] == 0
    issued = grouped["grouped"]["mt_commit_fsyncs_total"]
    saved = grouped["grouped"]["mt_commit_group_fsyncs_saved_total"]
    assert saved > 0
    assert issued == len(deferred) - saved
    assert len(deferred) == calls["eager"] + len(puts) * len(lay.disks)
    assert calls["grouped"] == (issued if wave_impl == "python" else 0)


# -- compaction --------------------------------------------------------------

def test_compaction_rewrites_live_extents(tmp_path):
    """Sealed mostly-dead segments compact: live extents move to fresh
    extents (owner metas flip), dead space is reclaimed, every object
    still reads back."""
    commit.CONFIG.segment_max_bytes = 1      # seal on every rotation
    lay = mk_layer(tmp_path)
    bodies = {}
    for i in range(6):
        bodies[f"c{i}"] = pattern(20_000 + 13 * i)
        lay.put_object("pbkt", f"c{i}", bodies[f"c{i}"])
    for i in range(0, 6, 2):                 # kill half -> dead extents
        lay.delete_object("pbkt", f"c{i}")
        bodies.pop(f"c{i}")
    close_write_planes(lay)
    moved = sum(d.compact_segments(min_dead_ratio=0.0)["moved"]
                for d in lay.disks)
    assert moved > 0
    for name, body in bodies.items():
        assert lay.get_object("pbkt", name)[1] == body
    # compaction must not strand packed objects off the segment plane
    assert all(r is not None for r in seg_refs(lay, "c1"))
    close_write_planes(lay)


# -- landing an op body's files ----------------------------------------------

def land_fi(vid="", ddir="dd"):
    return FileInfo(volume="bkt", name="o", version_id=vid, data_dir=ddir,
                    mod_time=1_234_567_890, size=8,
                    erasure=ErasureInfo(data_blocks=2, parity_blocks=1,
                                        block_size=1024, index=1,
                                        distribution=[1, 2, 3]))


def tree(root):
    """{path under root: bytes, or None for a directory}; an xl.meta
    tmp file under its name without the pid and counter."""
    out = {}
    for base, dirs, files in os.walk(root):
        rel = os.path.relpath(base, root)
        if rel.startswith(".mt.sys"):
            continue
        out[rel] = None
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                out[os.path.join(rel, f.split(".tmp.")[0]
                                 + (".tmp" if ".tmp." in f else ""))] \
                    = fh.read()
    return out


def open_fds():
    return len(os.listdir("/proc/self/fd"))


def one_commit(root, scene, form, monkeypatch):
    """One write_data_commit on a new drive, a collector armed, through
    one form of the landing; returns everything it left behind."""
    root.mkdir()
    d = XLStorage(str(root))
    d.make_vol("bkt")
    vol = os.path.join(d.root, "bkt")
    name, fi = "o", land_fi()
    if scene == "existing":
        d.write_data_commit("bkt", "o", land_fi(vid="v0", ddir="d0"),
                            b"old bytes", shard_index=1)
        fi = land_fi(vid="v1")
    elif scene == "nested":
        name = "a/b/o"
    elif scene == "volume_gone":
        d.stat_vol("bkt")                # the volume is remembered
        shutil.rmtree(vol)
    elif scene == "ddir_exists":
        os.makedirs(os.path.join(vol, "o", "dd"))
    elif scene == "unwritable":
        if os.geteuid() == 0:            # root writes anywhere: a file
            open(os.path.join(vol, "o"), "wb").close()   # in the way
        else:
            os.makedirs(os.path.join(vol, "o"))
            os.chmod(os.path.join(vol, "o"), 0o555)
    out = {"exc": None}
    n0 = open_fds()
    with monkeypatch.context() as mp:
        land_form(mp, form)
        fsyncs = []
        real_fsync = os.fsync
        mp.setattr(os, "fsync", lambda fd: (fsyncs.append(fd),
                                            real_fsync(fd)))
        col = commit.GroupCollector()
        commit.arm(col)
        try:
            d.write_data_commit("bkt", name, fi, pattern(5000),
                                shard_index=1)
        except Exception as e:           # noqa: BLE001 — compared below
            out["exc"] = (type(e).__name__, getattr(e, "errno", None),
                          os.path.relpath(e.filename, d.root)
                          if getattr(e, "filename", None) else None)
        finally:
            out["calls"] = col.body_calls
            out["body_fsyncs"] = len(fsyncs)
            out["fds"] = sorted(
                os.path.relpath(os.readlink(f"/proc/self/fd/{r[0]}"),
                                d.root).split(".tmp.")[0]
                for r in col._fds)
            out["dirs"] = sorted(os.path.relpath(p, d.root)
                                 for p in col._dirs)
            out["before_flush"] = tree(d.root)
            col.flush()
            commit.disarm()
        out["after_flush"] = tree(d.root)
    out["leaked_fds"] = open_fds() - n0
    if scene == "unwritable" and os.geteuid() != 0:
        os.chmod(os.path.join(vol, "o"), 0o755)
    return out


@pytest.mark.parametrize("scene", ["fresh", "existing", "nested",
                                   "volume_gone", "ddir_exists",
                                   "unwritable"])
def test_landing_native_and_os_forms_leave_the_same(tmp_path, monkeypatch,
                                                    scene):
    """write_data_commit's one-shot branch through commit.land_part +
    land_file: the native calls and the os.* sequence leave
    byte-identical trees (before the flush, where the version is not
    visible yet, and after it), the same ``fresh`` answer (the bucket
    dir is registered for a fresh object only), the same registered
    fds, the same error with the same errno and path, and no open
    descriptor; only the number of blocking calls differs."""
    got = {form: one_commit(tmp_path / form, scene, form, monkeypatch)
           for form in LAND_FORMS}
    nat, py = got["native"], got["python"]
    calls = {form: g.pop("calls") for form, g in got.items()}
    assert nat == py
    assert nat["leaked_fds"] == 0 and nat["body_fsyncs"] == 0
    ok = scene in ("fresh", "existing", "nested")
    obj = "bkt/a/b/o" if scene == "nested" else "bkt/o"
    if ok:
        assert nat["exc"] is None
        assert nat["fds"] == [f"{obj}/dd/part.1", f"{obj}/xl.meta"]
        assert (os.path.dirname(obj) in nat["dirs"]) \
            == (scene != "existing")
        assert f"{obj}/xl.meta" not in nat["before_flush"] \
            or scene == "existing"
        assert nat["before_flush"][f"{obj}/xl.meta.tmp"] \
            == nat["after_flush"][f"{obj}/xl.meta"]
        assert nat["after_flush"][f"{obj}/dd/part.1"] == pattern(5000)
        assert f"{obj}/xl.meta.tmp" not in nat["after_flush"]
        # one call per landing against the os.* sequence's 2 mkdirs and
        # 4 calls per file (nested: + the landing that found no parent)
        assert calls["native"] == (3 if scene == "nested" else 2)
        assert calls["python"] == (12 if scene == "nested" else 10)
    if scene == "existing":
        assert nat["after_flush"]["bkt/o/d0/part.1"] == b"old bytes"
    if scene == "volume_gone":
        assert nat["exc"][0] == "VolumeNotFound"
        assert "bkt" not in nat["after_flush"]      # not resurrected
    if scene == "ddir_exists":
        assert nat["exc"] == ("FileExistsError", 17, "bkt/o/dd")
        assert nat["fds"] == []
    if scene == "unwritable":
        assert nat["exc"][0] == ("NotADirectoryError"
                                 if os.geteuid() == 0
                                 else "PermissionError")
        assert nat["exc"][2] == "bkt/o/dd" and nat["fds"] == []


def land_buffers(n):
    body = pattern(n)
    rows = np.frombuffer(body * 2, dtype=np.uint8).reshape(2, n)
    return {"bytes": body, "bytearray": bytearray(body),
            "memoryview": memoryview(body),
            "numpy_row": rows[1]}


@pytest.mark.parametrize("form", LAND_FORMS)
@pytest.mark.parametrize("kind", sorted(land_buffers(16)))
def test_landing_takes_the_callers_buffer_without_a_copy(tmp_path,
                                                         monkeypatch,
                                                         form, kind):
    """bytes, bytearray, memoryview (read-only) and a NumPy uint8 row
    all go down by address: landing 4 MiB allocates nothing of that
    size (a 5 MiB ``bytes(...)`` under the GIL would give back what the
    one call saves)."""
    land_form(monkeypatch, form)
    n = 4 << 20
    buf = land_buffers(n)[kind]
    obj = str(tmp_path / "o")
    tracemalloc.start()
    try:
        fresh = commit.land_part(obj, obj + "/dd", obj + "/dd/part.1", buf)
        commit.land_file(obj + "/xl.meta.tmp", buf)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert fresh is True
    assert peak < n // 16, peak
    for path in (obj + "/dd/part.1", obj + "/xl.meta.tmp"):
        with open(path, "rb") as f:
            assert f.read() == pattern(n)
    # a strided view is gathered first and lands the same bytes
    commit.land_file(obj + "/strided", land_buffers(64)["numpy_row"][::2])
    with open(obj + "/strided", "rb") as f:
        assert f.read() == pattern(64)[::2]


@pytest.mark.parametrize("form", LAND_FORMS)
@pytest.mark.parametrize("armed", [True, False],
                         ids=["collector_armed", "no_collector"])
def test_landing_defers_the_fsync_exactly_when_a_collector_is_armed(
        tmp_path, monkeypatch, form, armed):
    """/dev/null takes a write and refuses an fsync (EINVAL), whoever
    issues it.  Armed: no fsync in the body — the landing succeeds and
    ONE dup'd fd of the file is registered.  Not armed: the fsync runs
    inside the call, before it returns — the landing raises its EINVAL,
    and leaves no descriptor open."""
    land_form(monkeypatch, form)
    col = commit.GroupCollector()
    if armed:
        commit.arm(col)
    n0 = open_fds()
    try:
        if armed:
            commit.land_file("/dev/null", b"x")
            assert len(col._fds) == 1 and open_fds() == n0 + 1
            assert os.readlink(f"/proc/self/fd/{col._fds[0][0]}") \
                == "/dev/null"
            # ... and a regular file's registered fd is the file itself
            commit.land_file(str(tmp_path / "f"), b"abc")
            fd = col._fds[1][0]
            assert os.fstat(fd).st_ino == os.stat(tmp_path / "f").st_ino
            assert os.get_inheritable(fd) is False
        else:
            with pytest.raises(OSError) as e:
                commit.land_file("/dev/null", b"x")
            assert e.value.errno == 22
            assert not col._fds
            commit.land_file(str(tmp_path / "f"), b"abc")    # fsync ok
    finally:
        commit.disarm()
        for rec in col._fds:
            os.close(rec[0])
    assert open_fds() == n0
    with open(tmp_path / "f", "rb") as f:
        assert f.read() == b"abc"


@pytest.mark.parametrize("form", LAND_FORMS)
def test_landed_version_flips_only_after_the_wave_that_covers_both_fds(
        tmp_path, monkeypatch, form):
    """Kill ordering, one op: between the body and the flush a crash
    finds the part file and the xl.meta tmp file but NO xl.meta; the
    os.replace runs only once the file wave has fsynced and closed both
    registered fds (the part's and the tmp file's)."""
    land_form(monkeypatch, form)
    (tmp_path / "d").mkdir()
    d = XLStorage(str(tmp_path / "d"))
    d.make_vol("bkt")
    col = commit.GroupCollector()
    commit.arm(col)
    seen = []
    real_replace = os.replace

    def replace(src, dst, **kw):
        for fd, ino in inodes:
            try:
                assert os.fstat(fd).st_ino != ino, "open across the flip"
            except OSError:
                pass                                  # closed
        seen.append((os.path.basename(src).split(".tmp.")[0],
                     os.path.basename(dst)))
        real_replace(src, dst, **kw)
    monkeypatch.setattr(os, "replace", replace)
    try:
        d.write_data_commit("bkt", "o", land_fi(), pattern(3000),
                            shard_index=1)
        inodes = [(r[0], os.fstat(r[0]).st_ino) for r in col._fds]
        assert len(inodes) == 2
        before = tree(d.root)
        assert "bkt/o/xl.meta" not in before and not seen
        assert before["bkt/o/dd/part.1"] == pattern(3000)
        col.flush()
    finally:
        commit.disarm()
    assert seen == [("xl.meta", "xl.meta")]
    assert d.read_version("bkt", "o", "").data_dir == "dd"


FSYNC_SHIM = """
#define _GNU_SOURCE
#include <dlfcn.h>
static int count;
int mt_shim_fsyncs(void) { return __atomic_load_n(&count, __ATOMIC_SEQ_CST); }
int fsync(int fd) {
    static int (*real)(int);
    if (!real) real = (int (*)(int))dlsym(RTLD_NEXT, "fsync");
    __atomic_add_fetch(&count, 1, __ATOMIC_SEQ_CST);
    return real(fd);
}
"""

EAGER_COUNT = """
import ctypes, json, os, sys
from minio_tpu.storage import commit
from minio_tpu.storage.xl_storage import XLStorage
from tests.test_commit_plane import land_fi, pattern, tree
shim = ctypes.CDLL(sys.argv[1])
out = {}
for form in ("native", "python"):
    if form == "python":
        commit._wave_lib = lambda: None
    elif commit._wave_lib() is None:
        continue
    root = os.path.join(sys.argv[2], form)
    os.mkdir(root)
    d = XLStorage(root)
    d.make_vol("bkt")
    counts = []
    for vid, ddir in (("v0", "d0"), ("v1", "d1")):   # fresh, then not
        n0 = shim.mt_shim_fsyncs()
        d.write_data_commit("bkt", "o", land_fi(vid=vid, ddir=ddir),
                            pattern(70_000), shard_index=1)
        counts.append(shim.mt_shim_fsyncs() - n0)
    n0 = shim.mt_shim_fsyncs()
    d.write_all("bkt", "doc", b"a document")
    counts.append(shim.mt_shim_fsyncs() - n0)
    out[form] = [counts, sorted(tree(root))]
print(json.dumps(out))
"""


def test_eager_landing_issues_the_os_forms_fsyncs(tmp_path):
    """No collector armed (grouping off, a peer's RPC ops): the native
    landing makes its fsyncs inside the C call, where no wrapped
    ``os.fsync`` sees them.  A preloaded ``fsync`` that counts sees
    both forms: the same number per op — the part file, its data dir,
    the xl.meta tmp file, the object dir, and the bucket dir for a
    fresh object — and the same tree."""
    (tmp_path / "shim.c").write_text(FSYNC_SHIM)
    so = str(tmp_path / "shim.so")
    try:
        subprocess.run(["cc", "-shared", "-fPIC", "-O1", "-o", so,
                        str(tmp_path / "shim.c"), "-ldl"], check=True,
                       capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError):
        pytest.skip("no C compiler for the fsync-counting shim")
    res = subprocess.run(
        [sys.executable, "-c", EAGER_COUNT, so, str(tmp_path)],
        env={**os.environ, "LD_PRELOAD": so, "JAX_PLATFORMS": "cpu",
             "MT_FSYNC": "1"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    if "native" not in got:
        pytest.skip("native/syncwave.c did not build here")
    assert got["native"] == got["python"]
    assert got["native"][0] == [5, 4, 2]


# -- a body that reaches its gate before the digest ---------------------------

class Gate:
    """An overlapped PUT's meta gate, opened by hand; ``vd`` None is a
    failed digest."""

    def __init__(self, vd, opened=False):
        self.vd, self.calls, self.ev = vd, 0, threading.Event()
        if opened:
            self.ev.set()

    def ready(self):
        return self.ev.is_set()

    def open_in(self, seconds):
        threading.Timer(seconds, self.ev.set).start()

    def __call__(self):
        self.calls += 1
        assert self.ev.wait(20)
        if self.vd is None:
            raise serrors.StorageError("commit aborted (BadDigest)")
        return self.vd


class StubOp:
    """What a collector needs of a writer-plane op."""

    def __init__(self):
        self.idx, self.stream, self.errs, self.bound = 0, self, [], 0

    def _latch_err(self, idx, err):
        self.errs.append(err)

    def bind(self):
        self.bound += 1


def gated_commit(d, col, name, vid, gate_open, data=None, vd=True):
    """One write_data_commit of ``name`` under ``col`` as an op of its
    own; returns (gate, op)."""
    fi = land_fi(vid=vid, ddir="d-" + vid)
    gate = Gate(fi.to_dict() if vd else None, opened=gate_open)
    op = col.current_op = StubOp()
    d.write_data_commit("bkt", name, fi,
                        pattern(3000) if data is None else data,
                        shard_index=1, meta_gate=gate)
    return gate, op


def metas(d):
    """xl.meta files and tmp files on the drive, by object."""
    return sorted(k for k in tree(d.root) if "xl.meta" in k)


@pytest.fixture
def armed_drive(tmp_path):
    d = XLStorage(str(tmp_path))
    d.make_vol("bkt")
    col = commit.GroupCollector()
    commit.arm(col)
    flips = []
    real_replace = os.replace

    def replace(src, dst, **kw):
        flips.append(os.path.relpath(dst, d.root))
        real_replace(src, dst, **kw)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(os, "replace", replace)
        try:
            yield d, col, flips
        finally:
            commit.disarm()


@pytest.mark.parametrize("form", LAND_FORMS)
def test_body_yields_at_a_gate_that_is_not_open(armed_drive, monkeypatch,
                                                form):
    """The digest is not there when the part has landed: the body
    returns with its merge handed to the collector (the gate is not
    even called: nothing parks), a batch-mate whose own gate IS open
    queues behind it all the same, and the merges run in op order."""
    land_form(monkeypatch, form)
    d, col, flips = armed_drive
    ga, opa = gated_commit(d, col, "a", "va", gate_open=False)
    assert ga.calls == 0 and metas(d) == []
    assert tree(d.root)["bkt/a/d-va/part.1"] == pattern(3000)
    assert col.tails()
    gb, opb = gated_commit(d, col, "b", "vb", gate_open=True)
    assert gb.calls == 0 and metas(d) == []
    ga.ev.set()
    col.run_tails()
    assert (ga.calls, gb.calls) == (1, 1) and not col.tails()
    assert metas(d) == ["bkt/a/xl.meta.tmp", "bkt/b/xl.meta.tmp"]
    # each as its op; b, the op that was running, is restored after
    assert (opa.bound, opb.bound) == (1, 2) and col.current_op is opb
    assert set(col.tail_s) == {opa, opb} and not opa.errs + opb.errs
    assert len(col._fds) == 4 and not flips
    col.flush()
    assert flips == ["bkt/a/xl.meta", "bkt/b/xl.meta"]
    assert d.read_version("bkt", "a", "va").data_dir == "d-va"
    assert d.read_version("bkt", "b", "vb").data_dir == "d-vb"


def test_open_gate_and_nothing_owed_merges_in_the_body(armed_drive):
    d, col, _ = armed_drive
    g, _ = gated_commit(d, col, "a", "va", gate_open=True)
    assert g.calls == 1 and not col.tails()
    assert metas(d) == ["bkt/a/xl.meta.tmp"]


def test_two_versions_of_one_object_merge_in_op_order(armed_drive):
    """The hazard the queueing rule is for: v1 (fresh, gate shut) and v2
    (not fresh, gate open) of ONE object in one batch.  v2 merging first
    would be overwritten by v1, which reads no xl.meta."""
    d, col, flips = armed_drive
    g1, _ = gated_commit(d, col, "o", "v1", gate_open=False)
    gated_commit(d, col, "o", "v2", gate_open=True)
    g1.ev.set()
    col.flush()                  # a flush runs what is owed first
    assert flips == ["bkt/o/xl.meta", "bkt/o/xl.meta"]
    assert sorted(v.version_id for v in d.list_versions("bkt", "o")) \
        == ["v1", "v2"]


@pytest.mark.parametrize("later", ["read_version", "ungated_commit",
                                   "write_packed", "write_metadata"])
def test_a_later_op_that_cannot_queue_runs_what_is_owed_first(armed_drive,
                                                              later):
    """Per-drive FIFO: any other drive op of the batch (and a
    write_data_commit without a gate to ask) starts only once the
    earlier ops' second halves have run."""
    d, col, flips = armed_drive
    g, _ = gated_commit(d, col, "a", "va", gate_open=False)
    g.open_in(0.05)
    col.current_op = StubOp()
    if later == "read_version":
        assert d.read_version("bkt", "a", "va").data_dir == "d-va"
    elif later == "ungated_commit":
        d.write_data_commit("bkt", "c", land_fi(vid="vc", ddir="d-vc"),
                            pattern(100), shard_index=1)
        assert metas(d) == ["bkt/a/xl.meta.tmp", "bkt/c/xl.meta.tmp"]
    elif later == "write_packed":
        d.write_packed("bkt", "p", land_fi(vid="vp", ddir=""),
                       pattern(100), shard_index=1)
    else:
        d.write_metadata("bkt", "a", land_fi(vid="vm", ddir=""))
    assert g.calls == 1 and not col.tails()
    col.flush()
    assert flips[0] == "bkt/a/xl.meta"
    assert d.read_version("bkt", "a", "va").data_dir == "d-va"
    if later == "write_metadata":        # merged into, not written over
        assert sorted(v.version_id for v in d.list_versions("bkt", "a")) \
            == ["va", "vm"]


def test_a_failed_digest_latches_on_its_own_op_only(armed_drive):
    d, col, flips = armed_drive
    ga, opa = gated_commit(d, col, "a", "va", gate_open=False, vd=False)
    gb, opb = gated_commit(d, col, "b", "vb", gate_open=False)
    ga.ev.set()
    gb.ev.set()
    col.run_tails()
    assert [type(e).__name__ for e in opa.errs] == ["StorageError"]
    assert not opb.errs
    col.flush()
    assert flips == ["bkt/b/xl.meta"]
    assert metas(d) == ["bkt/b/xl.meta"]      # no version of a, no tmp


@pytest.mark.parametrize("scene", ["no_collector", "streamed"])
def test_gate_parks_in_the_body_where_nothing_can_take_the_merge(
        tmp_path, scene):
    """Grouping off (or a peer's RPC op): no collector, the body waits
    at its gate as before.  A streamed part keeps its own abort path
    (the part is discarded at once) and waits too."""
    d = XLStorage(str(tmp_path))
    d.make_vol("bkt")
    col = commit.GroupCollector()
    if scene == "streamed":
        commit.arm(col)
    try:
        fi = land_fi(vid="va", ddir="d-va")
        gate = Gate(fi.to_dict())
        gate.open_in(0.05)
        data = iter([pattern(1000), pattern(2000)]) \
            if scene == "streamed" else pattern(3000)
        d.write_data_commit("bkt", "a", fi, data, shard_index=1,
                            meta_gate=gate)
        assert gate.calls == 1 and not col.tails()
        col.flush()
    finally:
        commit.disarm()
    assert d.read_version("bkt", "a", "va").data_dir == "d-va"


def test_writer_lands_a_batchs_parts_before_it_waits_for_a_digest(
        tmp_path, monkeypatch):
    """One drive's writer thread, one batch of three overlapped PUTs
    whose digests are all still out: the three parts land first, then
    the three merges in op order, then ONE flush; a body's wall
    includes its second half."""
    from minio_tpu.storage.writers import WriterPlane
    d = XLStorage(str(tmp_path))
    d.make_vol("bkt")
    log = []
    real_part, real_atomic = commit.land_part, xl_storage._write_file_atomic
    monkeypatch.setattr(commit, "land_part", lambda obj, *a, **kw: (
        log.append("part " + os.path.basename(obj)),
        real_part(obj, *a, **kw))[1])
    monkeypatch.setattr(xl_storage, "_write_file_atomic", lambda p, *a, **kw: (
        log.append("meta " + os.path.basename(os.path.dirname(p))),
        real_atomic(p, *a, **kw))[1])
    plane = WriterPlane(queue_depth=8)
    sw = plane.stream([d])
    hold, parked = threading.Event(), threading.Event()
    gates = {}

    def commit_op(name):
        fi = land_fi(vid="v" + name, ddir="d" + name)
        gates[name] = Gate(fi.to_dict())
        return lambda idx, disk: disk.write_data_commit(
            "bkt", name, fi, pattern(3000), shard_index=1,
            meta_gate=gates[name])
    b0, batches0 = hist("mt_commit_body_seconds"), \
        counter("mt_commit_group_batches_total")
    try:
        sw.submit(0, lambda i, disk: (parked.set(), hold.wait(20)))
        assert parked.wait(20)
        for name in "abc":
            sw.submit(0, commit_op(name))
        hold.set()
        end = time.monotonic() + 20
        while len(log) < 3 and time.monotonic() < end:
            time.sleep(0.002)
        time.sleep(0.05)                 # parked on a's gate by now
        assert log == ["part a", "part b", "part c"]
        assert [g.calls for g in gates.values()] == [1, 0, 0]
        for g in gates.values():
            g.ev.set()
        assert sw.drain(20) and sw.errs == [None]
    finally:
        hold.set()
        for g in gates.values():
            g.ev.set()
        plane.close()
    assert log[3:] == ["meta a", "meta b", "meta c"]
    assert counter("mt_commit_group_batches_total") - batches0 == 1
    body = hist("mt_commit_body_seconds")
    assert body[1] - b0[1] == 4              # the hold op and the three
    assert body[0] - b0[0] >= 0.05           # a's wait is body time
    for name in "abc":
        assert d.read_version("bkt", name, "v" + name).data_dir \
            == "d" + name

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
    land the same bytes as with grouping off.
"""

import glob
import hashlib
import os
import shutil
import threading
import time

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

@pytest.fixture(params=["native", "python"])
def wave_impl(request, monkeypatch):
    """Both forms of a flush wave: the one call into native/syncwave.c,
    and the os.* loop that stands in for it without a compiler (the
    only form whose fsyncs a wrapped ``os.fsync`` can see)."""
    if request.param == "python":
        monkeypatch.setattr(commit, "_wave_lib", lambda: None)
    elif commit._wave_lib() is None:
        pytest.skip("native/syncwave.c did not build here")
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
    commit_flush_width / commit_fsyncs_per_put / commit_*_ms tick."""
    monkeypatch.setattr(eo, "_SINGLE_CORE", False)
    commit.CONFIG.pack_threshold = 0          # regular objects only
    lay = mk_layer(tmp_path)
    n = 6
    log = SyncLog(monkeypatch)
    f0, w0 = counter("mt_commit_fsyncs_total"), \
        counter("mt_commit_flush_waves_total")
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

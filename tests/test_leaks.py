"""Resource-leak detection (cmd/leak-detect_test.go tier): repeated
server/cluster start-stop cycles must not accumulate threads or leave
sockets listening.
"""

import os
import socket
import threading
import time

import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl_storage import XLStorage

# shared with the soak plane: every soak scenario runs this same
# settle-then-count assertion after teardown (soak/slo.py)
from minio_tpu.soak.slo import settled_thread_count as \
    _settled_thread_count


def test_server_start_stop_does_not_leak_threads(tmp_path):
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    # warm the shared layer pool to FULL size (ThreadPoolExecutor spawns
    # workers on demand up to max_workers and keeps them — growth during
    # the cycles below would read as a leak when it's just lazy ramp-up)
    layer.make_bucket("warmup")
    layer.put_object("warmup", "o", b"w")
    list(layer._pool.map(time.sleep,
                         [0.05] * layer._pool._max_workers))
    baseline = _settled_thread_count()
    # thread-discipline accounting: every thread the server planes
    # start is named mt-* (lint-enforced); anonymous Thread-N threads
    # appearing during the cycles and surviving a stop would be
    # unattributable leaks.  Earlier suites' leftovers are excluded by
    # id-snapshot.
    anon_before = {id(t) for t in threading.enumerate()
                   if t.name.startswith("Thread-")}
    ports = []
    for cycle in range(3):
        srv = S3Server(layer, access_key="lk", secret_key="ls")
        srv.start()
        ports.append(srv.port)
        c = S3Client(srv.endpoint, "lk", "ls")
        c.make_bucket(f"leak{cycle}")
        c.put_object(f"leak{cycle}", "o", b"x" * 1024)
        assert c.get_object(f"leak{cycle}", "o").body == b"x" * 1024
        srv.stop()
    after = _settled_thread_count()
    # the shared layer's pool persists; per-server threads must not pile
    # up across cycles (allow a small slack for lazy singletons)
    assert after <= baseline + 3, (baseline, after)
    anon_new = [t.name for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("Thread-")
                and id(t) not in anon_before]
    assert not anon_new, (
        f"anonymous threads survived server stop: {anon_new} — "
        f"name them mt-<subsystem>-... (thread-discipline rule)")
    # every listener actually closed
    for p in ports:
        s = socket.socket()
        try:
            assert s.connect_ex(("127.0.0.1", p)) != 0, f"port {p} open"
        finally:
            s.close()


def test_select_disconnect_releases_governor_and_threads(tmp_path):
    """Client disconnect mid-Select-stream (the satellite drill): the
    scanner stops, its readahead plane winds down, and the memory
    governor's charge is released — no surviving scanner threads, no
    residual ``inuse_bytes``."""
    import http.client

    from minio_tpu.s3.sigv4 import Credentials, sign_request
    from minio_tpu.utils.memgov import GOVERNOR
    disks = []
    for i in range(4):
        d = tmp_path / f"sd{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="lk", secret_key="ls")
    srv.start()
    try:
        c = S3Client(srv.endpoint, "lk", "ls")
        c.make_bucket("selleak")
        row = b"col1,col2,col3-some-padding-bytes\n"
        data = row * ((6 << 20) // len(row))     # output > flush bytes
        c.put_object("selleak", "big.csv", data)
        body = (
            b'<?xml version="1.0"?><SelectObjectContentRequest '
            b'xmlns="http://s3.amazonaws.com/doc/2006-03-01/">'
            b"<Expression>SELECT * FROM S3Object</Expression>"
            b"<ExpressionType>SQL</ExpressionType>"
            b"<InputSerialization><CSV/></InputSerialization>"
            b"<OutputSerialization><CSV/></OutputSerialization>"
            b"</SelectObjectContentRequest>")
        # the layer's pool spawns workers on demand and keeps them: warm
        # it to full size, or its ramp-up under the Select's read
        # fan-out reads as a leak (as in the start/stop test above)
        list(layer._pool.map(time.sleep,
                             [0.05] * layer._pool._max_workers))
        baseline = _settled_thread_count()
        assert GOVERNOR.inuse_bytes("select") == 0
        path = "/selleak/big.csv?select&select-type=2"
        hdrs = sign_request(Credentials("lk", "ls"), "POST",
                            srv.endpoint + path, {}, body, "us-east-1")
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=30)
        try:
            conn.request("POST", path, body=body, headers=hdrs)
            resp = conn.getresponse()
            assert resp.status == 200
            got = resp.read(1024)           # a slice of the stream...
            assert got
        finally:
            conn.close()                    # ...then hang up mid-frame
        # the dying handler must release its charge and its threads
        deadline = time.monotonic() + 15.0
        while GOVERNOR.inuse_bytes("select") and \
                time.monotonic() < deadline:
            time.sleep(0.1)
        assert GOVERNOR.inuse_bytes("select") == 0, GOVERNOR.stats()
        after = _settled_thread_count()
        assert after <= baseline + 2, (baseline, after)
    finally:
        srv.stop()
    # request-scoped charges settle; stop() also released any resident
    # hot-read cache bytes, so the total is zero too
    assert GOVERNOR.transient_bytes() == 0
    assert GOVERNOR.inuse_bytes("cache") == 0


def test_egress_workers_stop_with_server(tmp_path, monkeypatch):
    """Config-built egress targets (logger/audit webhooks) get close()d
    on server stop: sender threads join and the process-global logger
    no longer fans entries into the dead server's targets."""
    # port 1 refuses instantly — failures are fast, records spill to
    # the disk store, and the workers exist long enough to observe
    monkeypatch.setenv("MT_LOGGER_WEBHOOK_ENABLE", "on")
    monkeypatch.setenv("MT_LOGGER_WEBHOOK_ENDPOINT",
                       "http://127.0.0.1:1/log")
    monkeypatch.setenv("MT_LOGGER_WEBHOOK_QUEUE_DIR",
                       str(tmp_path / "lq"))
    monkeypatch.setenv("MT_AUDIT_WEBHOOK_ENABLE", "on")
    monkeypatch.setenv("MT_AUDIT_WEBHOOK_ENDPOINT",
                       "http://127.0.0.1:1/audit")
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="ek", secret_key="es")
    srv.start()
    owned = list(srv._egress_owned)
    assert [t.target_type for t in owned] == ["logger", "audit"]
    c = S3Client(srv.endpoint, "ek", "es")
    c.make_bucket("egleak")             # audit entries flow
    srv.logger.error("egress leak probe")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not any(
            t.name.startswith("mt-egress")
            for t in threading.enumerate()):
        time.sleep(0.02)
    assert any(t.name.startswith("mt-egress")
               for t in threading.enumerate())
    srv.stop()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and any(
            t.is_alive() and t.name.startswith("mt-egress")
            for t in threading.enumerate()):
        time.sleep(0.05)
    leftover = [t.name for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("mt-egress")]
    assert not leftover, leftover
    from minio_tpu.obs.logger import GLOBAL as global_logger
    assert not any(t in global_logger.targets for t in owned)


def test_writer_plane_threads_stop_with_server(tmp_path):
    """Per-drive writer threads (mt-putw-*) die with the server — even
    when stop() lands mid-stream with a writer queue BLOCKED on a hung
    drive op and the PUT loop stalled at the enqueue bound.  The md5
    chain rides the layer's shared pool (no threads of its own), so
    nothing md5-shaped can leak either."""
    import io

    from minio_tpu.objectlayer import erasure_object as eo

    # earlier suites in the same process may hold idle writer threads on
    # layers they never stopped; this test's contract is scoped to the
    # threads THIS server's plane starts
    preexisting = {id(th) for th in threading.enumerate()
                   if th.name.startswith("mt-putw")}
    release = threading.Event()

    class BlockingDisk:
        """First append parks until released (a hung drive)."""

        def __init__(self, inner):
            self._inner = inner
            self.blocked = threading.Event()

        @property
        def root(self):
            return self._inner.root

        def append_file(self, volume, path, data):
            self.blocked.set()
            release.wait(20)
            return self._inner.append_file(volume, path, data)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    disks = []
    for i in range(4):
        d = tmp_path / f"wd{i}"
        d.mkdir()
        inner = XLStorage(str(d))
        disks.append(BlockingDisk(inner) if i == 0 else inner)
    layer = ErasureObjects(disks, parity=2, block_size=4096,
                           backend="numpy")
    layer._pipe_depth = 2
    layer._pipe_queue_depth = 1
    old_batch = eo.STREAM_BATCH_BYTES
    eo.STREAM_BATCH_BYTES = 2 * 4096
    srv = S3Server(layer, access_key="wp", secret_key="wp")
    layer._pipe_depth = 2              # server reload may have reset it
    layer._pipe_queue_depth = 1
    srv.start()
    try:
        layer.make_bucket("wpbkt")
        body = b"z" * (40 * 4096)
        put_err: list = []

        def put():
            try:
                layer.put_object_stream("wpbkt", "obj", io.BytesIO(body))
            except Exception as e:  # noqa: BLE001 — asserted below
                put_err.append(e)

        t = threading.Thread(target=put, daemon=True)
        t.start()
        # wait until the hung drive blocks and its queue backs up
        assert disks[0].blocked.wait(10)
        def plane_threads():
            return [th for th in threading.enumerate()
                    if th.name.startswith("mt-putw")
                    and id(th) not in preexisting]

        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not plane_threads():
            time.sleep(0.02)
        assert plane_threads()
        # unblock the hung op only once the plane has actually BEGUN
        # closing (generation bump) — a wall-clock timer races stop()'s
        # serve_forever poll latency and can release the drive while
        # the PUT could still complete
        plane = layer._write_plane
        gen0 = plane._gen

        def release_when_closing():
            end = time.monotonic() + 15.0
            while time.monotonic() < end and plane._gen == gen0:
                time.sleep(0.02)
            release.set()

        threading.Thread(target=release_when_closing,
                         daemon=True).start()
        srv.stop()                      # closes the writer plane
        t.join(15)
        assert not t.is_alive()
        # the aborted PUT surfaced an error (PlaneClosed directly, or
        # quorum loss once every drive's queued ops failed with it)
        assert put_err, "mid-stream PUT survived server stop"
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and any(
                th.is_alive() for th in plane_threads()):
            time.sleep(0.05)
        leftover = [th.name for th in plane_threads() if th.is_alive()]
        assert not leftover, leftover
        # no tmp staging left behind by the aborted stream
        for d in disks:
            root = d.root if hasattr(d, "root") else d._inner.root
            import glob as _glob
            import os as _os
            tmps = [p for p in _glob.glob(
                _os.path.join(root, ".mt.sys", "tmp", "*"))
                if _os.path.isdir(p)]
            assert not tmps, tmps
        # the plane reopens lazily: the layer keeps working afterwards
        layer.put_object_stream("wpbkt", "after", io.BytesIO(body))
        assert layer.get_object("wpbkt", "after")[1] == body
    finally:
        release.set()
        eo.STREAM_BATCH_BYTES = old_batch
        from minio_tpu.storage.writers import close_write_planes
        close_write_planes(layer)


def test_codec_batcher_leaves_no_threads_or_state(tmp_path):
    """The cross-request codec batcher owns NO threads (combiners are
    borrowed caller threads, the LaneScheduler discipline) — after a
    burst of concurrent batched traffic, including a caller that died
    mid-queue, nothing mt-codec-shaped survives and every combining
    bucket has been drained and pruned."""
    import numpy as np

    from minio_tpu.ops.codec import Erasure
    from minio_tpu.parallel import batcher

    cfg = batcher.CONFIG
    saved = (cfg.enable, cfg.window_s, cfg._loaded)
    cfg.enable, cfg.window_s, cfg._loaded = True, 0.02, True
    try:
        body = np.random.default_rng(3).integers(
            0, 256, 4 * 4096, dtype=np.uint8).tobytes()
        c = Erasure(4, 2, 4096, backend="tpu")
        rows = np.asarray(c.matrix)[4:]
        blocks = np.frombuffer(body, np.uint8).reshape(4, 4, 1024)

        def worker():
            c.encode_object(body)

        def dying_worker():
            # a deadline'd caller: cancels out of the queue if parked
            batcher.GLOBAL.apply(c, "encode", rows, blocks,
                                 timeout=0.001)

        ths = [threading.Thread(target=worker, name=f"mt-codec-l{i}")
               for i in range(6)]
        ths.append(threading.Thread(target=dying_worker,
                                    name="mt-codec-dying"))
        for t in ths:
            t.start()
        for t in ths:
            t.join(30)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and any(
                t.is_alive() and t.name.startswith("mt-codec")
                for t in threading.enumerate()):
            time.sleep(0.05)
        leftover = [t.name for t in threading.enumerate()
                    if t.is_alive() and t.name.startswith("mt-codec")]
        assert not leftover, leftover
        assert not batcher.GLOBAL._buckets, "combining bucket leaked"
    finally:
        cfg.enable, cfg.window_s, cfg._loaded = saved


def test_device_md5_state_does_not_survive_server_stop(tmp_path,
                                                       monkeypatch):
    """The device-MD5 plane owns NO threads (the md5 combining bucket
    borrows caller threads exactly like the codec batcher): after a
    server runs strict-ETag PUTs on the device backend and stops, the
    bucket is idle — no waiter, combiner or in-flight dispatch — and
    nothing md5-shaped is left running."""
    import pytest

    from minio_tpu.hashing import md5_device, md5fast
    from minio_tpu.parallel import batcher

    if not md5_device.available():
        pytest.skip(md5_device.unavailable_reason())
    disks = []
    for i in range(4):
        d = tmp_path / f"md{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    # the env override outranks the knob, so the server's own
    # reload_pipeline_config at start cannot reset the rung under us
    monkeypatch.setenv("MT_MD5", "device")
    try:
        srv = S3Server(layer, access_key="mk", secret_key="ms")
        srv.start()
        try:
            c = S3Client(srv.endpoint, "mk", "ms")
            c.make_bucket("devmd5")
            body = b"\x5a" * 300_000

            def put(i):
                c.put_object("devmd5", f"o{i}", body)

            ths = [threading.Thread(target=put, args=(i,),
                                    daemon=True, name=f"mt-md5put-{i}")
                   for i in range(4)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(60)
            got = c.get_object("devmd5", "o0")
            assert got.body == body
            import hashlib
            etag = {k.lower(): v for k, v in
                    got.headers.items()}.get("etag", "")
            assert etag.strip('"') == \
                hashlib.md5(body).hexdigest()   # device ETag, strict
            assert batcher.MD5_GLOBAL.snapshot()["requests"] > 0, \
                "PUTs never rode the device-MD5 bucket"
        finally:
            srv.stop()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                not batcher.MD5_GLOBAL.idle():
            time.sleep(0.05)
        assert batcher.MD5_GLOBAL.idle(), \
            "device-MD5 bucket state survived server stop"
    finally:
        md5fast.set_backend("auto")


def test_diskcache_threads_join_on_close_and_server_stop(tmp_path):
    """The mt-diskcache-* thread discipline (PR-10 rule, wired for
    real this PR): the writeback sender and the periodic GC sweeper
    are named, daemonized, and JOINED — by an explicit close() and by
    S3Server.stop() walking wrapped layers."""
    from minio_tpu.objectlayer.diskcache import CacheObjects

    def diskcache_threads():
        return [t.name for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("mt-diskcache")]

    disks = []
    for i in range(4):
        d = tmp_path / f"dc{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    inner = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    # direct close(): wb thread woken from its queue park, gc thread
    # woken from its interval wait, both joined
    cache = CacheObjects(inner, [str(tmp_path / "cd0")],
                         writeback=True, gc_interval_s=0.05)
    cache.make_bucket("dcache")
    cache.put_object("dcache", "o", b"wb-bytes")
    cache.flush_writeback()
    assert diskcache_threads(), "wb/gc threads never started"
    cache.close()
    deadline = time.monotonic() + 5.0
    while diskcache_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not diskcache_threads(), diskcache_threads()
    # server stop path: a CacheObjects-wrapped layer's threads die
    # WITH the server (stop() walks .inner chains and closes)
    cache2 = CacheObjects(inner, [str(tmp_path / "cd1")],
                          gc_interval_s=0.05)
    srv = S3Server(cache2, access_key="dk", secret_key="ds")
    srv.start()
    try:
        assert diskcache_threads(), "gc sweeper never started"
    finally:
        srv.stop()
    deadline = time.monotonic() + 5.0
    while diskcache_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not diskcache_threads(), diskcache_threads()


def test_hot_read_plane_owns_no_threads_and_releases_bytes(tmp_path):
    """The hot-read plane's shutdown contract: leaders are borrowed
    caller threads (nothing to join), and server stop releases every
    cached byte back to the memory governor."""
    from minio_tpu.objectlayer import hotread
    from minio_tpu.utils.memgov import GOVERNOR
    cfg = hotread.CONFIG
    saved = (cfg.enable, cfg.heat_threshold, cfg._loaded)
    cfg.enable, cfg.heat_threshold, cfg._loaded = True, 1, True
    try:
        disks = []
        for i in range(4):
            d = tmp_path / f"hr{i}"
            d.mkdir()
            disks.append(XLStorage(str(d)))
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        # warm the shared layer pool to FULL size first (lazy ramp-up
        # during the GETs below would read as a leak)
        layer.make_bucket("warm")
        layer.put_object("warm", "o", b"w")
        list(layer._pool.map(time.sleep,
                             [0.05] * layer._pool._max_workers))
        before = _settled_thread_count()
        srv = S3Server(layer, access_key="hk", secret_key="hs")
        srv.start()
        try:
            layer.hotread.heat_fn = lambda: 100
            c = S3Client(srv.endpoint, "hk", "hs")
            c.make_bucket("hotleak")
            c.put_object("hotleak", "o", b"h" * 4096)
            for _ in range(3):
                assert c.get_object("hotleak", "o").body == b"h" * 4096
            assert layer.hotread.cache.stats()["entries"] > 0
            assert GOVERNOR.inuse_bytes("cache") > 0
        finally:
            srv.stop()
        # cached bytes released with the server; no plane threads ever
        assert GOVERNOR.inuse_bytes("cache") == 0
        assert layer.hotread.cache.stats()["entries"] == 0
        assert _settled_thread_count() <= before + 2
    finally:
        (cfg.enable, cfg.heat_threshold, cfg._loaded) = saved


def test_rpc_server_stop_closes_listener(tmp_path):
    from minio_tpu.parallel.rpc import RPCClient, RPCError, RPCServer
    srv = RPCServer("leaksecret")
    srv.start()
    port = srv.port
    assert RPCClient(srv.endpoint, "leaksecret").call("sys", "ping") == \
        "pong"
    srv.stop()
    s = socket.socket()
    try:
        assert s.connect_ex(("127.0.0.1", port)) != 0
    finally:
        s.close()


@pytest.mark.parametrize("hung", [False, True], ids=["idle", "hung-wave"])
def test_flush_waves_leave_no_thread_behind(tmp_path, monkeypatch, hung):
    """A group-commit flush issues its fsyncs in waves (storage/commit.py
    sync_files / sync_dirs); whatever threads a wave uses are joined
    inside the call, and the drives' writers are the plane's only
    threads: after close_write_planes no mt-putw* thread is alive and
    the process holds no more OS threads than before the plane started
    — also when the close finds a writer parked inside a hung wave."""
    from minio_tpu.storage import commit
    from minio_tpu.storage.writers import close_write_planes

    def os_threads():
        return len(os.listdir("/proc/self/task"))

    def plane_threads():
        return [th for th in threading.enumerate()
                if th.name.startswith("mt-putw") and th.is_alive()
                and th.name not in preexisting]
    monkeypatch.setattr(commit.CONFIG, "_loaded", True)
    monkeypatch.setattr(commit.CONFIG, "enable", True)
    release, parked = threading.Event(), threading.Event()
    armed = threading.Event()
    real_sync_files = commit.sync_files

    def sync_files(fds):
        if hung and armed.is_set() and not parked.is_set():
            parked.set()
            release.wait(20)
        return real_sync_files(fds)
    monkeypatch.setattr(commit, "sync_files", sync_files)
    disks = []
    for i in range(4):
        d = tmp_path / f"sp{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=4096,
                           backend="numpy")
    layer._pipe_depth = 2            # force regardless of core count
    layer.make_bucket("spbkt")
    body = b"s" * (2 << 20)          # a part file: waves of several fds
    # ramp the layer's lazy pool and the wave helper up before counting
    layer.put_object("spbkt", "warm", body)
    list(layer._pool.map(time.sleep, [0.05] * layer._pool._max_workers))
    close_write_planes(layer)
    preexisting = {th.name for th in threading.enumerate()
                   if th.name.startswith("mt-putw")}
    base = os_threads()
    plane = layer._write_plane
    done: list = []
    armed.set()

    def put():
        try:
            layer.put_object("spbkt", "obj", body)
            done.append(None)
        except Exception as e:  # noqa: BLE001 — close may abort it
            done.append(e)
    t = threading.Thread(target=put, daemon=True)
    t.start()
    try:
        if hung:
            assert parked.wait(10)
            gen0 = plane._gen

            def release_when_closing():
                end = time.monotonic() + 15.0
                while time.monotonic() < end and plane._gen == gen0:
                    time.sleep(0.02)
                release.set()
            threading.Thread(target=release_when_closing,
                             daemon=True).start()
        else:
            t.join(15)
            assert done == [None]
        assert plane_threads()
        close_write_planes(layer, timeout=10.0)
        t.join(15)
        assert not t.is_alive() and done
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and (
                plane_threads() or os_threads() > base):
            time.sleep(0.05)
        assert not plane_threads(), [th.name for th in plane_threads()]
        assert os_threads() <= base, (os_threads(), base)
        # the plane reopens lazily
        layer.put_object("spbkt", "after", body)
        assert layer.get_object("spbkt", "after")[1] == body
    finally:
        release.set()
        close_write_planes(layer)


LAND_STEPS = {
    # step -> (call on commit given the scratch dir, the error it raises)
    "mkdir_obj": (lambda c, d: c.land_part(
        d + "/gone/o", d + "/gone/o/dd", d + "/gone/o/dd/part.1", b"x"),
        FileNotFoundError),
    "mkdir_ddir": (lambda c, d: c.land_part(
        d + "/o", d + "/o/dd", d + "/o/dd/part.1", b"x"),
        FileExistsError),
    "open": (lambda c, d: c.land_file(d + "/o/dd", b"x"),
             IsADirectoryError),
    "write": (lambda c, d: c.land_file("/dev/full", b"x" * 70_000),
              OSError),                      # ENOSPC
    "sync": (lambda c, d: c.land_file("/dev/null", b"x"),
             OSError),                       # fsync(/dev/null): EINVAL
}


@pytest.mark.parametrize("form", ["native", "python"])
@pytest.mark.parametrize("step", sorted(LAND_STEPS))
def test_landing_leaves_no_fd_open_on_a_failing_step(tmp_path, monkeypatch,
                                                     step, form):
    """An op body's landing (storage/commit.py land_part / land_file,
    native/syncwave.c beside the flush waves) that fails at any of its
    steps — either mkdir, the open, the write, the fsync — raises that
    step's OSError and holds no descriptor afterwards, in the native
    form and in the os.* form alike; a landing that works holds none
    either (no collector armed: nothing is dup'd)."""
    from minio_tpu.storage import commit
    if form == "python":
        monkeypatch.setattr(commit, "_wave_lib", lambda: None)
    elif commit._wave_lib() is None:
        pytest.skip("native/syncwave.c did not build here")
    d = str(tmp_path)
    assert commit.land_part(d + "/o", d + "/o/dd", d + "/o/dd/part.1",
                            b"abc") is True
    call, exc = LAND_STEPS[step]
    before = sorted(os.listdir("/proc/self/fd"))
    for _ in range(3):
        with pytest.raises(exc):
            call(commit, d)
    commit.land_file(d + "/o/f", b"abc")
    assert sorted(os.listdir("/proc/self/fd")) == before
    with open(d + "/o/dd/part.1", "rb") as f:
        assert f.read() == b"abc"

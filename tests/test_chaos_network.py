"""Network chaos tier — deterministic fault injection against the
request plane (the wire analog of the NaughtyDisk storage tests).

Covers the acceptance scenarios of the resilience layer:
  * slowloris on the S3 port is cut off at the configured deadline
    while concurrent PUT/GET traffic completes unimpeded;
  * saturated request pool sheds with 503 + Retry-After;
  * killing one peer mid-PUT yields a quorum-committed object, the
    node breaker opens within N failures, and the restarted peer is
    re-admitted via a half-open probe;
  * lock refresh under partition surfaces LockLost instead of letting
    the holder believe it is protected past the locker-side TTL;
  * FaultyProxy programs (503 burst, mid-body reset, black-hole) by
    connection number — programmed faults, no wall-clock coin flips.
"""

import os
import socket
import threading
import time

import pytest

from minio_tpu.parallel.dsync import (DRWMutex, LocalLocker, LockLost,
                                      RemoteLocker,
                                      register_lock_service)
from minio_tpu.parallel.faulty import Fault, FaultyProxy
from minio_tpu.parallel.rpc import (CircuitBreaker, RPCClient, RPCError,
                                    RPCServer)
from minio_tpu.utils.retry import RetryPolicy


def _no_retry_client(endpoint, fail_max=100, cooldown_s=60.0,
                     timeout=5.0):
    return RPCClient(endpoint, "testsecret", timeout=timeout,
                     breaker=CircuitBreaker(fail_max=fail_max,
                                            cooldown_s=cooldown_s),
                     retry=RetryPolicy(attempts=1))


# -- FaultyProxy programs ---------------------------------------------------

@pytest.fixture
def upstream():
    srv = RPCServer("testsecret")
    srv.register("t", {"echo": lambda x: x})
    srv.start()
    yield srv
    srv.stop()


def test_proxy_passthrough_and_programmed_503(upstream):
    proxy = FaultyProxy("127.0.0.1", upstream.port,
                        plan={2: Fault.http_503()}).start()
    try:
        c = _no_retry_client(proxy.endpoint)
        assert c.call("t", "echo", x=1) == 1        # conn 1: clean
        c2 = _no_retry_client(proxy.endpoint)       # fresh pool ->
        with pytest.raises(RPCError):               # conn 2: 503 burst
            c2.call("t", "echo", x=2)
        c3 = _no_retry_client(proxy.endpoint)
        assert c3.call("t", "echo", x=3) == 3       # conn 3: clean again
    finally:
        proxy.stop()


def test_proxy_mid_body_reset_detected(upstream):
    """A connection RST mid-response must surface as a transport error
    (and a breaker failure), never as a short read treated as truth."""
    proxy = FaultyProxy("127.0.0.1", upstream.port,
                        plan={1: Fault.reset(after_bytes=5)}).start()
    try:
        c = _no_retry_client(proxy.endpoint, fail_max=1)
        with pytest.raises(RPCError):
            c.call("t", "echo", x="Z" * 4096)
        assert c.breaker.state == CircuitBreaker.OPEN
    finally:
        proxy.stop()


def test_proxy_blackhole_hits_client_deadline(upstream):
    """A peer that accepts but never answers is bounded by the client
    deadline, not forever."""
    proxy = FaultyProxy("127.0.0.1", upstream.port,
                        default=Fault.blackhole()).start()
    try:
        c = _no_retry_client(proxy.endpoint, timeout=1.0)
        c._dyn_for("t")._timeout = 1.0      # pin the adaptive deadline
        t0 = time.monotonic()
        with pytest.raises(RPCError):
            c.call("t", "echo", x=1)
        assert time.monotonic() - t0 < 10.0
    finally:
        proxy.stop()


def test_proxy_503_burst_trips_breaker_then_heals(upstream):
    """A 5xx-bursting intermediary opens the node breaker (fail fast);
    healing the link re-admits the peer via the half-open probe."""
    clock = [0.0]
    proxy = FaultyProxy("127.0.0.1", upstream.port,
                        default=Fault.http_503()).start()
    try:
        c = RPCClient(proxy.endpoint, "testsecret",
                      breaker=CircuitBreaker(fail_max=2, cooldown_s=5.0,
                                             clock=lambda: clock[0]),
                      retry=RetryPolicy(attempts=1))
        for _ in range(2):
            with pytest.raises(RPCError):
                c.call("t", "echo", x=1)
        assert c.breaker.state == CircuitBreaker.OPEN
        with pytest.raises(RPCError) as ei:
            c.call("t", "echo", x=1)
        assert ei.value.error_type == "PeerOffline"
        proxy.set_default(Fault.passthrough())      # heal the link
        clock[0] = 6.0                              # cooldown elapses
        assert c.call("t", "echo", x=1) == 1        # probe re-admits
        assert c.breaker.state == CircuitBreaker.CLOSED
    finally:
        proxy.stop()


# -- S3 frontend: slowloris + shed ------------------------------------------

@pytest.fixture
def s3_server(tmp_path, monkeypatch):
    monkeypatch.setenv("MT_API_READ_HEADER_TIMEOUT", "500ms")
    monkeypatch.setenv("MT_API_BODY_DEADLINE", "1s")
    # pin the budget to exactly the deadline (no size-scaled headroom)
    monkeypatch.setenv("MT_API_BODY_MIN_RATE", "0")
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl_storage import XLStorage
    disks = []
    for i in range(4):
        d = tmp_path / f"disk{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=256 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="testkey", secret_key="testsecret")
    srv.start()
    yield srv
    srv.stop()


def test_slowloris_header_cut_at_deadline(s3_server):
    s = socket.create_connection(("127.0.0.1", s3_server.port))
    try:
        s.settimeout(10.0)
        s.sendall(b"GET / HT")                  # header never finishes
        t0 = time.monotonic()
        assert s.recv(4096) == b""              # server closed on us
        assert time.monotonic() - t0 < 5.0      # at ~the 0.5 s deadline
    finally:
        s.close()


def test_select_stream_proxy_reset_releases_scanner(tmp_path):
    """FaultyProxy reset mid-Select-event-stream (the satellite drill):
    the connection dies between Records frames; the server's scanner
    stops and its memory-governor charge drains — the frontend twin of
    the internode mid-frame reset drills below."""
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.s3.sigv4 import Credentials, sign_request
    from minio_tpu.storage.xl_storage import XLStorage
    from minio_tpu.utils.memgov import GOVERNOR
    disks = []
    for i in range(4):
        d = tmp_path / f"sxd{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=256 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="testkey", secret_key="testsecret")
    srv.start()
    proxy = FaultyProxy("127.0.0.1", srv.port).start()
    try:
        c = S3Client(srv.endpoint, "testkey", "testsecret")
        c.make_bucket("chsel")
        row = b"alpha,beta,gamma-some-padding-for-size\n"
        data = row * ((6 << 20) // len(row))
        c.put_object("chsel", "big.csv", data)
        body = (
            b'<?xml version="1.0"?><SelectObjectContentRequest '
            b'xmlns="http://s3.amazonaws.com/doc/2006-03-01/">'
            b"<Expression>SELECT * FROM S3Object</Expression>"
            b"<ExpressionType>SQL</ExpressionType>"
            b"<InputSerialization><CSV/></InputSerialization>"
            b"<OutputSerialization><CSV/></OutputSerialization>"
            b"</SelectObjectContentRequest>")
        path = "/chsel/big.csv?select&select-type=2"
        # sign against the REAL endpoint; send through the proxy, which
        # resets the wire after ~128 KiB of response crossed it
        hdrs = sign_request(Credentials("testkey", "testsecret"),
                            "POST", srv.endpoint + path, {}, body,
                            "us-east-1")
        proxy.program(proxy.connections_seen() + 1,
                      Fault.reset(after_bytes=128 * 1024))
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=30)
        try:
            conn.request("POST", path, body=body, headers=hdrs)
            with pytest.raises((ConnectionError, http.client.HTTPException,
                                TimeoutError, OSError)):
                resp = conn.getresponse()
                while resp.read(65536):
                    pass
                raise ConnectionResetError("stream ended short")
        finally:
            conn.close()
        deadline = time.monotonic() + 15.0
        while GOVERNOR.inuse_bytes("select") and \
                time.monotonic() < deadline:
            time.sleep(0.1)
        assert GOVERNOR.inuse_bytes("select") == 0, GOVERNOR.stats()
        # the link heals: the same query completes through the proxy
        hdrs2 = sign_request(Credentials("testkey", "testsecret"),
                             "POST", srv.endpoint + path, {}, body,
                             "us-east-1")
        conn2 = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                           timeout=60)
        try:
            conn2.request("POST", path, body=body, headers=hdrs2)
            resp2 = conn2.getresponse()
            assert resp2.status == 200
            out = resp2.read()
        finally:
            conn2.close()
        from minio_tpu.s3select import message as sel_msg
        assert sel_msg.parse_events(out)[-1][0] == "End"
    finally:
        proxy.stop()
        srv.stop()


def test_slow_body_cut_with_408_while_traffic_flows(s3_server):
    """The acceptance scenario: a trickling body is cut at the absolute
    body deadline with 408 RequestTimeout, while concurrent PUT/GET on
    other connections completes unimpeded."""
    from minio_tpu.s3.client import S3Client
    cli = S3Client(s3_server.endpoint, "testkey", "testsecret")
    cli.make_bucket("chaos")

    s = socket.create_connection(("127.0.0.1", s3_server.port))
    s.settimeout(10.0)
    s.sendall(b"PUT /chaos/slow HTTP/1.1\r\nHost: h\r\n"
              b"Content-Length: 1000000\r\n\r\n")
    stop = threading.Event()

    def trickle():
        try:
            while not stop.is_set():
                s.sendall(b"a")
                time.sleep(0.05)
        except OSError:
            pass

    t = threading.Thread(target=trickle, daemon=True)
    t.start()
    try:
        # concurrent traffic while the slowloris is parked
        data = os.urandom(512 * 1024)
        cli.put_object("chaos", "ok", data)
        assert cli.get_object("chaos", "ok").body == data

        resp = b""
        while True:
            try:
                chunk = s.recv(4096)
            except OSError:
                break
            if not chunk:
                break
            resp += chunk
        assert b"408" in resp.split(b"\r\n")[0]
        assert b"RequestTimeout" in resp
    finally:
        stop.set()
        s.close()
    # the slow client never produced an object
    from minio_tpu.s3.client import S3ClientError
    with pytest.raises(S3ClientError):
        cli.get_object("chaos", "slow")


def test_saturated_pool_sheds_503_with_retry_after(tmp_path,
                                                   monkeypatch):
    monkeypatch.setenv("MT_API_REQUESTS_MAX", "1")
    monkeypatch.setenv("MT_API_REQUESTS_DEADLINE", "200ms")
    monkeypatch.setenv("MT_API_BODY_DEADLINE", "2s")
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl_storage import XLStorage
    disks = []
    for i in range(4):
        d = tmp_path / f"disk{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=256 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="testkey", secret_key="testsecret")
    srv.start()
    try:
        # park a slow-bodied request in the ONLY slot
        hold = socket.create_connection(("127.0.0.1", srv.port))
        hold.sendall(b"PUT /chaos/hold HTTP/1.1\r\nHost: h\r\n"
                     b"Content-Length: 100\r\n\r\n")
        time.sleep(0.1)
        # second request: waits up to the 200 ms deadline, then shed
        s = socket.create_connection(("127.0.0.1", srv.port))
        s.settimeout(10.0)
        s.sendall(b"GET /chaos/x HTTP/1.1\r\nHost: h\r\n\r\n")
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = s.recv(4096)
            if not chunk:
                break
            resp += chunk
        head = resp.split(b"\r\n\r\n")[0]
        assert b"503" in head.split(b"\r\n")[0]
        assert b"Retry-After:" in head
        s.close()
        hold.close()
        # slot frees once the held connection dies: traffic resumes
        from minio_tpu.s3.client import S3Client
        cli = S3Client(srv.endpoint, "testkey", "testsecret")
        deadline = time.monotonic() + 10.0
        while True:
            try:
                cli.make_bucket("after")
                break
            except Exception:  # noqa: BLE001 — held slot still draining
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)
        assert cli.head_bucket("after")
    finally:
        srv.stop()


def test_graceful_drain_completes_inflight_put(tmp_path, monkeypatch):
    """Graceful shutdown (ISSUE 8 satellite): stop() refuses NEW
    connections first (listener closed), lets the in-flight PUT finish
    byte-correct within api.shutdown_drain_s, then severs."""
    monkeypatch.setenv("MT_API_SHUTDOWN_DRAIN_S", "8s")
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.xl_storage import XLStorage
    disks = []
    for i in range(4):
        d = tmp_path / f"disk{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="drkey", secret_key="drsecret")
    srv.start()
    assert srv.shutdown_drain_s == 8.0
    cli = S3Client(srv.endpoint, "drkey", "drsecret")
    cli.make_bucket("drain")
    # a handler stays in _active_conns through its post-response
    # bookkeeping (flight record, metrics) — wait for make_bucket's
    # handler to fully retire so the active conn we poll for below
    # can only be OUR mid-flight PUT, not its dying predecessor
    deadline = time.monotonic() + 5.0
    while srv._active_conns:
        assert time.monotonic() < deadline, "make_bucket never retired"
        time.sleep(0.01)
    url = cli.presign("PUT", "drain", "slowobj")
    path_q = url[len(srv.endpoint):]
    body = os.urandom(64 * 1024)
    s = socket.create_connection(("127.0.0.1", srv.port))
    s.settimeout(20.0)
    try:
        s.sendall((f"PUT {path_q} HTTP/1.1\r\n"
                   f"Host: 127.0.0.1:{srv.port}\r\n"
                   f"Content-Length: {len(body)}\r\n\r\n").encode())
        s.sendall(body[:100])                  # PUT is now mid-flight
        deadline = time.monotonic() + 5.0
        while not srv._active_conns:
            assert time.monotonic() < deadline, "request never started"
            time.sleep(0.01)
        stopper = threading.Thread(target=srv.stop, daemon=True)
        stopper.start()
        # new connections are refused once the listener closes
        deadline = time.monotonic() + 5.0
        while True:
            probe = socket.socket()
            try:
                refused = probe.connect_ex(("127.0.0.1", srv.port)) != 0
            finally:
                probe.close()
            if refused:
                break
            assert time.monotonic() < deadline, "listener never closed"
            time.sleep(0.05)
        assert stopper.is_alive()              # still draining us
        s.sendall(body[100:])                  # finish the body
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = s.recv(4096)
            if not chunk:
                break
            resp += chunk
        assert b"200" in resp.split(b"\r\n")[0]
        stopper.join(timeout=15.0)
        assert not stopper.is_alive()
        # the drained PUT landed byte-correct
        _, got = layer.get_object("drain", "slowobj")
        assert got == body
    finally:
        s.close()
        from minio_tpu.storage.writers import close_write_planes
        close_write_planes(layer)


# -- peer kill/flap mid-PUT with quorum preserved ---------------------------

@pytest.fixture
def chaos_cluster(tmp_path, monkeypatch):
    """3 in-process nodes x 2 drives, one 6-drive erasure set, with
    snappy breaker settings so peer death is detected in a couple of
    calls and re-admission probes come fast."""
    monkeypatch.setenv("MT_RPC_BREAKER_FAILURES", "2")
    monkeypatch.setenv("MT_RPC_BREAKER_COOLDOWN", "200ms")
    monkeypatch.setenv("MT_RPC_RETRY_ATTEMPTS", "1")
    from minio_tpu.cluster import NodeSpec, start_cluster
    specs = []
    for n in range(3):
        dirs = []
        for d in range(2):
            p = tmp_path / f"n{n}d{d}"
            p.mkdir()
            dirs.append(str(p))
        specs.append(NodeSpec(node_id=f"node{n}", drive_dirs=dirs))
    nodes = start_cluster(specs, "testsecret", set_drive_count=6)
    yield nodes
    for node in nodes:
        try:
            node.stop()
        except Exception:  # noqa: BLE001 — some tests stop nodes
            pass


def test_peer_kill_mid_put_quorum_commit_and_breaker(chaos_cluster):
    nodes = chaos_cluster
    layer0 = nodes[0].layer
    layer0.make_bucket("chaos")
    data0 = os.urandom(128 * 1024)
    layer0.put_object("chaos", "before", data0)

    # kill node2 (its 2 drives + locker vanish mid-workload)
    victim_port = nodes[2].rpc.port
    nodes[2].rpc.stop()

    # PUT with the peer dead: 4/6 drives reach write quorum
    data1 = os.urandom(256 * 1024)
    layer0.put_object("chaos", "during", data1)
    _, got = layer0.get_object("chaos", "during")
    assert got == data1
    _, got0 = layer0.get_object("chaos", "before")
    assert got0 == data0

    # the remote-drive breakers for node2 opened within 2 failures:
    # further calls fail FAST (no timeout stacking)
    from minio_tpu.storage import errors as serrors
    all_disks = [d for s in layer0.sets for d in s.disks]
    victims = [d for d in all_disks
               if f":{victim_port}/" in d.endpoint()]
    assert len(victims) == 2
    t0 = time.monotonic()
    for d in victims:
        with pytest.raises(serrors.StorageError):
            d.read_all("chaos-probe-vol", "nope")
    assert time.monotonic() - t0 < 2.0

    # peer returns on the SAME port with the same drives; after the
    # breaker cooldown the half-open probe re-admits it
    from minio_tpu.parallel.dsync import register_lock_service
    from minio_tpu.storage.remote import register_storage_service
    srv2 = RPCServer("testsecret", port=victim_port)
    register_storage_service(srv2, nodes[2].drives)
    register_lock_service(srv2, nodes[2].locker)
    srv2.start()
    try:
        time.sleep(0.3)     # > breaker cooldown (200 ms)
        # the shared heal-convergence contract (soak/slo.py, the same
        # helper the soak matrix asserts): repeated sweeps double as
        # the half-open probe traffic that re-admits the peer, and
        # convergence requires classify_disks clean on EVERY drive —
        # the 'during' object's missing shards are healed back onto
        # the returned node, not merely readable around it
        from minio_tpu.soak.slo import assert_converged
        out = assert_converged(layer0, timeout_s=30.0)
        assert out["objects_checked"] >= 2
        # full-strength PUT/GET once re-admitted
        data2 = os.urandom(64 * 1024)
        layer0.put_object("chaos", "after", data2)
        _, got2 = layer0.get_object("chaos", "after")
        assert got2 == data2
    finally:
        srv2.stop()


def test_peer_kill_mid_stream_with_writer_queues(chaos_cluster,
                                                 monkeypatch):
    """The peer-kill drill on the PIPELINED path: a streaming PUT with
    per-drive writer queues in flight loses a 2-drive peer between
    batches.  The queued ops for the dead drives fail (breaker-fast),
    errors latch, the 4 surviving drives hold write quorum, and the
    commit lands byte-correct."""
    import io

    import minio_tpu.objectlayer.erasure_object as eo
    nodes = chaos_cluster
    layer0 = nodes[0].layer
    for s in layer0.sets:
        s._pipe_depth = 2           # force the plane on any host
        s._pipe_queue_depth = 2
    # small stream batches so one PUT spans several writer rounds
    monkeypatch.setattr(eo, "STREAM_BATCH_BYTES", 256 * 1024)
    es = layer0.sets[0]
    batch = es._stream_batch_size()
    layer0.make_bucket("chaosq")
    body = os.urandom(4 * batch + 1234)

    killed = threading.Event()

    class KillerReader:
        """Kills node2's RPC plane after the second batch is served —
        its two drives die with creates already queued/landed."""

        def __init__(self, data):
            self._f = io.BytesIO(data)
            self._served = 0

        def read(self, n=-1):
            c = self._f.read(n)
            self._served += len(c)
            if self._served >= 2 * batch and not killed.is_set():
                killed.set()
                nodes[2].rpc.stop()
            return c

    layer0.put_object_stream("chaosq", "queued", KillerReader(body))
    assert killed.is_set()
    _, got = layer0.get_object("chaosq", "queued")
    assert got == body
    # quorum math held: exactly the peer's drives are object-less
    fis, errs = es._fanout(
        lambda d: d.read_version("chaosq", "queued", None))
    assert sum(1 for f in fis if f is not None) == 4
    assert sum(1 for e in errs if e is not None) == 2


# -- lock refresh under partition -------------------------------------------

def test_lock_refresh_partition_raises_lock_lost():
    """A held DRWMutex whose lockers become unreachable must see its
    grants presumed-expired after one TTL of failed refreshes — the
    holder aborts at the commit point instead of writing unprotected."""
    local = LocalLocker()
    servers = []
    lockers = [local]
    for _ in range(2):
        srv = RPCServer("testsecret")
        lk = LocalLocker()
        register_lock_service(srv, lk)
        srv.start()
        servers.append(srv)
        lockers.append(RemoteLocker(_no_retry_client(srv.endpoint)))

    m = DRWMutex(lockers, "chaos/partition", ttl_s=0.6)
    m.lock(write=True, timeout=5.0)
    try:
        m.ensure_valid()                    # healthy: still protected
        for srv in servers:                 # partition: both peers gone
            srv.stop()
        # refreshes run every ttl/3; after REFRESH_FAILS_MAX consecutive
        # transport failures the grants are presumed expired -> below
        # write quorum (needs 2/3) -> lost fires
        assert m.lost.wait(timeout=10.0)
        with pytest.raises(LockLost):
            m.ensure_valid()
    finally:
        m.unlock()


def test_lock_refresh_survives_single_blip():
    """One locker briefly unreachable is NOT a lost lock: quorum holds
    via the remaining lockers and the blip resets on recovery."""
    local = LocalLocker()
    srv = RPCServer("testsecret")
    lk = LocalLocker()
    register_lock_service(srv, lk)
    srv.start()
    lockers = [local, RemoteLocker(_no_retry_client(srv.endpoint))]
    m = DRWMutex(lockers, "chaos/blip", ttl_s=0.6)
    m.lock(write=True, timeout=5.0)
    try:
        # 2 lockers, write quorum 2: losing the remote would lose the
        # lock, but a single failed round (< REFRESH_FAILS_MAX) is a
        # blip, not a partition
        m._refresh_fails[1] = 1
        m._do_refresh()                     # succeeds: counter resets
        assert m._refresh_fails[1] == 0
        assert not m.lost.is_set()
        m.ensure_valid()
    finally:
        m.unlock()
        srv.stop()


# -- chunked-streaming faults (ISSUE 6: reset/blackhole mid-frame) ----------

def _stream_remote_layer(tmp_path, monkeypatch, secret="streamchaos"):
    """4 local + 2 remote drives, remotes behind a FaultyProxy, with
    internode streaming forced down to tiny frames so every shard
    append/commit rides the framed mode."""
    from minio_tpu.objectlayer import erasure_object as eo
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.parallel.rpc import STREAM, RPCServer
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    from minio_tpu.storage.xl_storage import XLStorage
    monkeypatch.setattr(STREAM, "enable", True)
    monkeypatch.setattr(STREAM, "chunk_bytes", 1024)
    monkeypatch.setattr(STREAM, "_loaded", True)
    monkeypatch.setattr(eo, "STREAM_BATCH_BYTES", 2 * 4096)
    rpc = RPCServer(secret)
    remote_drives = {}
    for i in range(2):
        d = tmp_path / f"r{i}"
        d.mkdir()
        remote_drives[f"r{i}"] = XLStorage(str(d))
    register_storage_service(rpc, remote_drives)
    rpc.start()
    proxy = FaultyProxy("127.0.0.1", rpc.port).start()
    disks = []
    for i in range(4):
        d = tmp_path / f"l{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    remotes = [_no_retry_client(proxy.endpoint, timeout=2.0)
               for _ in range(2)]
    for i, c in enumerate(remotes):
        c.secret = secret
        disks.append(RemoteStorage(c, f"r{i}"))
    lay = ErasureObjects(disks, parity=2, block_size=4096,
                         backend="numpy", inline_threshold=512)
    lay._pipe_depth = 2
    lay.make_bucket("cbkt")
    return lay, proxy, rpc, remotes


def _drop_pools(clients):
    for c in clients:
        with c._pool_mu:
            for conn in c._pool:
                conn.close()
            c._pool.clear()


def test_stream_reset_mid_frame_latches_and_quorum_commits(
        tmp_path, monkeypatch):
    """The proxy RSTs every new connection carrying streamed frames:
    the half-streamed appends surface as TRANSPORT failures (RPCError,
    breaker fed), latch in the per-drive writer plane, and the PUT
    commits on the 4/6 local quorum — with NO partial shard visible on
    the faulted remotes."""
    import hashlib as _hashlib
    import io as _io

    from minio_tpu.storage.writers import close_write_planes
    lay, proxy, rpc, remotes = _stream_remote_layer(tmp_path, monkeypatch)
    try:
        body = (b"frame-chaos!" * 4096)[: 10 * 4096]
        # healthy pass first: streamed appends reach the remotes
        oi = lay.put_object_stream("cbkt", "ok", _io.BytesIO(body))
        assert oi.etag == _hashlib.md5(body).hexdigest()
        # now cut every NEW connection mid-stream and drop the pools so
        # the next PUT's streamed appends must ride faulted connections
        proxy.set_default(Fault.reset(after_bytes=0))
        _drop_pools(remotes)
        from minio_tpu.admin.metrics import GLOBAL
        errs0 = sum(v for k, v in GLOBAL.snapshot().items()
                    if k[0] == "mt_node_rpc_errors_total")
        oi = lay.put_object_stream("cbkt", "cut", _io.BytesIO(body))
        assert oi.etag == _hashlib.md5(body).hexdigest()
        assert lay.get_object("cbkt", "cut")[1] == body
        # transport failures were recorded (breaker/retry path), and
        # no partial shard of the faulted PUT is visible remotely
        errs1 = sum(v for k, v in GLOBAL.snapshot().items()
                    if k[0] == "mt_node_rpc_errors_total")
        assert errs1 > errs0
        for i in range(2):
            assert not os.path.exists(
                os.path.join(str(tmp_path / f"r{i}"), "cbkt", "cut",
                             "xl.meta"))
    finally:
        close_write_planes(lay)
        proxy.stop()
        rpc.stop()


def test_stream_blackhole_mid_frame_is_transport_failure(
        tmp_path, monkeypatch):
    """A blackholed peer swallows streamed frames and never answers:
    the sender's deadline converts it into a typed transport RPCError
    (never a hang, never a half-applied op on the real peer)."""
    from minio_tpu.parallel.rpc import STREAM, RPCServer
    from minio_tpu.storage import errors as serrors
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    from minio_tpu.storage.xl_storage import XLStorage
    monkeypatch.setattr(STREAM, "enable", True)
    monkeypatch.setattr(STREAM, "chunk_bytes", 1024)
    monkeypatch.setattr(STREAM, "_loaded", True)
    d = tmp_path / "bh"
    d.mkdir()
    drive = XLStorage(str(d))
    drive.make_vol("vol1")
    rpc = RPCServer("testsecret")
    register_storage_service(rpc, {"bh": drive})
    rpc.start()
    proxy = FaultyProxy("127.0.0.1", rpc.port,
                        default=Fault.blackhole()).start()
    client = _no_retry_client(proxy.endpoint, timeout=1.0)
    r = RemoteStorage(client, "bh")
    try:
        t0 = time.monotonic()
        with pytest.raises(serrors.StorageError):
            r.append_file("vol1", "f", b"x" * 50_000)
        assert time.monotonic() - t0 < 10.0       # deadline, not a hang
        assert client.breaker._failures > 0       # fed the breaker
        # the real peer never applied anything
        with pytest.raises(serrors.FileNotFound):
            drive.read_file_stream("vol1", "f", 0, 1)
    finally:
        proxy.stop()
        rpc.stop()


def test_locktrace_drill_peer_kill_graph_stays_acyclic(tmp_path,
                                                       monkeypatch):
    """Concurrency-analysis chaos drill: a full 3-node cluster built
    with lock tracing ON takes a peer kill + return under concurrent
    PUT/GET workers and heals back — and the lock-order graph every
    mutex recorded along the way (writer planes, dsync, breakers,
    egress, metacache, the memory governor) must come out ACYCLIC
    with zero long-hold violations.  The AB/BA canary in
    tests/test_locktrace.py proves the detector would have caught an
    inversion; this drill proves the real data plane does not have
    one on the peer-death path."""
    from minio_tpu.soak.slo import assert_converged
    from minio_tpu.storage.remote import register_storage_service
    from minio_tpu.utils import locktrace
    monkeypatch.setenv("MT_RPC_BREAKER_FAILURES", "2")
    monkeypatch.setenv("MT_RPC_BREAKER_COOLDOWN", "200ms")
    monkeypatch.setenv("MT_RPC_RETRY_ATTEMPTS", "1")
    from minio_tpu.cluster import NodeSpec, start_cluster
    was = locktrace.enabled()
    locktrace.enable()
    locktrace.reset()
    nodes = []
    try:
        specs = []
        for n in range(3):
            dirs = []
            for d in range(2):
                p = tmp_path / f"lt{n}d{d}"
                p.mkdir()
                dirs.append(str(p))
            specs.append(NodeSpec(node_id=f"ltnode{n}",
                                  drive_dirs=dirs))
        nodes = start_cluster(specs, "testsecret", set_drive_count=6)
        layer0 = nodes[0].layer
        layer0.make_bucket("ltchaos")
        stop = threading.Event()

        def worker(wi):
            i = 0
            while not stop.is_set():
                key = f"w{wi}-{i % 4}"
                try:
                    layer0.put_object("ltchaos", key,
                                      os.urandom(32 * 1024))
                    layer0.get_object("ltchaos", key)
                except Exception:  # noqa: BLE001 — faults are the
                    pass           # point; SLO is the graph below
                i += 1

        threads = [threading.Thread(target=worker, args=(wi,),
                                    daemon=True,
                                    name=f"mt-test-ltw-{wi}")
                   for wi in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        victim_port = nodes[2].rpc.port
        nodes[2].rpc.stop()            # peer dies mid-traffic
        time.sleep(0.8)
        srv2 = RPCServer("testsecret", port=victim_port)
        register_storage_service(srv2, nodes[2].drives)
        register_lock_service(srv2, nodes[2].locker)
        srv2.start()                   # ...and comes back
        try:
            time.sleep(0.5)
            stop.set()
            for t in threads:
                t.join(10)
            assert_converged(layer0, timeout_s=30.0)
        finally:
            srv2.stop()
        # the acceptance assertion: real traffic + a fault timeline
        # were traced (non-vacuous) and produced no potential deadlock
        # and no long holds under contention
        assert locktrace.acquire_count() > 500, \
            locktrace.acquire_count()
        summary = locktrace.assert_acyclic()
        assert summary["long_holds"] == 0
    finally:
        stop_err = None
        for node in nodes:
            try:
                node.stop()
            except Exception as e:  # noqa: BLE001 — drill teardown
                stop_err = e
        if not was:
            locktrace.disable()
        # reset in the FINALLY: a failed assertion above must not leak
        # the recorded graph into later suites' scrape idle contracts
        locktrace.reset()
        assert stop_err is None, stop_err


# -- TLS chaos drills (ISSUE 13: faults mid-handshake + mid-encrypted-frame)


def _tls_manager(tmp_path_factory):
    from tests._pki import cluster_pki
    return cluster_pki(tmp_path_factory).cert_manager()


def test_tls_reset_mid_handshake_is_transport_failure(
        tmp_path_factory):
    """The proxy RSTs the connection in the middle of the TLS
    handshake: the client surfaces a typed transport RPCError (never a
    hang, never a protocol-layer crash) and the breaker is fed."""
    from minio_tpu.secure import transport as secure_transport
    mgr = _tls_manager(tmp_path_factory)
    srv = RPCServer("tls-chaos", tls=mgr)
    srv.register("t", {"echo": lambda x: x})
    srv.start()
    secure_transport.configure(mgr)
    # cut after 64 relayed bytes — inside the ClientHello/ServerHello
    # exchange, long before any HTTP bytes exist
    proxy = FaultyProxy("127.0.0.1", srv.port,
                        default=Fault.reset(after_bytes=64)).start()
    try:
        c = _no_retry_client(proxy.endpoint.replace("http://",
                                                    "https://"),
                             fail_max=1)
        c.secret = "tls-chaos"
        with pytest.raises(RPCError):
            c.call("t", "echo", x=1)
        assert c.breaker.state == CircuitBreaker.OPEN
    finally:
        proxy.stop()
        srv.stop()
        secure_transport.configure(None)


def test_tls_blackhole_mid_handshake_hits_deadline(tmp_path_factory):
    """A blackholed peer swallows the ClientHello and never answers:
    the client's deadline converts the stalled handshake into a typed
    transport RPCError within the timeout, not a parked thread."""
    from minio_tpu.secure import transport as secure_transport
    mgr = _tls_manager(tmp_path_factory)
    srv = RPCServer("tls-chaos-bh", tls=mgr)
    srv.start()
    secure_transport.configure(mgr)
    proxy = FaultyProxy("127.0.0.1", srv.port,
                        default=Fault.blackhole()).start()
    try:
        c = _no_retry_client(proxy.endpoint.replace("http://",
                                                    "https://"),
                             fail_max=1, timeout=1.0)
        c.secret = "tls-chaos-bh"
        t0 = time.monotonic()
        with pytest.raises(RPCError):
            c.call("t", "echo", x=1)
        assert time.monotonic() - t0 < 10.0
        assert c.breaker._failures > 0
    finally:
        proxy.stop()
        srv.stop()
        secure_transport.configure(None)


def test_tls_stream_reset_mid_encrypted_frame_quorum_commits(
        tmp_path, tmp_path_factory, monkeypatch):
    """The mid-frame reset drill ON THE ENCRYPTED CHANNEL: 4 local +
    2 remote TLS drives, the proxy RSTs every new connection carrying
    streamed frames — the half-streamed appends latch as transport
    failures in the writer plane, the PUT commits on the 4/6 local
    quorum, and NO partial shard is visible on the faulted remotes.
    Byte-for-byte the plaintext drill's contract, over mTLS."""
    import hashlib as _hashlib
    import io as _io

    from minio_tpu.objectlayer import erasure_object as eo
    from minio_tpu.objectlayer.erasure_object import ErasureObjects
    from minio_tpu.parallel.rpc import STREAM
    from minio_tpu.secure import transport as secure_transport
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    from minio_tpu.storage.writers import close_write_planes
    from minio_tpu.storage.xl_storage import XLStorage
    monkeypatch.setattr(STREAM, "enable", True)
    monkeypatch.setattr(STREAM, "chunk_bytes", 1024)
    monkeypatch.setattr(STREAM, "_loaded", True)
    monkeypatch.setattr(eo, "STREAM_BATCH_BYTES", 2 * 4096)
    mgr = _tls_manager(tmp_path_factory)
    secure_transport.configure(mgr)
    rpc = RPCServer("tls-stream-chaos", tls=mgr)
    remote_drives = {}
    for i in range(2):
        d = tmp_path / f"tr{i}"
        d.mkdir()
        remote_drives[f"r{i}"] = XLStorage(str(d))
    register_storage_service(rpc, remote_drives)
    rpc.start()
    proxy = FaultyProxy("127.0.0.1", rpc.port).start()
    disks = []
    for i in range(4):
        d = tmp_path / f"tl{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    remotes = [_no_retry_client(
        proxy.endpoint.replace("http://", "https://"), timeout=2.0)
        for _ in range(2)]
    for i, c in enumerate(remotes):
        c.secret = "tls-stream-chaos"
        disks.append(RemoteStorage(c, f"r{i}"))
    lay = ErasureObjects(disks, parity=2, block_size=4096,
                         backend="numpy", inline_threshold=512)
    lay._pipe_depth = 2
    lay.make_bucket("tlsbkt")
    try:
        body = (b"tls-frame-chaos!" * 4096)[: 10 * 4096]
        # healthy encrypted pass: streamed appends reach the remotes
        oi = lay.put_object_stream("tlsbkt", "ok", _io.BytesIO(body))
        assert oi.etag == _hashlib.md5(body).hexdigest()
        assert remote_drives["r0"].read_all(
            "tlsbkt", "ok/xl.meta") is not None
        # now every NEW connection dies mid-stream (RST inside the
        # encrypted frame sequence) and the pools are dropped
        proxy.set_default(Fault.reset(after_bytes=0))
        _drop_pools(remotes)
        from minio_tpu.admin.metrics import GLOBAL
        errs0 = sum(v for k, v in GLOBAL.snapshot().items()
                    if k[0] == "mt_node_rpc_errors_total")
        oi = lay.put_object_stream("tlsbkt", "cut", _io.BytesIO(body))
        assert oi.etag == _hashlib.md5(body).hexdigest()
        assert lay.get_object("tlsbkt", "cut")[1] == body
        errs1 = sum(v for k, v in GLOBAL.snapshot().items()
                    if k[0] == "mt_node_rpc_errors_total")
        assert errs1 > errs0
        for i in range(2):
            assert not os.path.exists(
                os.path.join(str(tmp_path / f"tr{i}"), "tlsbkt",
                             "cut", "xl.meta"))
    finally:
        close_write_planes(lay)
        proxy.stop()
        rpc.stop()
        secure_transport.configure(None)

def test_aborted_request_keeps_stage_vector(s3_server):
    """Satellite drill (ISSUE 17): a request that dies mid-body —
    client disconnect / wire reset — must still complete its
    flight-recorder record WITH the stage vector and an ``aborted``
    marker, landing in the error ring where breach forensics look.
    Two legs: a GET whose response is RST mid-body by FaultyProxy,
    and a PUT whose client RSTs mid-request-body."""
    import http.client
    import struct

    from minio_tpu.s3.client import S3Client
    from minio_tpu.s3.sigv4 import Credentials, sign_request
    srv = s3_server
    c = S3Client(srv.endpoint, "testkey", "testsecret")
    c.make_bucket("chab")
    # big enough that the response cannot hide in kernel socket
    # buffers: the proxy stops reading after the reset budget, so the
    # server's body_write must block and then fail on the RST
    data = os.urandom(32 << 20)
    # staged past the front: the fixture's 1 s body deadline is the
    # subject of leg 2, and cuts a 32 MiB upload on a loaded host
    srv.layer.put_object("chab", "big", data)

    def newest_abort(api):
        for r in srv.flightrec.query(errors_only=True, limit=50):
            if r["api"] == api and \
                    r.get("error", "").startswith("aborted:"):
                return r
        return None

    def wait_abort(api):
        deadline = time.monotonic() + 10.0
        rec = None
        while rec is None and time.monotonic() < deadline:
            rec = newest_abort(api)
            if rec is None:
                time.sleep(0.05)
        return rec

    # -- leg 1: response dies mid-body (FaultyProxy reset) ------------
    proxy = FaultyProxy("127.0.0.1", srv.port).start()
    try:
        path = "/chab/big"
        # sign against the REAL endpoint; send through the proxy,
        # which RSTs the client after 64 KiB of response — the server
        # hits a ConnectionError mid-body_write
        hdrs = sign_request(Credentials("testkey", "testsecret"),
                            "GET", srv.endpoint + path, {}, b"",
                            "us-east-1")
        proxy.program(proxy.connections_seen() + 1,
                      Fault.reset(after_bytes=64 * 1024))
        conn = http.client.HTTPConnection("127.0.0.1", proxy.port,
                                          timeout=30)
        try:
            conn.request("GET", path, headers=hdrs)
            with pytest.raises((ConnectionError,
                                http.client.HTTPException,
                                TimeoutError, OSError)):
                resp = conn.getresponse()
                while resp.read(65536):
                    pass
                raise ConnectionResetError("stream ended short")
        finally:
            conn.close()
    finally:
        proxy.stop()
    rec = wait_abort("GetObject")
    assert rec is not None, srv.flightrec.query(errors_only=True,
                                                limit=10)
    assert rec["stages"], rec          # stage vector survived

    # -- leg 2: request dies mid-body (client RST) --------------------
    body = os.urandom(1 << 20)
    path2 = "/chab/dead"
    hdrs2 = sign_request(Credentials("testkey", "testsecret"),
                         "PUT", srv.endpoint + path2, {}, body,
                         "us-east-1")
    s = socket.create_connection(("127.0.0.1", srv.port))
    try:
        req = [f"PUT {path2} HTTP/1.1\r\n".encode(),
               f"Host: 127.0.0.1:{srv.port}\r\n".encode(),
               f"Content-Length: {len(body)}\r\n".encode()]
        for k, v in hdrs2.items():
            if k.lower() in ("host", "content-length"):
                continue
            req.append(f"{k}: {v}\r\n".encode())
        req.append(b"\r\n")
        s.sendall(b"".join(req))
        s.sendall(body[: len(body) // 2])
        # RST, not FIN: SO_LINGER(1, 0) makes close() send a reset so
        # the server's body read raises ConnectionResetError
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
    finally:
        s.close()
    rec2 = wait_abort("PutObject")
    assert rec2 is not None, srv.flightrec.query(errors_only=True,
                                                 limit=10)
    assert rec2["status"] == 499, rec2   # no status had been sent
    assert rec2["stages"], rec2

"""Multi-node cluster tests — the localhost distributed harness
(mirrors SURVEY.md §4 'multi-node without a cluster':
storage RPC loopback + dsync against live lock servers +
verify-healing.sh-style kill-a-node flows, in-process)."""

import threading
import time

import pytest

from minio_tpu.cluster import NodeSpec, start_cluster
from minio_tpu.objectlayer import healing
from minio_tpu.objectlayer.interface import ObjectNotFound
from minio_tpu.parallel.dsync import (DRWMutex, LocalLocker, LockTimeout,
                                      NamespaceLock)
from minio_tpu.parallel.rpc import RPCClient, RPCError, RPCServer, mint_token
from minio_tpu.storage import errors as serrors
from minio_tpu.storage.remote import RemoteStorage, register_storage_service
from minio_tpu.storage.xl_storage import XLStorage

BS = 64 * 1024


# -- RPC layer -------------------------------------------------------------

def test_rpc_auth_and_errors(tmp_path):
    srv = RPCServer("s3cret")
    srv.register("echo", {"hi": lambda x: x * 2,
                          "boom": lambda: (_ for _ in ()).throw(
                              ValueError("nope"))})
    srv.start()
    try:
        c = RPCClient(srv.endpoint, "s3cret")
        assert c.call("echo", "hi", x=21) == 42
        with pytest.raises(RPCError) as ei:
            c.call("echo", "boom")
        assert ei.value.error_type == "ValueError"
        bad = RPCClient(srv.endpoint, "wrong-secret")
        with pytest.raises(RPCError) as ei:
            bad.call("echo", "hi", x=1)
        assert ei.value.error_type == "AuthError"
        with pytest.raises(RPCError) as ei:
            c.call("echo", "missing")
        assert ei.value.error_type == "NoSuchMethod"
    finally:
        srv.stop()


def test_remote_storage_full_surface(tmp_path):
    (tmp_path / "d0").mkdir()
    local = XLStorage(str(tmp_path / "d0"))
    srv = RPCServer("k")
    register_storage_service(srv, {"drive0": local})
    srv.start()
    try:
        remote = RemoteStorage(RPCClient(srv.endpoint, "k"), "drive0")
        remote.make_vol("bkt")
        remote.write_all("bkt", "a/b", b"hello")
        assert remote.read_all("bkt", "a/b") == b"hello"
        assert remote.read_file_stream("bkt", "a/b", 1, 3) == b"ell"
        assert remote.stat_info_file("bkt", "a/b") == 5
        assert [v.name for v in remote.list_vols()] == ["bkt"]
        with pytest.raises(serrors.FileNotFound):
            remote.read_all("bkt", "missing")
        with pytest.raises(serrors.VolumeNotFound):
            remote.stat_vol("nope")
        # metadata ops cross the wire typed
        from minio_tpu.storage.datatypes import ErasureInfo, FileInfo, now_ns
        fi = FileInfo(version_id="v1", data_dir="dd", mod_time=now_ns(),
                      size=10,
                      erasure=ErasureInfo(data_blocks=1, parity_blocks=1,
                                          block_size=BS, index=1,
                                          distribution=[1, 2]))
        remote.write_metadata("bkt", "obj", fi)
        got = remote.read_version("bkt", "obj")
        assert got.version_id == "v1" and got.erasure.distribution == [1, 2]
        assert local.read_version("bkt", "obj").version_id == "v1"
    finally:
        srv.stop()


def test_rpc_connection_pooling(tmp_path):
    """Calls reuse keep-alive connections (cmd/rest/client.go:114 shared
    persistent transport) instead of a TCP handshake per call."""
    srv = RPCServer("p00l")
    srv.register("echo", {"hi": lambda x: x})
    srv.start()
    try:
        c = RPCClient(srv.endpoint, "p00l")
        assert c.call("echo", "hi", x=1) == 1
        assert len(c._pool) == 1
        conn1 = c._pool[0]
        for i in range(5):
            assert c.call("echo", "hi", x=i) == i
        assert len(c._pool) == 1
        assert c._pool[0] is conn1, "connection was not reused"
    finally:
        srv.stop()


def test_rpc_stale_pooled_connection_retries(tmp_path):
    """A peer restart invalidates pooled connections; the next call
    retries on a fresh connection instead of flapping the peer offline."""
    srv = RPCServer("st4le")
    srv.register("echo", {"hi": lambda x: x})
    srv.start()
    port = srv.port
    c = RPCClient(srv.endpoint, "st4le")
    assert c.call("echo", "hi", x=7) == 7
    assert len(c._pool) == 1
    srv.stop()
    # restart on the SAME port: pooled conn is now stale
    srv2 = RPCServer("st4le", port=port)
    srv2.register("echo", {"hi": lambda x: x})
    srv2.start()
    try:
        # idempotent calls retry transparently across the restart
        assert c.call("echo", "hi", _idempotent=True, x=8) == 8
        assert c.is_online()
    finally:
        srv2.stop()


def test_raw_shard_transfer_roundtrip(tmp_path):
    """Bulk shard bodies ride raw HTTP bodies (no msgpack double copy):
    create/append/read_file_stream over the raw endpoints."""
    (tmp_path / "rd0").mkdir()
    local = XLStorage(str(tmp_path / "rd0"))
    srv = RPCServer("r4w")
    register_storage_service(srv, {"drive0": local})
    srv.start()
    try:
        remote = RemoteStorage(RPCClient(srv.endpoint, "r4w"), "drive0")
        remote.make_vol("rawbkt")
        blob1 = bytes(range(256)) * 100
        blob2 = blob1[::-1]
        remote.create_file("rawbkt", "big/shard", blob1)
        remote.append_file("rawbkt", "big/shard", blob2)
        assert remote.read_file_stream("rawbkt", "big/shard", 0,
                                       len(blob1)) == blob1
        assert remote.read_file_stream(
            "rawbkt", "big/shard", len(blob1), len(blob2)) == blob2
        # typed errors still cross the raw path
        with pytest.raises(serrors.FileNotFound):
            remote.read_file_stream("rawbkt", "nope", 0, 10)
        # size-mismatch guard survives the transport
        with pytest.raises(serrors.FileCorrupt):
            remote.create_file("rawbkt", "sized", b"abc", file_size=99)
    finally:
        srv.stop()


# -- dsync -----------------------------------------------------------------

def test_drw_mutex_local_exclusion():
    lockers = [LocalLocker() for _ in range(3)]
    a = DRWMutex(lockers, "res")
    b = DRWMutex(lockers, "res")
    a.lock(write=True)
    with pytest.raises(LockTimeout):
        b.lock(write=True, timeout=0.1)
    a.unlock()
    b.lock(write=True, timeout=1.0)
    b.unlock()


def test_drw_mutex_read_sharing():
    lockers = [LocalLocker() for _ in range(3)]
    r1 = DRWMutex(lockers, "res")
    r2 = DRWMutex(lockers, "res")
    r1.lock(write=False)
    r2.lock(write=False, timeout=0.5)   # shared readers coexist
    w = DRWMutex(lockers, "res")
    with pytest.raises(LockTimeout):
        w.lock(write=True, timeout=0.1)
    r1.unlock()
    r2.unlock()
    w.lock(write=True, timeout=1.0)
    w.unlock()


def test_drw_mutex_quorum_with_dead_locker():
    class DeadLocker:
        def lock(self, *a, **kw):
            raise RPCError("ConnectionError", "down")

        def unlock(self, *a, **kw):
            raise RPCError("ConnectionError", "down")

    lockers = [LocalLocker(), LocalLocker(), DeadLocker()]
    m = DRWMutex(lockers, "res")
    m.lock(write=True, timeout=1.0)     # 2-of-3 quorum holds
    m.unlock()


def test_lock_ttl_expiry_frees_crashed_holder():
    """A holder that stops refreshing (crash analog) loses its grants
    after one TTL; another client acquires (drwmutex refresh +
    local-locker expiry, pkg/dsync/drwmutex.go:143-321)."""
    lockers = [LocalLocker(default_ttl_s=0.3) for _ in range(3)]
    crashed = DRWMutex(lockers, "res", ttl_s=0.3)
    crashed.lock(write=True)
    # simulate kill -9: the shared refresher forgets this holder
    from minio_tpu.parallel.dsync import _REFRESHER
    _REFRESHER.remove(crashed)

    waiter = DRWMutex(lockers, "res", ttl_s=0.3)
    t0 = time.monotonic()
    waiter.lock(write=True, timeout=5.0)   # steals after expiry
    took = time.monotonic() - t0
    assert took < 2.0, f"stole only after {took:.2f}s"
    waiter.unlock()


def test_lock_refresh_keeps_long_holders_alive():
    """An alive holder's refresh thread extends the TTL indefinitely —
    long operations are never stolen from."""
    lockers = [LocalLocker(default_ttl_s=0.3) for _ in range(3)]
    holder = DRWMutex(lockers, "res", ttl_s=0.3)
    holder.lock(write=True)
    time.sleep(1.0)      # several TTLs pass while refreshing
    thief = DRWMutex(lockers, "res", ttl_s=0.3)
    with pytest.raises(LockTimeout):
        thief.lock(write=True, timeout=0.2)
    holder.unlock()
    thief.lock(write=True, timeout=1.0)
    thief.unlock()


def test_lock_acquisition_is_concurrent_not_serial():
    """Fan-out is concurrent with per-locker timeouts: two slow lockers
    cost max(delay), not sum (drwmutex.go:207-297)."""
    class SlowLocker(LocalLocker):
        def lock(self, *a, **kw):
            time.sleep(0.4)
            return super().lock(*a, **kw)

    lockers = [SlowLocker(), SlowLocker(), LocalLocker()]
    m = DRWMutex(lockers, "res")
    t0 = time.monotonic()
    m.lock(write=True, timeout=5.0)
    took = time.monotonic() - t0
    m.unlock()
    assert took < 0.75, f"serial fan-out suspected: {took:.2f}s"


def test_lock_lost_surfaces_to_holder():
    """A holder whose grants expire under it (pause > TTL) must see
    LockLost at the commit point instead of silently double-writing."""
    from minio_tpu.parallel.dsync import LockLost
    lockers = [LocalLocker(default_ttl_s=0.2) for _ in range(3)]
    holder = DRWMutex(lockers, "res", ttl_s=0.2)
    holder.lock(write=True)
    # simulate a long GC/VM pause: stop refreshing, let grants expire,
    # let a competitor take the lock
    from minio_tpu.parallel.dsync import _REFRESHER
    _REFRESHER.remove(holder)
    thief = DRWMutex(lockers, "res", ttl_s=0.2)
    thief.lock(write=True, timeout=5.0)
    # resume the holder's refresh: the next round sees < quorum grants
    holder._do_refresh()
    deadline = time.monotonic() + 2.0
    while not holder.lost.is_set() and time.monotonic() < deadline:
        time.sleep(0.02)
    with pytest.raises(LockLost):
        holder.ensure_valid()
    thief.unlock()
    holder.unlock()


def test_locker_expiry_sweep():
    lk = LocalLocker(default_ttl_s=0.1)
    assert lk.lock("a", "uid1", True)
    assert lk.lock("b", "uid2", False)
    time.sleep(0.15)
    assert lk.expire_old_locks() == 2
    assert not lk.is_locked("a") and not lk.is_locked("b")


# -- full cluster ----------------------------------------------------------

@pytest.fixture
def cluster(tmp_path):
    specs = []
    for n in range(3):
        dirs = []
        for d in range(2):
            p = tmp_path / f"node{n}-drive{d}"
            p.mkdir()
            dirs.append(str(p))
        specs.append(NodeSpec(f"node{n}", dirs))
    nodes = start_cluster(specs, "cluster-secret", set_drive_count=6,
                          parity=2, block_size=BS, backend="numpy")
    yield nodes
    for node in nodes:
        node.stop()


def test_cluster_put_get_across_nodes(cluster):
    n0, n1, n2 = cluster
    n0.layer.make_bucket("bkt")
    data = bytes(range(256)) * 600
    n0.layer.put_object("bkt", "shared-object", data)
    # every node serves the object, reading shards over the wire
    for node in (n1, n2):
        _, got = node.layer.get_object("bkt", "shared-object")
        assert got == data
    # every node agrees on listing
    assert [o.name for o in n2.layer.list_objects("bkt").objects] == \
        ["shared-object"]


def test_cluster_node_serves_storage_and_locks_only(cluster):
    """A node's internode listener carries the storage and lock
    services and no other raw route: there is no codec service for
    chip-less peers (nothing could construct its client; removed in
    PR 29)."""
    for node in cluster:
        assert set(node.rpc._raw) == {"storage-write", "storage-read"}
        assert not [n for n in node.rpc._raw_stream
                    if n.startswith("codec")]


def test_cluster_survives_node_loss(cluster):
    n0, n1, n2 = cluster
    n0.layer.make_bucket("bkt")
    data = b"fault-tolerant-payload" * 1000
    n0.layer.put_object("bkt", "obj", data)
    # kill node 1 (takes 2 of 6 drives offline; parity=2 suffices)
    n1.stop()
    _, got = n0.layer.get_object("bkt", "obj")
    assert got == data
    # writes still reach quorum (4 of 6 drives >= write quorum 4)
    n0.layer.put_object("bkt", "obj2", b"written-degraded")
    _, got = n2.layer.get_object("bkt", "obj2")
    assert got == b"written-degraded"


def test_cluster_heal_after_node_wipe(cluster, tmp_path):
    import shutil
    n0, n1, n2 = cluster
    n0.layer.make_bucket("bkt")
    data = bytes(range(256)) * 300
    n0.layer.put_object("bkt", "heal-me", data)
    # wipe node2's drives (simulates disk replacement on that host)
    for d in n2.spec.drive_dirs:
        shutil.rmtree(f"{d}/bkt", ignore_errors=True)
    er = n0.layer.get_hashed_set("heal-me")
    res = healing.heal_object(er, "bkt", "heal-me")
    assert res.after_ok == 6
    _, got = n2.layer.get_object("bkt", "heal-me")
    assert got == data


def test_cluster_distributed_lock_exclusion(cluster):
    n0, n1, _ = cluster
    l0 = n0.layer.sets[0].ns_lock.new_lock("bkt", "obj")
    l1 = n1.layer.sets[0].ns_lock.new_lock("bkt", "obj")
    l0.lock(write=True)
    with pytest.raises(LockTimeout):
        l1.lock(write=True, timeout=0.2)
    l0.unlock()
    l1.lock(write=True, timeout=2.0)
    l1.unlock()


def test_dynamic_timeout_adapts():
    """cmd/dynamic-timeouts.go analog: successes shrink the deadline
    toward observed latency, failures grow it, both bounded."""
    from minio_tpu.parallel.rpc import DynamicTimeout, RPCClient
    dt = DynamicTimeout(initial=30.0, minimum=1.0, maximum=120.0,
                        window=4)
    for _ in range(16):                      # fast link: 50ms calls
        dt.log_success(0.05)
    assert dt.timeout() < 10.0               # shrank toward 4x observed
    fast = dt.timeout()
    for _ in range(20):
        dt.log_failure()
    assert dt.timeout() == 120.0             # grew to the bound
    for _ in range(64):
        dt.log_success(0.05)
    assert dt.timeout() < 10.0               # recovers after failures
    assert dt.timeout() >= 1.0
    del fast
    # per-service trackers: storage keeps a higher floor than lock/ping
    c = RPCClient("http://127.0.0.1:1", "s")
    for _ in range(64):
        c._dyn_for("storage").log_success(0.01)
        c._dyn_for("lock").log_success(0.01)
    assert c._dyn_for("storage").timeout() >= 10.0
    assert c._dyn_for("lock").timeout() < 10.0

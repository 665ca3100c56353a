"""BucketMetadataSys remembers the EMPTY answer of its quorum read
(objectlayer/bucket_meta.py): a bucket nobody configured costs one drive
fan-out, not one per lookup; the cached answer is bounded, evicted by
every change, aged by the existence TTL, and a change one node
acknowledged is in force on its reachable peers when it returns
(parallel/peer.py ``bucket_meta_changed``).
"""

import json
import random
import socket
import time

import pytest

from minio_tpu.admin import metrics
from minio_tpu.objectlayer import bucket_meta
from minio_tpu.objectlayer.bucket_meta import BucketMetadataSys
from minio_tpu.objectlayer.erasure_object import (BUCKET_TTL_S,
                                                  ErasureObjects)
from minio_tpu.parallel.peer import PeerNotifier, register_peer_service
from minio_tpu.parallel.rpc import RPCClient, RPCServer
from minio_tpu.s3.client import S3Client, S3ClientError
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl_storage import XLStorage


def _layer(tmp_path):
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir(exist_ok=True)
        disks.append(XLStorage(str(d)))
    return ErasureObjects(disks, parity=2, block_size=64 * 1024,
                          backend="numpy")


class _Counted:
    """A layer whose ``_fanout`` calls are counted."""

    def __init__(self, inner):
        self._inner = inner
        self.fanouts = 0

    def _fanout(self, fn):
        self.fanouts += 1
        return self._inner._fanout(fn)


class _NoDrives:
    """Four drives that hold nothing, without the file system."""

    def __init__(self):
        self.fanouts = 0

    def _fanout(self, fn):
        self.fanouts += 1
        return [None] * 4, [FileNotFoundError()] * 4


class _Clock:
    def __init__(self, monkeypatch, start=1000.0):
        self.t = start
        monkeypatch.setattr(bucket_meta, "_now", lambda: self.t)


def _lookups(result):
    return metrics.GLOBAL.snapshot().get(
        ("mt_bucket_meta_lookups_total", (("result", result),)), 0.0)


@pytest.fixture
def counted(tmp_path):
    layer = _layer(tmp_path)
    layer.make_bucket("plain")
    er = _Counted(layer)
    return er, BucketMetadataSys(er)


@pytest.mark.parametrize("lookup", [
    lambda bm: bm.get_config("plain", "quota"),
    lambda bm: bm.versioning_enabled("plain"),
    lambda bm: bm.get_bucket_policy("plain"),
    lambda bm: bm.get_parsed("plain", "lifecycle", json.loads),
], ids=["get_config", "versioning_enabled", "get_bucket_policy",
        "get_parsed"])
def test_unconfigured_bucket_costs_one_fanout(counted, monkeypatch, lookup):
    er, bm = counted
    _Clock(monkeypatch)         # no entry ages inside the test
    hit0, read0 = _lookups("hit"), _lookups("read")
    for _ in range(50):
        assert not lookup(bm)
    assert er.fanouts == 1
    assert _lookups("read") - read0 == 1
    assert _lookups("hit") - hit0 == 49


def test_family_is_in_the_scrape_from_zero(tmp_path):
    BucketMetadataSys(_NoDrives())
    text = metrics.render()
    assert "# TYPE mt_bucket_meta_lookups_total counter" in text
    for result in ("hit", "read"):
        assert f'mt_bucket_meta_lookups_total{{result="{result}"}} ' in text


@pytest.mark.parametrize("change, served_from_cache, versioned", [
    (lambda bm: bm.update("plain", "quota", "{}"), True, False),
    (lambda bm: bm.set_versioning("plain", True), True, True),
    (lambda bm: bm.drop("plain"), False, False),
    (lambda bm: bm.invalidate("plain"), False, False),
], ids=["update", "set_versioning", "drop", "invalidate"])
def test_every_change_evicts_the_empty_answer(counted, monkeypatch, change,
                                              served_from_cache, versioned):
    """No change leaves "no document" standing.  ``update`` (and
    ``set_versioning`` through it) reads the drives itself, never the
    cached answer, and caches what it wrote; ``drop`` and ``invalidate``
    make the next ``get`` read the drives."""
    er, bm = counted
    _Clock(monkeypatch)
    assert bm.get("plain") == {} and bm.get("plain") == {}
    assert er.fanouts == 1
    change(bm)
    assert "plain" not in bm._empty
    before = er.fanouts
    if served_from_cache:
        assert before >= 3              # its own read and its write
        assert bm.get("plain").get("_rev") == 1
        assert er.fanouts == before
    else:
        assert bm.get("plain") == {}
        assert er.fanouts == before + 1
    assert bm.versioning_enabled("plain") is versioned


def test_update_builds_on_the_drives_not_on_a_cached_empty(tmp_path,
                                                           monkeypatch):
    """B holds "no document" while A configures the bucket; B's own
    first change must extend A's document, not replace it."""
    layer = _layer(tmp_path)
    layer.make_bucket("shared")
    _Clock(monkeypatch)
    a, b = BucketMetadataSys(layer), BucketMetadataSys(layer)
    assert b.get("shared") == {}
    a.set_versioning("shared", True)
    b.set_config("shared", "quota", '{"quota": 1}')
    assert b.versioning_enabled("shared")
    a.invalidate("shared")
    assert a.get_config("shared", "quota") == '{"quota": 1}'
    assert a.versioning_enabled("shared")


def test_empty_answer_is_reread_after_the_existence_ttl(counted,
                                                        monkeypatch):
    er, bm = counted
    clock = _Clock(monkeypatch)
    bm.get("plain")
    clock.t += BUCKET_TTL_S - 0.01
    bm.get("plain")
    assert er.fanouts == 1
    clock.t += 0.02
    bm.get("plain")
    assert er.fanouts == 2
    # the re-read starts a new age
    clock.t += BUCKET_TTL_S - 0.01
    bm.get("plain")
    assert er.fanouts == 2


def test_a_document_is_not_aged(counted, monkeypatch):
    er, bm = counted
    clock = _Clock(monkeypatch)
    bm.set_versioning("plain", True)
    before = er.fanouts
    clock.t += 100 * BUCKET_TTL_S
    assert bm.versioning_enabled("plain")
    assert er.fanouts == before


def test_probes_of_unknown_names_leave_the_cache_bounded(monkeypatch):
    er = _NoDrives()
    bm = BucketMetadataSys(er)
    clock = _Clock(monkeypatch)
    rng = random.Random(28)
    n = 100_000
    for i in range(n):
        assert bm.get("probe-%016x" % rng.getrandbits(64)) == {}
        assert len(bm._empty) <= bm._EMPTY_MAX
        if i % 1000 == 0:
            clock.t += 0.05
    assert er.fanouts == n
    assert not bm._cache and not bm._parsed_cache
    # once the probes stop, the next empty answer sweeps the expired out
    clock.t += BUCKET_TTL_S
    bm.get("one-more")
    assert list(bm._empty) == ["one-more"]


def test_a_read_that_raced_a_change_does_not_store_what_it_saw(tmp_path):
    """get() reads "no document", a change lands before it stores: the
    stale empty answer must not outlive the change."""
    layer = _layer(tmp_path)
    layer.make_bucket("racy")
    other = BucketMetadataSys(layer)

    class _Racing(_Counted):
        def _fanout(self, fn):
            out = super()._fanout(fn)
            if self.fanouts == 1:       # between get's read and its store
                other.set_versioning("racy", True)
                bm.invalidate("racy")   # the peer reload of that change
            return out

    bm = BucketMetadataSys(_Racing(layer))
    assert bm.get("racy") == {}
    assert bm.versioning_enabled("racy")


@pytest.mark.parametrize("how", ["invalidate", "ttl"])
def test_second_sys_sees_first_versioning(tmp_path, monkeypatch, how):
    """Two nodes' metadata systems over the same drives: B sees A's
    FIRST configuration after the peer reload, and without it once the
    empty answer has aged out."""
    layer = _layer(tmp_path)
    layer.make_bucket("shared")
    clock = _Clock(monkeypatch)
    a, b = BucketMetadataSys(layer), BucketMetadataSys(layer)
    assert not b.versioning_enabled("shared")
    a.set_versioning("shared", True)
    assert a.versioning_enabled("shared")
    assert not b.versioning_enabled("shared")   # B's cached "no document"
    if how == "invalidate":
        b.invalidate("shared")
    else:
        clock.t += BUCKET_TTL_S + 0.01
    assert b.versioning_enabled("shared")


# -- served nodes ----------------------------------------------------------

DENY = json.dumps({
    "Version": "2012-10-17",
    "Statement": [{"Effect": "Deny", "Principal": "*",
                   "Action": ["s3:GetObject"],
                   "Resource": ["arn:aws:s3:::guarded/*"]}]})


def _closed_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def two_nodes(tmp_path):
    """Nodes A and B over the same four drives; A's notifier reaches B's
    RPC plane and a third peer that is down."""
    node_a = S3Server(_layer(tmp_path), access_key="ck", secret_key="cs")
    node_b = S3Server(_layer(tmp_path), access_key="ck", secret_key="cs")
    node_a.start()
    node_b.start()
    rpc_b = RPCServer("peer-secret")
    register_peer_service(rpc_b, node_b)
    rpc_b.start()
    node_a.attach_peers(PeerNotifier([
        RPCClient(rpc_b.endpoint, "peer-secret"),
        RPCClient(f"http://127.0.0.1:{_closed_port()}", "peer-secret")]))
    try:
        yield node_a, node_b
    finally:
        node_a.stop()
        node_b.stop()
        rpc_b.stop()


def test_first_deny_acknowledged_by_a_refuses_next_request_on_b(
        two_nodes, monkeypatch):
    node_a, node_b = two_nodes
    _Clock(monkeypatch)     # frozen: only the peer reload can evict on B
    ca = S3Client(node_a.endpoint, "ck", "cs")
    cb = S3Client(node_b.endpoint, "ck", "cs")
    ca.make_bucket("guarded")
    ca.put_object("guarded", "k", b"v")
    assert cb.get_object("guarded", "k").body == b"v"
    assert "guarded" in node_b.bucket_meta._empty
    t0 = time.monotonic()
    ca.request("PUT", "/guarded", "policy", DENY.encode())
    took = time.monotonic() - t0
    for c in (cb, ca):
        with pytest.raises(S3ClientError) as ei:
            c.get_object("guarded", "k")
        assert ei.value.code == "AccessDenied"
    # the peer that is down neither failed A's PUT nor held it beyond
    # the RPC client's own deadline (connection refused + its retries)
    assert took < 10.0
    # a later change of the now non-empty document is as prompt
    ca.request("DELETE", "/guarded", "policy")
    assert cb.get_object("guarded", "k").body == b"v"


def test_delete_bucket_evicts_here_and_on_the_peer(two_nodes, monkeypatch):
    node_a, node_b = two_nodes
    _Clock(monkeypatch)
    ca = S3Client(node_a.endpoint, "ck", "cs")
    cb = S3Client(node_b.endpoint, "ck", "cs")
    ca.make_bucket("gone")
    ca.set_versioning("gone", True)
    assert node_b.bucket_meta.versioning_enabled("gone")
    ca.delete_bucket("gone")
    assert "gone" not in node_a.bucket_meta._cache
    assert "gone" not in node_b.bucket_meta._cache
    cb.make_bucket("gone")
    assert not node_b.bucket_meta.versioning_enabled("gone")
    assert not node_a.bucket_meta.versioning_enabled("gone")


def test_versioning_on_a_written_bucket_takes_effect_on_next_put(tmp_path):
    srv = S3Server(_layer(tmp_path), access_key="ck", secret_key="cs")
    srv.start()
    try:
        c = S3Client(srv.endpoint, "ck", "cs")
        c.make_bucket("late")
        r = c.put_object("late", "k", b"one")
        assert "x-amz-version-id" not in r.headers
        assert "late" in srv.bucket_meta._empty
        c.set_versioning("late", True)
        r = c.put_object("late", "k", b"two")
        assert r.headers.get("x-amz-version-id")
    finally:
        srv.stop()

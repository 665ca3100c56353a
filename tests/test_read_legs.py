"""The read side's spans (ISSUE 36): after one HEAD, GET or DELETE
against a served 16-drive set, two scrapes of ``/minio-tpu/metrics``
show the quorum metadata read (``mt_read_leg_seconds{op=meta}``), the
shard read fan-out's children (``queue`` / ``gather``, the drive call in
``mt_drive_call_seconds``, ``get.verify``) and the two host copies, each
counted as many times as the code ran it; a leg's CPU twin never reads
over its wall; a remote drive's call is timed with no subscriber
connected; a streaming PUT's body arrival is stage ``body_read``.  The
serial-vector reconciliation of HeadObject / GetObject / DeleteObject
is with the other reconciliation cases, in tests/test_xray.py.
"""

import http.client
import json
import os
import re
import time

import pytest

from minio_tpu.admin.metrics import KERNEL_BUCKETS, Metrics
from minio_tpu.objectlayer import hotread
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.obs import trace
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl_storage import XLStorage

N, K = 16, 12
BS = 256 * 1024
BKT = "legsbkt"
# one object of each on-disk form (inline <= 128 KiB < packed <= 1 MiB
# < part file), each several blocks of BS but one read batch
SIZES = {"plain": 1_500_123, "inline": 50_000, "packed": 400_000}

_SAMPLE = re.compile(r"^(\w+)(?:\{(.*)\})? (\S+)$")
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def _scrape(srv) -> dict:
    """{(sample name, frozenset of labels)} -> value, of one scrape."""
    host, port = srv.endpoint.replace("http://", "").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    conn.request("GET", "/minio-tpu/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    out = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m and not line.startswith("#"):
            out[(m.group(1),
                 frozenset(_LABEL.findall(m.group(2) or "")))] = \
                float(m.group(3))
    return out


def _rose(s0: dict, s1: dict, name: str, **labels) -> float:
    """after - before, summed over the samples of ``name`` whose labels
    include ``labels``."""
    want = set(labels.items())

    def total(s):
        return sum(v for (n, ls), v in s.items()
                   if n == name and want <= ls)
    return total(s1) - total(s0)


def _newest_record(c, api: str, stage: str) -> dict:
    """The newest flight-recorder row of ``api`` once it names
    ``stage`` (rows land after the response bytes go out)."""
    deadline = time.monotonic() + 2.0
    while True:
        recs = json.loads(c.request(
            "GET", "/minio-tpu/admin/v1/xray",
            f"api={api}&n=1").body)["records"]
        if recs and stage in recs[0]["stages"]:
            return recs[0]
        assert time.monotonic() < deadline, recs
        time.sleep(0.01)


@pytest.fixture(scope="module", params=["numpy", "tpu"])
def served(request, tmp_path_factory):
    root = tmp_path_factory.mktemp(f"legs-{request.param}")
    disks = []
    for i in range(N):
        d = root / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, block_size=BS, backend=request.param)
    srv = S3Server(layer, access_key="lk", secret_key="ls")
    srv.start()
    S3Client(srv.endpoint, "lk", "ls").make_bucket(BKT)
    yield srv
    srv.stop()


def _put(srv, key: str, form: str) -> bytes:
    body = os.urandom(SIZES[form])
    S3Client(srv.endpoint, "lk", "ls").put_object(BKT, key, body)
    return body


def _drop_a_data_shard(layer, key: str) -> None:
    """Remove the part file of shard 1 (a data shard) from its drive."""
    for disk in layer.disks:
        fi = disk.read_version(BKT, key)
        if fi.erasure.index == 1:
            os.remove(os.path.join(disk.root, BKT, key, fi.data_dir,
                                   "part.1"))
            return
    raise AssertionError("no drive holds shard 1")


# what one operation adds, per case: metadata reads; shard-read
# fan-outs, their children, the verifies that ran; the drive op that
# read the shards; delete fan-outs
CASES = {
    "head": dict(form="plain", op="head", meta=1),
    "get-plain": dict(form="plain", op="get", meta=1, fanouts=1,
                      children=K, verifies=K, io="read_file_stream"),
    "get-inline": dict(form="inline", op="get", meta=1, fanouts=1,
                       children=K, verifies=K, io=None),
    "get-packed": dict(form="packed", op="get", meta=1, fanouts=1,
                       children=K, verifies=K, io="read_segment"),
    # a hit is validated by one quorum metadata read and reads no shard
    "get-cache-hit": dict(form="inline", op="get", meta=1, warm=True,
                          hits=1),
    # the first batch of k loses one child before its verify; a second
    # batch of one parity shard makes up for it
    "get-degraded": dict(form="plain", op="get", meta=1, fanouts=2,
                         children=K + 1, verifies=K, degraded=True,
                         io="read_file_stream"),
    "delete": dict(form="packed", op="delete", meta=0, deletes=1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_operation_counts_its_legs(served, case):
    want = CASES[case]
    backend = served.layer.backend
    c = S3Client(served.endpoint, "lk", "ls")
    key = f"{case}-{backend}"
    body = _put(served, key, want["form"])
    if want.get("degraded"):
        _drop_a_data_shard(served.layer, key)
    if want.get("warm"):
        # a tiny object is admitted by its first read; let the reuse
        # window of that read's own validation pass
        assert c.get_object(BKT, key).body == body
        time.sleep(hotread.CONFIG.validate_ttl_ms / 1000.0 + 0.05)
    assert not trace.active()
    s0 = _scrape(served)
    if want["op"] == "head":
        c.head_object(BKT, key)
    elif want["op"] == "get":
        assert c.get_object(BKT, key).body == body
    else:
        c.delete_object(BKT, key)
    s1 = _scrape(served)

    def legs(op, leg):
        return _rose(s0, s1, "mt_read_leg_seconds_count", op=op, leg=leg)

    def calls(op):
        return _rose(s0, s1, "mt_drive_call_seconds_count", op=op,
                     kind="local")

    meta = want["meta"]
    assert legs("meta", "fanout") == meta
    assert legs("meta", "pick") == meta
    assert legs("meta", "gather") == meta
    assert legs("meta", "queue") == N * meta
    assert calls("read_version") == N * meta
    fanouts = want.get("fanouts", 0)
    assert legs("get", "fanout") == (1 if fanouts else 0)
    assert legs("get", "assemble") == (1 if fanouts else 0)
    assert legs("get", "copy_out") == (1 if fanouts else 0)
    assert legs("get", "gather") == fanouts
    assert legs("get", "queue") == want.get("children", 0)
    assert legs("get", "verify") == want.get("verifies", 0)
    for op in ("read_file_stream", "read_segment"):
        assert calls(op) == (want.get("children", 0)
                             if op == want.get("io") else 0), op
    deletes = want.get("deletes", 0)
    assert legs("delete", "gather") == deletes
    assert legs("delete", "queue") == N * deletes
    assert calls("delete_version") == N * deletes
    assert _rose(s0, s1, "mt_cache_hits_total") == want.get("hits", 0)
    if want.get("degraded") and backend == "tpu":
        # the rebuild keeps its device legs, inside get.assemble
        assert _rose(s0, s1, "mt_tpu_leg_seconds_count",
                     op="decode") > 0


@pytest.mark.parametrize("family", ["mt_read_leg", "mt_tpu_leg"])
def test_a_legs_cpu_never_reads_over_its_wall(served, family):
    """One span in ``CPU_SAMPLE_EVERY`` of a name (the first always)
    read its thread's CPU clock and observed CPU and wall into the twin
    family under ``clock``: every span leg has samples, as many of each
    clock, never more than the leg ran, and CPU <= wall + a tick."""
    c = S3Client(served.endpoint, "lk", "ls")
    key = f"cpu-{family}-{served.layer.backend}"
    body = _put(served, key, "plain")
    assert c.get_object(BKT, key).body == body
    s = _scrape(served)
    ran = {ls: v for (n, ls), v in s.items()
           if n == f"{family}_seconds_count"
           # queue and gather are folded from two threads' clocks
           and dict(ls).get("leg") not in ("queue", "gather")}
    twin = f"{family}_cpu_seconds"

    def sampled(ls, clock, suffix):
        return s.get((f"{twin}_{suffix}", ls | {("clock", clock)}))

    assert ran, f"no {family}_seconds samples"
    for ls, n in ran.items():
        k = sampled(ls, "cpu", "count")
        assert k == sampled(ls, "wall", "count"), dict(ls)
        # (children that enter one name together may each find the
        # sampling count where it stood: a few more samples, never n)
        assert 1 <= k <= max(1, n // 2), (dict(ls), k, n)
        cpu, wall = sampled(ls, "cpu", "sum"), sampled(ls, "wall", "sum")
        assert 0 <= cpu <= wall + 0.001 * k, (dict(ls), cpu, wall)


def test_a_window_spanning_get_streams_under_stream_wait(served,
                                                         monkeypatch):
    """A GET the hot-read plane hands on (its range spans windows) is
    produced on a readahead thread: the request thread's serial vector
    has ``stream_wait``, the producer's ``drive_read`` / ``decode`` /
    ``meta_read`` are async detail, and the sum still reconciles."""
    monkeypatch.setattr(hotread.CONFIG, "window_bytes", BS)
    c = S3Client(served.endpoint, "lk", "ls")
    key = f"spanning-{served.layer.backend}"
    body = _put(served, key, "plain")
    c.get_object(BKT, key)      # the plane learns the object's size
    s0 = _scrape(served)
    assert c.get_object(BKT, key).body == body
    s1 = _scrape(served)
    assert _rose(s0, s1, "mt_read_leg_seconds_count",
                 op="meta", leg="fanout") == 1
    rec = _newest_record(c, "GetObject", "stream_wait")
    assert "meta_read" in rec["stages"]     # before the stream starts
    assert {"drive_read", "decode"} <= set(rec["asyncStages"])
    assert "drive_read" not in rec["stages"]
    assert sum(rec["stages"].values()) == rec["durationNs"]


def test_remote_children_are_timed_with_no_subscriber(tmp_path):
    """12 of a fan-out's 16 children are RPCs in a four-node cluster:
    the caller's wall of each lands in ``kind="remote"`` whether or not
    anyone subscribed to the trace, and the owner's drive call beside
    it in ``kind="local"``."""
    from minio_tpu.admin.metrics import GLOBAL
    from minio_tpu.parallel.rpc import RPCClient, RPCServer
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    owned = {}
    for i in range(3):
        d = tmp_path / f"r{i}"
        d.mkdir()
        owned[f"r{i}"] = XLStorage(str(d))
    rpc = RPCServer("legs-secret")
    register_storage_service(rpc, owned)
    rpc.start()
    try:
        d = tmp_path / "l0"
        d.mkdir()
        disks = [XLStorage(str(d))] + [
            RemoteStorage(RPCClient(rpc.endpoint, "legs-secret"), name)
            for name in owned]
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        layer.make_bucket("rbkt")
        layer.put_object("rbkt", "obj", b"r" * 300_000)

        def count(kind):
            return sum(h[len(KERNEL_BUCKETS)] for (name, labels, _), h
                       in GLOBAL.hist_snapshot().items()
                       if name == "mt_drive_call_seconds"
                       and dict(labels) == {"op": "read_version",
                                            "kind": kind})
        assert not trace.active()
        before = count("remote"), count("local")
        layer.get_object_info("rbkt", "obj")
        assert count("remote") - before[0] == 3
        # this process owns all four drives: each call counted once
        assert count("local") - before[1] == 4
    finally:
        rpc.stop()


@pytest.mark.parametrize("blocking", [False, True],
                         ids=["busy", "asleep"])
def test_span_records_cpu_beside_wall(blocking):
    name = f"unit.{'asleep' if blocking else 'busy'}"
    trace._cpu_seen.pop(name, None)
    for i in range(trace.CPU_SAMPLE_EVERY + 1):
        with trace.span("read", name) as sp:
            if i:
                continue
            if blocking:
                time.sleep(0.05)
            else:
                t_end = time.thread_time_ns() + 20_000_000
                while time.thread_time_ns() < t_end:
                    pass
        # the first span of a name reads the CPU clock, then every
        # CPU_SAMPLE_EVERY-th: the clock is a trapped syscall where the
        # benchmark runs
        assert (sp.cpu_ns is not None) == \
            (i % trace.CPU_SAMPLE_EVERY == 0), i
        if i == 0:
            assert 0 <= sp.cpu_ns <= sp.dur_ns + 1_000_000
            if blocking:
                assert sp.cpu_ns < sp.dur_ns // 2   # the wait is not CPU
            else:
                assert sp.cpu_ns >= 20_000_000


def test_observe_many_is_n_observes_under_one_lock():
    values = [0.0002, 0.004, 0.004, 3.0]
    one, many = Metrics(), Metrics()
    for v in values:
        one.observe("fam", {"op": "x"}, v, buckets=KERNEL_BUCKETS)
    many.observe_many("fam", {"op": "x"}, values, buckets=KERNEL_BUCKETS)
    assert many.hist_snapshot() == one.hist_snapshot()
    many.observe_many("fam", {"op": "x"}, [], buckets=KERNEL_BUCKETS)
    assert many.hist_snapshot() == one.hist_snapshot()


def test_a_streaming_puts_body_is_body_read(tmp_path):
    """A body over the streaming threshold arrives through
    ``_BodyReader.read``: socket read + sha256 are stage ``body_read``
    on the request thread, not ``other``."""
    from minio_tpu.s3.server import STREAM_PUT_THRESHOLD
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=1 << 20,
                           backend="numpy")
    srv = S3Server(layer, access_key="lk", secret_key="ls")
    srv.start()
    try:
        c = S3Client(srv.endpoint, "lk", "ls")
        c.make_bucket(BKT)
        c.put_object(BKT, "big", os.urandom(STREAM_PUT_THRESHOLD + 4321))
        rec = _newest_record(c, "PutObject", "body_read")
        assert rec["stages"]["body_read"] > 0, rec["stages"]
        assert sum(rec["stages"].values()) == rec["durationNs"]
    finally:
        srv.stop()

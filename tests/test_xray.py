"""Request X-ray + flight recorder (ISSUE 15 tentpole): per-stage
latency attribution threaded through the request path, reconciliation
of the serial stage vector with the measured total, the always-on
idle contract (bounded ring appends, no trace construction without a
consumer), the admin ``xray`` route (local + peer-aggregated), and
the ``mt_s3_stage_seconds`` scrape family.
"""

import json
import threading
import time

import pytest

from minio_tpu.background.tracker import DataUpdateTracker
from minio_tpu.obs import stages, trace
from minio_tpu.obs.flightrec import FlightRecorder
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.parallel.peer import PeerNotifier, register_peer_service
from minio_tpu.parallel.rpc import RPCClient, RPCServer
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.xl_storage import XLStorage


# -- StageClock unit tier ----------------------------------------------------

def test_stage_clock_nesting_is_exclusive_and_reconciles():
    clock = stages.StageClock()
    with_stage = stages._Stage
    stages._CLOCK.set(clock)
    try:
        with with_stage("cache"):
            time.sleep(0.02)
            with with_stage("lock_wait"):
                time.sleep(0.02)
    finally:
        stages.clear()
    serial, async_d, unattr = clock.finish()
    # nested lock_wait's time was subtracted from cache (exclusive
    # self-times), and the vector + other reconciles with the total
    assert serial["lock_wait"] >= 15_000_000
    assert serial["cache"] >= 15_000_000
    assert serial["cache"] < 35_000_000, "nested stage double-counted"
    total = sum(serial.values())
    assert unattr >= 0, "serial stages exceeded the wall total"
    assert total == sum(v for k, v in serial.items())
    assert not async_d


def test_stage_clock_routes_foreign_threads_to_async_detail():
    clock = stages.StageClock()

    def worker():
        stages.set_clock(clock)
        with stages.stage("rpc"):
            time.sleep(0.01)
        stages.add("drive_read", 5_000_000)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    serial, async_d, _ = clock.finish()
    # a non-owner thread can never pollute the serial reconciliation
    assert "rpc" not in serial and "drive_read" not in serial
    assert async_d["rpc"] >= 5_000_000
    assert async_d["drive_read"] == 5_000_000


def test_stage_helpers_are_noops_without_a_clock():
    stages.clear()
    with stages.stage("auth"):
        pass
    stages.add("encode", 123)
    stages.add_async("rpc", 123)        # nothing to assert: must not raise
    assert stages.current() is None


# -- flight recorder unit tier -----------------------------------------------

def test_flight_recorder_rings_bound_and_filter():
    rec = FlightRecorder(req_ring=8, err_ring=4,
                         snap_interval_s=3600.0)
    for i in range(20):
        rec.record(f"r{i}", "GetObject", 500 if i % 5 == 0 else 200,
                   dur_ns=i * 1_000_000, rx=0, tx=10,
                   stages=(("auth", 100),))
    st = rec.stats()
    assert st["requests"] == 8 and st["recordsTotal"] == 20
    assert st["errors"] == 4          # bounded, newest kept
    out = rec.query(api="GetObject", min_duration_ms=15.0)
    assert out and all(r["durationNs"] >= 15_000_000 for r in out)
    assert out[0]["durationNs"] >= out[-1]["durationNs"]  # newest first
    errs = rec.query(errors_only=True)
    assert errs and all(r["status"] == 500 for r in errs)
    assert rec.query(api="PutObject") == []


# -- served tier -------------------------------------------------------------

@pytest.fixture
def served(tmp_path):
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="xk", secret_key="xs")
    srv.start()
    yield srv
    srv.stop()


def _xray(c, qs="n=50"):
    r = c.request("GET", "/minio-tpu/admin/v1/xray", qs)
    return json.loads(r.body)


def _settle(srv, want_total, timeout_s=2.0):
    """Completion records land in the handler thread's ``finally``
    AFTER the response bytes go out, and the client opens a fresh
    connection per request — so the caller can outrun the last
    append by a hair.  Wait for the ring to catch up before
    asserting on it."""
    deadline = time.monotonic() + timeout_s
    while srv.flightrec.records_total < want_total and \
            time.monotonic() < deadline:
        time.sleep(0.005)


def test_get_put_carry_complete_stage_timeline(served):
    c = S3Client(served.endpoint, "xk", "xs")
    c.make_bucket("xbkt")
    c.put_object("xbkt", "obj", b"z" * 300_000)
    c.get_object("xbkt", "obj")
    _settle(served, 3)
    doc = _xray(c)
    recs = {r["api"]: r for r in doc["records"]}
    assert "PutObject" in recs and "GetObject" in recs
    put, get = recs["PutObject"], recs["GetObject"]
    # the PUT crossed auth, policy, body read, encode, lock, commit
    for want in ("auth", "policy", "body_read", "encode", "lock_wait",
                 "drive_commit", "other"):
        assert want in put["stages"], (want, put["stages"])
    for want in ("auth", "policy", "lock_wait", "other"):
        assert want in get["stages"], (want, get["stages"])
    # a GET reads shards and decodes somewhere on its path (serial on
    # the buffered path, async detail under readahead)
    get_all = {**get["stages"], **get["asyncStages"]}
    assert "drive_read" in get_all and "decode" in get_all
    # every emitted name is in the documented catalog
    for rec in (put, get):
        names = set(rec["stages"]) | set(rec["asyncStages"])
        assert names <= set(stages.STAGE_NAMES), names
    # reconciliation: serial stages + other == the measured total
    for rec in (put, get):
        assert sum(rec["stages"].values()) == rec["durationNs"], rec


# what a read-side request's serial vector names since ISSUE 36: the
# quorum metadata read, the hand-over of the body, a DELETE's fan-out
@pytest.mark.parametrize("api,want", [
    ("HeadObject", ("auth", "policy", "lock_wait", "meta_read")),
    ("GetObject", ("auth", "policy", "lock_wait", "meta_read",
                   "stream_wait", "body_write")),
    ("DeleteObject", ("auth", "policy", "lock_wait", "drive_commit")),
])
def test_read_side_vectors_reconcile(served, api, want):
    c = S3Client(served.endpoint, "xk", "xs")
    c.make_bucket("rbkt")
    c.put_object("rbkt", "obj", b"r" * 300_000)
    if api == "HeadObject":
        c.head_object("rbkt", "obj")
    elif api == "GetObject":
        c.get_object("rbkt", "obj")
    else:
        c.delete_object("rbkt", "obj")
    _settle(served, 3)
    rec = {r["api"]: r for r in _xray(c)["records"]}[api]
    for name in want + ("other",):
        assert name in rec["stages"], (name, rec["stages"])
    names = set(rec["stages"]) | set(rec["asyncStages"])
    assert names <= set(stages.STAGE_NAMES), names
    # the serial stages + other are the request wall, to the ns: the
    # new stages nest (meta_read inside cache, a buffered read's
    # drive_read inside stream_wait) and take their time out of their
    # parents; nothing is counted twice
    assert sum(rec["stages"].values()) == rec["durationNs"], rec
    if api != "DeleteObject":
        # the metadata read is named: HEAD's remainder is no longer
        # the whole request
        assert rec["stages"]["meta_read"] > 0


def test_stage_histogram_and_trace_detail(served):
    c = S3Client(served.endpoint, "xk", "xs")
    c.make_bucket("hbkt")
    with served.trace_hub.subscribe() as sub:
        c.put_object("hbkt", "obj", b"t" * 50_000)
        spans = list(sub.drain(200, timeout=2.0))
    https = [s for s in spans if s.get("type") == "http"
             and s["funcName"] == "PutObject"]
    assert https, "no http trace for the PUT"
    detail = https[0].get("detail")
    assert detail and "stages" in detail, https[0]
    assert "encode" in detail["stages"]
    assert sum(detail["stages"].values()) == detail["totalNs"]
    # scrape family: per-api, per-stage samples
    import http.client
    host, port = served.endpoint.replace("http://", "").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    conn.request("GET", "/minio-tpu/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    assert 'mt_s3_stage_seconds_count{api="PutObject",stage="encode",' \
        'vec="serial"}' in text
    assert 'mt_flight_ring_depth{ring="requests"}' in text


def test_always_on_idle_contract(served, monkeypatch):
    """With zero trace subscribers, serving requests must not build a
    single trace dict — the always-on cost is the stage clock's
    in-place dict updates plus two bounded ring appends per request —
    and the flight ring must still have recorded every request as a
    compact tuple (no dict on the hot path)."""
    calls = {"trace": 0, "span": 0}
    real_trace = trace.make_trace
    monkeypatch.setattr(
        trace, "make_trace",
        lambda *a, **k: (calls.__setitem__("trace", calls["trace"] + 1),
                         real_trace(*a, **k))[1])
    real_span = trace.make_span
    monkeypatch.setattr(
        trace, "make_span",
        lambda *a, **k: (calls.__setitem__("span", calls["span"] + 1),
                         real_span(*a, **k))[1])
    assert not trace.active()
    c = S3Client(served.endpoint, "xk", "xs")
    c.make_bucket("ibkt")
    before = served.flightrec.records_total
    n = 6
    for i in range(n):
        c.put_object("ibkt", f"o{i}", b"idle" * 256)
    assert calls == {"trace": 0, "span": 0}, \
        "trace records built with no consumer"
    _settle(served, before + n)
    assert served.flightrec.records_total >= before + n
    newest = served.flightrec.requests[-1]
    assert isinstance(newest, tuple), "hot-path record is not compact"
    assert isinstance(newest[7], tuple), "stage vector not a tuple"


def test_xray_disable_switch(served, monkeypatch):
    """MT_XRAY_DISABLE (the bench A/B leg's baseline) arms no clock:
    requests still serve and still ride the flight ring, with an
    empty stage vector."""
    monkeypatch.setattr(stages, "ENABLED", False)
    c = S3Client(served.endpoint, "xk", "xs")
    c.make_bucket("dbkt")
    c.put_object("dbkt", "obj", b"q" * 1024)
    doc = _xray(c, "api=PutObject&n=1")
    assert doc["records"], "flight ring must record even when disabled"
    assert doc["records"][0]["stages"] == {}


# -- cluster tier ------------------------------------------------------------

@pytest.fixture
def duo(tmp_path):
    """Two S3 nodes; A's peer notifier dials B's peer RPC service
    (the test_cluster_obs pattern)."""
    for i in range(4):
        (tmp_path / f"d{i}").mkdir()

    def mk_node():
        disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
        layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                               backend="numpy")
        return S3Server(layer, access_key="ck", secret_key="cs")

    node_a, node_b = mk_node(), mk_node()
    node_a.start()
    node_b.start()
    node_b.attach_tracker(DataUpdateTracker())
    rpc_b = RPCServer("xray-peer-secret")
    register_peer_service(rpc_b, node_b)
    rpc_b.start()
    node_a.attach_peers(PeerNotifier(
        [RPCClient(rpc_b.endpoint, "xray-peer-secret")]))
    yield node_a, node_b, rpc_b
    node_a.stop()
    node_b.stop()
    try:
        rpc_b.stop()
    except Exception:  # noqa: BLE001 — a test may have stopped it
        pass


def test_xray_aggregates_peers_and_cluster_healthinfo(duo):
    node_a, node_b, rpc_b = duo
    ca = S3Client(node_a.endpoint, "ck", "cs")
    cb = S3Client(node_b.endpoint, "ck", "cs")
    ca.make_bucket("peerbkt")
    ca.put_object("peerbkt", "oa", b"a" * 4096)
    cb.put_object("peerbkt", "ob", b"b" * 4096)
    doc = json.loads(ca.request(
        "GET", "/minio-tpu/admin/v1/xray", "n=20").body)
    assert any(r["api"] == "PutObject" for r in doc["records"])
    assert doc.get("peers"), "peer leg missing"
    peer = doc["peers"][0]
    assert peer.get("records") is not None
    assert any(r["api"] == "PutObject" for r in peer["records"]), \
        "node B's PUT not visible through the peer xray leg"
    # cluster healthinfo folds both nodes into one document
    hd = json.loads(ca.request(
        "GET", "/minio-tpu/admin/v1/healthinfo", "scope=cluster").body)
    assert hd["scope"] == "cluster" and len(hd["nodes"]) == 2
    assert all("system" in n for n in hd["nodes"] if "error" not in n)
    # a downed peer is MARKED offline, the call never fails
    rpc_b.stop()
    hd = json.loads(ca.request(
        "GET", "/minio-tpu/admin/v1/healthinfo", "scope=cluster").body)
    assert len(hd["nodes"]) == 2
    assert any(n.get("offline") for n in hd["nodes"]), hd["nodes"]


# -- device legs beside the stage clock (ISSUE 23) ----------------------------

@pytest.fixture
def device_layer(tmp_path):
    """An object layer on a DEVICE codec (XLA:CPU under conftest's
    explicit CPU pin), so a PUT crosses every leg of the device path."""
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=256 * 1024,
                           backend="tpu")
    layer.make_bucket("legbkt")
    return layer


def _clocked_put(layer, key: str, body: bytes):
    """One PUT under an armed clock and request id; returns (serial,
    async, unattributed, total_ns, this request's tpu ring records)."""
    rid = f"xray-legs-{key}"
    trace.set_request_id(rid)
    trace.set_span_parent(rid)
    clock = stages.StageClock()
    stages.set_clock(clock)
    t0 = time.monotonic_ns()
    try:
        layer.put_object("legbkt", key, body)
    finally:
        dur = time.monotonic_ns() - t0
        serial, async_ns, un = clock.finish(dur)
        stages.clear()
        trace.set_request_id("")
        trace.set_span_parent("")
    legs = [r for r in trace.SPANS.snapshot()
            if r[trace._R_RID] == rid and r[trace._R_TYPE] == "tpu"]
    return serial, async_ns, un, dur, legs


@pytest.mark.parametrize("size", [200_000, (1 << 20) + 4321],
                         ids=["md5-on-request-thread", "md5-on-pool"])
def test_legs_leave_the_serial_vector_alone(device_layer, size):
    """The legs are spans, never stages: the serial vector of a
    device-codec PUT holds catalog stages only and, with ``other``,
    still reconciles with the request wall EXACTLY; the time the legs
    cover stays in ``encode``'s self time (the legs sum to no more than
    it); and the ETag md5 shows as async detail only, whichever thread
    ran it."""
    serial, async_ns, un, dur, legs = _clocked_put(
        device_layer, f"o{size}", b"x" * size)
    assert un >= 0, "double count in the serial vector"
    assert sum(serial.values()) == dur
    assert set(serial) <= set(stages.STAGE_NAMES), serial
    assert set(async_ns) <= set(stages.STAGE_NAMES), async_ns
    assert "md5" in async_ns and "md5" not in serial, (serial, async_ns)
    assert async_ns["md5"] > 0
    by_name: dict = {}
    for r in legs:
        by_name[r[trace._R_NAME]] = by_name.get(r[trace._R_NAME], 0) \
            + r[trace._R_DUR]
    assert {"encode.dispatch", "hash.dispatch"} <= set(by_name), by_name
    # both whole dispatches ran inside stage encode, on this thread: one
    # after the other for a body under a block (256 KiB here), the
    # tail's bitrot leg INSIDE the encode dispatch for a longer one
    # (its four full blocks take the fused route, as one stripe group)
    whole = by_name["encode.dispatch"]
    if size < 256 * 1024:
        whole += by_name["hash.dispatch"]
    else:
        assert by_name["hash.dispatch"] <= by_name["encode.dispatch"]
        assert "encode-bitrot-group.batch" in by_name, by_name
    assert whole <= serial["encode"] + serial.get("batch_wait", 0)


def test_every_leg_enters_and_exits_the_annotator_once(device_layer,
                                                       monkeypatch):
    """With an annotator installed (ops/device.py hands obs/trace.py
    ``jax.profiler.TraceAnnotation``; here a recording fake) every leg
    enters and exits it exactly once, around its own interval, strictly
    nested, under the name ``mt:<op>.<leg>``."""
    log: list = []

    class Fake:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name, threading.get_ident()))

        def __exit__(self, *exc):
            log.append(("exit", self.name, threading.get_ident()))

    monkeypatch.setattr(trace, "_ANNOTATOR", Fake)
    _s, _a, _u, _d, legs = _clocked_put(device_layer, "ann",
                                        b"a" * 200_000)
    me = threading.get_ident()
    mine = [(what, name) for what, name, tid in log if tid == me]
    stack: list = []
    closed: list = []
    for what, name in mine:
        if what == "enter":
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            closed.append(stack.pop())
    assert not stack, f"never exited: {stack}"
    # one annotation per ring record, same names, same completion order
    ring = ["mt:" + r[trace._R_NAME] for r in legs]
    assert closed == ring, (closed, ring)
    assert {"mt:encode.launch", "mt:encode.fetch", "mt:hash.launch",
            "mt:hash.fetch"} <= set(closed)


def test_obs_never_imports_jax():
    """A ``--backend numpy`` server never loads JAX: importing all of
    ``minio_tpu.obs`` and timing a span with no annotator installed
    leaves ``jax`` out of ``sys.modules``."""
    import subprocess
    import sys
    code = (
        "import importlib, pkgutil, sys\n"
        "import minio_tpu.obs as obs\n"
        "for m in pkgutil.iter_modules(obs.__path__):\n"
        "    importlib.import_module('minio_tpu.obs.' + m.name)\n"
        "from minio_tpu.obs import trace\n"
        "assert trace._ANNOTATOR is None\n"
        "trace.set_request_id('r1')\n"
        "with trace.span('tpu', 'encode.prep', nbytes=1):\n"
        "    pass\n"
        "assert trace.SPANS.snapshot()[-1][trace._R_NAME] == 'encode.prep'\n"
        "assert 'jax' not in sys.modules, 'obs/ pulled JAX in'\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr

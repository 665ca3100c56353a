"""Deep tracing plane (obs smoke tier): subsystem spans, cluster-wide
request correlation, last-minute latency stats, slow-drive detection,
TPU-kernel metrics, and the idle-overhead contract.

Reference tier: `mc admin trace -a` (cmd/admin-handlers.go TraceHandler
type filters + peerRESTMethodTrace), cmd/last-minute.go, and the Dapper
span-with-propagated-context model (request IDs crossing the internode
boundary in an X-Request-ID header).
"""

import json
import re
import threading
import time

import pytest

from minio_tpu.obs import lastminute, trace
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage import health
from minio_tpu.storage.xl_storage import XLStorage


# -- idle-overhead contract -------------------------------------------------

def test_idle_storage_ops_build_no_spans(tmp_path, monkeypatch):
    """With zero trace subscribers and an idle ring, the storage hot
    path's tracing overhead is a single predicate — no span dict is
    constructed, nothing is published."""
    assert not trace.active(), "leaked subscriber/ring from another test"
    calls = {"make": 0, "publish": 0}
    real_make = trace.make_span
    monkeypatch.setattr(
        trace, "make_span",
        lambda *a, **k: (calls.__setitem__("make", calls["make"] + 1),
                         real_make(*a, **k))[1])
    monkeypatch.setattr(
        trace, "publish_span",
        lambda s: calls.__setitem__("publish", calls["publish"] + 1))
    d = tmp_path / "d0"
    d.mkdir()
    x = XLStorage(str(d))
    x.make_vol("vol")
    for i in range(50):
        x.write_all("vol", f"o{i}", b"payload")
        assert x.read_all("vol", f"o{i}") == b"payload"
    assert calls == {"make": 0, "publish": 0}
    # the always-on last-minute window still accumulated
    totals = x.latency.totals()
    assert totals["read_all"][0] == 50
    assert totals["write_all"][0] == 50
    assert totals["write_all"][2] == 50 * len(b"payload")
    # with a subscriber the same ops DO publish
    with trace.HTTP_TRACE.subscribe():
        x.read_all("vol", "o0")
    assert calls["publish"] >= 1


def test_nested_storage_ops_record_once(tmp_path):
    """Traced ops that call other traced ops internally (write_metadata
    -> write_all, read_version -> read_all) record ONE op per logical
    call — the outermost — so drive latency is never double-counted."""
    from minio_tpu.storage.datatypes import FileInfo
    d = tmp_path / "d0"
    d.mkdir()
    x = XLStorage(str(d))
    x.make_vol("vol")
    fi = FileInfo(volume="vol", name="obj", version_id="",
                  mod_time=123, size=0)
    x.write_metadata("vol", "obj", fi)
    x.read_version("vol", "obj")
    totals = x.latency.totals()
    assert totals["write_metadata"][0] == 1
    assert totals["read_version"][0] == 1
    # the nested write_all/read_all must not have been recorded
    assert "write_all" not in totals
    assert "read_all" not in totals


# -- last-minute windows ----------------------------------------------------

def test_window_slides_and_reports():
    w = lastminute.Window()
    w.record(1000, 10, now_s=100)
    w.record(3000, 20, now_s=130)
    assert w.total(now_s=130) == (2, 4000, 30)
    # 61s later the first sample aged out
    assert w.total(now_s=161) == (1, 3000, 20)
    # a slot is reclaimed when its second comes around again
    w.record(7000, 5, now_s=160)      # same slot index as 100
    assert w.total(now_s=161) == (2, 10000, 25)
    # p50 only reflects live samples
    assert w.p50(now_s=161) == 7000
    assert w.p50(now_s=300) == 0      # idle window reads 0


def test_opwindows_p50_and_top():
    ow = lastminute.OpWindows("drv")
    for _ in range(10):
        ow.record("read", 1_000_000, 100, now_s=50)
    for _ in range(3):
        ow.record("write", 9_000_000, 10, now_s=50)
    assert ow.p50_all(now_s=50) == 1_000_000
    rows = lastminute.top_entries(ow, now_s=50)
    assert rows[0]["name"] == "read" and rows[0]["count"] == 10
    assert rows[1]["name"] == "write" and rows[1]["avg_ns"] == 9_000_000


def test_slow_drive_flagged_not_ejected():
    class FakeDisk:
        def __init__(self, label, p50_ns, samples=20):
            self.latency = lastminute.OpWindows(label)
            for _ in range(samples):
                self.latency.record("read", p50_ns, 0)

    disks = [FakeDisk("d0", 1_000_000), FakeDisk("d1", 1_100_000),
             FakeDisk("d2", 900_000), FakeDisk("d3", 50_000_000)]
    out = health.slow_drives(disks, multiple=4.0, min_samples=10)
    assert out["d3"]["slow"] is True
    assert not any(out[d]["slow"] for d in ("d0", "d1", "d2"))
    # below min_samples the outlier is not flagged (too little signal)
    thin = [FakeDisk("t0", 1_000_000, samples=20),
            FakeDisk("t1", 1_000_000, samples=20),
            FakeDisk("t2", 50_000_000, samples=3)]
    out = health.slow_drives(thin, multiple=4.0, min_samples=10)
    assert out["t2"]["slow"] is False
    # leave-one-out median: in a 2-drive set the outlier must not drag
    # the comparison median up to its own p50 and escape detection
    pair = [FakeDisk("p0", 1_000_000), FakeDisk("p1", 100_000_000)]
    out = health.slow_drives(pair, multiple=4.0, min_samples=10)
    assert out["p1"]["slow"] is True
    assert out["p0"]["slow"] is False
    # knobs resolve from the kvconfig `drive` subsystem (env override)
    mult, min_s = health.slow_drive_knobs()
    assert mult == 4.0 and min_s == 10


@pytest.mark.parametrize("fast_share", [0.2, 0.5, 0.8])
def test_slow_drives_compare_op_by_op(fast_share):
    """A drive is judged against the same op on its peers: one whose
    reads of tens of us and writes of ms mix in another share than its
    peers' has a median over all ops far from theirs and is not slow; a
    drive 10x slower at every op is, whatever its mix."""
    class FakeDisk:
        def __init__(self, label, reads, writes, scale=1):
            self.latency = lastminute.OpWindows(label)
            for _ in range(reads):
                self.latency.record("read_version", 30_000 * scale, 0)
            for _ in range(writes):
                self.latency.record("write_data_commit", 2_000_000 * scale,
                                    0)

    fast = round(20 * fast_share)
    disks = [FakeDisk(f"d{i}", 12, 8) for i in range(3)]
    disks += [FakeDisk("mix", fast, 20 - fast),
              FakeDisk("slow", fast, 20 - fast, scale=10)]
    out = health.slow_drives(disks, multiple=4.0, min_samples=10)
    assert out["slow"]["slow"] is True
    assert out["slow"]["median_ns"] == out["slow"]["p50_ns"] // 10
    assert not any(out[d]["slow"] for d in ("d0", "d1", "d2", "mix"))
    assert out["mix"]["median_ns"] == out["mix"]["p50_ns"]


def test_slow_drives_grouped_per_set(tmp_path):
    """Detection compares a drive against its SET peers: a slow pool
    must not mask a relatively-failing drive in a fast pool."""
    class FakeDisk:
        def __init__(self, label, p50_ns):
            self.latency = lastminute.OpWindows(label)
            for _ in range(20):
                self.latency.record("read", p50_ns, 0)

        def is_online(self):
            return True

    class FakeSet:
        def __init__(self, disks):
            self.disks = disks

    class FakeLayer:
        def __init__(self, sets):
            self.sets = sets

    hdd = [FakeDisk(f"hdd{i}", 10_000_000) for i in range(4)]
    nvme = [FakeDisk(f"nvme{i}", 100_000) for i in range(3)]
    nvme.append(FakeDisk("nvme3", 5_000_000))   # 50x its set median
    layer = FakeLayer([FakeSet(hdd), FakeSet(nvme)])
    out = health.slow_drives_for_layer(layer, multiple=4.0,
                                       min_samples=10)
    assert out["nvme3"]["slow"] is True, \
        "fast-pool outlier masked by the slow pool"
    assert not any(out[f"hdd{i}"]["slow"] for i in range(4))


# -- served spans + correlation (single node) -------------------------------

@pytest.fixture
def served(tmp_path):
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="ok", secret_key="os")
    srv.start()
    yield srv
    srv.stop()


def test_request_id_correlates_http_and_storage_spans(served):
    c = S3Client(served.endpoint, "ok", "os")
    with served.trace_hub.subscribe() as sub:
        c.make_bucket("corrbkt")
        c.put_object("corrbkt", "obj", b"z" * 20000)
        spans = list(sub.drain(400, timeout=2.0))
    https = [s for s in spans if s.get("type") == "http"
             and s["funcName"] == "PutObject"]
    assert https
    rid = https[0]["requestID"]
    assert rid
    # every layer the PUT crossed shares the frontend's request ID —
    # including drive writes running in fan-out pool threads
    storage = [s for s in spans if s.get("type") == "storage"
               and s.get("requestID") == rid]
    assert storage, "no storage span carries the request ID"
    assert any(s["storage"]["volume"] == "corrbkt" for s in storage)
    tpu = [s for s in spans if s.get("type") == "tpu"
           and s.get("requestID") == rid]
    assert tpu, "no tpu (erasure-kernel) span carries the request ID"
    enc = tpu[0]
    assert enc["tpu"]["k"] + enc["tpu"]["m"] == 4
    assert enc["callStats"]["inputBytes"] >= 20000


def test_admin_trace_type_filter(served):
    c = S3Client(served.endpoint, "ok", "os")
    c.make_bucket("filtbkt")
    got = {}

    def consume(name, qs):
        r = c.request("GET", "/minio-tpu/admin/v1/trace", qs)
        got[name] = [json.loads(x)
                     for x in r.body.decode().splitlines() if x]

    threads = [
        threading.Thread(target=consume,
                         args=("http", "timeout=3&max-items=2")),
        threading.Thread(target=consume, args=(
            "deep", "timeout=3&max-items=5&type=storage,internode,tpu")),
    ]
    for t in threads:
        t.start()
    for _ in range(100):
        if served.trace_hub.num_subscribers >= 2:
            break
        time.sleep(0.02)
    c.put_object("filtbkt", "o1", b"traced" * 1000)
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    # default stream: http only (pre-deep-tracing contract unchanged)
    assert got["http"]
    assert all(i.get("type", "http") == "http" for i in got["http"])
    # typed stream: subsystem spans only, no http records
    assert got["deep"]
    kinds = {i["type"] for i in got["deep"]}
    assert kinds <= {"storage", "internode", "tpu"}
    assert "storage" in kinds


def test_http_only_stream_builds_no_deep_spans(served, monkeypatch):
    """The default (http-only) admin trace stream must not activate
    subsystem-span construction: it registers an opt-out, so the
    deep-span predicate stays False while it runs — pre-PR consumers
    keep pre-PR costs, not just pre-PR record shapes."""
    calls = {"span": 0}
    real = trace.make_span
    monkeypatch.setattr(
        trace, "make_span",
        lambda *a, **k: (calls.__setitem__("span", calls["span"] + 1),
                         real(*a, **k))[1])
    c = S3Client(served.endpoint, "ok", "os")
    c.make_bucket("hobkt")
    got = {}

    def consume():
        r = c.request("GET", "/minio-tpu/admin/v1/trace",
                      "timeout=3&max-items=1")
        got["lines"] = [json.loads(x)
                        for x in r.body.decode().splitlines() if x]

    t = threading.Thread(target=consume)
    t.start()
    for _ in range(100):
        if served.trace_hub.num_subscribers > 0:
            break
        time.sleep(0.02)
    assert not trace.active(), \
        "an http-only consumer must not arm deep spans"
    c.put_object("hobkt", "o1", b"h" * 4096)
    t.join(timeout=10)
    assert not t.is_alive()
    assert got["lines"] and got["lines"][0]["type"] == "http"
    assert calls["span"] == 0, "subsystem span built for http-only"


def test_broken_subscriber_filter_never_fails_publish(tmp_path):
    """publish() now runs inside storage data-path finallys: a raising
    subscriber filter must be dropped, never propagate to the drive op."""
    def bad_filter(item):
        raise RuntimeError("broken consumer")

    with trace.HTTP_TRACE.subscribe(bad_filter), \
            trace.HTTP_TRACE.subscribe() as good:
        d = tmp_path / "d0"
        d.mkdir()
        x = XLStorage(str(d))
        x.make_vol("vol")
        x.write_all("vol", "obj", b"ok")        # must not raise
        assert x.read_all("vol", "obj") == b"ok"
        spans = list(good.drain(10, timeout=1.0))
    assert any(s["funcName"] == "storage.write_all" for s in spans)


def test_unknown_trace_type_is_rejected(served):
    from minio_tpu.s3.client import S3ClientError
    import urllib.error
    c = S3Client(served.endpoint, "ok", "os")
    with pytest.raises((S3ClientError, urllib.error.HTTPError)):
        c.request("GET", "/minio-tpu/admin/v1/trace",
                  "timeout=1&type=storge")


def test_top_endpoint_reports_apis_and_drives(served):
    c = S3Client(served.endpoint, "ok", "os")
    c.make_bucket("topbkt")
    for i in range(4):
        c.put_object("topbkt", f"o{i}", b"t" * 2048)
        c.get_object("topbkt", f"o{i}")
    # the handler records its API window after the response is flushed
    doc = {}
    for _ in range(50):
        r = c.request("GET", "/minio-tpu/admin/v1/top", "")
        doc = json.loads(r.body)
        if any(a["name"] == "PutObject" for a in doc["apis"]):
            break
        time.sleep(0.05)
    apis = {a["name"]: a for a in doc["apis"]}
    assert apis["PutObject"]["count"] >= 4
    assert apis["PutObject"]["avg_ns"] > 0
    assert doc["drives"], "drive latency rows missing"
    d0 = doc["drives"][0]
    assert d0["count"] > 0 and d0["p50_ns"] >= 0
    assert "slow" in d0 and "ops" in d0
    assert doc["knobs"]["slow_latency_multiple"] == 4.0


def test_scrape_has_lastminute_and_tpu_families(served):
    c = S3Client(served.endpoint, "ok", "os")
    c.make_bucket("scrbkt")
    # above the inline threshold: shard files land via write_data_commit
    c.put_object("scrbkt", "obj", b"s" * (1 << 20))
    c.get_object("scrbkt", "obj")
    import http.client
    host, port = served.endpoint.replace("http://", "").split(":")
    text = ""
    for _ in range(40):
        conn = http.client.HTTPConnection(host, int(port), timeout=10)
        conn.request("GET", "/minio-tpu/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        if 'mt_s3_api_last_minute_requests{api="PutObject"}' in text:
            break
        time.sleep(0.05)
    m = re.search(r'mt_node_disk_latency_ops\{[^}]*op="'
                  r'write_data_commit"\} (\d+)', text)
    assert m and int(m.group(1)) > 0
    assert re.search(r"mt_tpu_ops_total\{[^}]*\} [1-9]", text)
    assert re.search(r"mt_tpu_bytes_total\{[^}]*\} [1-9]", text)
    assert re.search(r'mt_s3_api_last_minute_requests\{api="PutObject"\}'
                     r" [1-9]", text)
    assert "mt_node_disk_slow{" in text
    assert "mt_node_disk_latency_p50_ns{" in text


# -- cluster-wide correlation (2 nodes over real internode RPC) -------------

def test_peer_spans_carry_frontend_request_id(tmp_path):
    """A PUT served by node0 fans shard writes to node1 over RPC; the
    spans node1 emits (internode server side + its local drive ops)
    must carry node0's frontend request ID, forwarded in the
    X-Request-ID header — contextvars do not cross processes/threads,
    so only the wire can have carried it."""
    from minio_tpu.cluster import NodeSpec, start_cluster
    specs = []
    for n in range(2):
        dirs = []
        for d in range(2):
            p = tmp_path / f"node{n}-drive{d}"
            p.mkdir()
            dirs.append(str(p))
        specs.append(NodeSpec(f"node{n}", dirs))
    nodes = start_cluster(specs, "obs-secret", set_drive_count=4,
                          parity=1, block_size=16 * 1024,
                          backend="numpy")
    srv = S3Server(nodes[0].layer, access_key="ck", secret_key="cs")
    srv.start()
    try:
        c = S3Client(srv.endpoint, "ck", "cs")
        with trace.HTTP_TRACE.subscribe() as sub:
            c.make_bucket("xbkt")
            c.put_object("xbkt", "xobj", b"q" * 40000)
            c.get_object("xbkt", "xobj")
            spans = list(sub.drain(2000, timeout=3.0))
        https = [s for s in spans if s.get("type") == "http"
                 and s["funcName"] == "PutObject"]
        assert https
        rid = https[0]["requestID"]
        assert rid
        node1_roots = tuple(specs[1].drive_dirs)
        # node1's drive-local spans (emitted inside its RPC handler
        # threads) carry node0's request ID
        peer_disk = [
            s for s in spans if s.get("type") == "storage"
            and not s.get("storage", {}).get("remote")
            and s.get("storage", {}).get("drive", "")
            .startswith(node1_roots)]
        assert peer_disk, "no drive-local span from the peer node"
        assert any(s.get("requestID") == rid for s in peer_disk)
        # and the internode client+server spans correlate too
        internode = [s for s in spans if s.get("type") == "internode"
                     and s.get("requestID") == rid]
        sides = {s["internode"]["side"] for s in internode}
        assert {"client", "server"} <= sides
    finally:
        srv.stop()
        for node in nodes:
            node.stop()

def test_cross_node_tree_assembles_idle_from_rings(tmp_path):
    """ISSUE 17 e2e: a PUT served by node0 fans shards to node1 over
    internode RPC with ZERO trace subscribers — yet the causal rings
    alone reconstruct the full cross-node tree: node1's drive ops knit
    under the internode client span via the X-Span-Parent header, the
    quorum gating row rides the quorum.write span, and nothing in the
    peer subtree is an orphan."""
    from minio_tpu.cluster import NodeSpec, start_cluster
    from minio_tpu.obs import tracetree
    specs = []
    for n in range(2):
        dirs = []
        for d in range(2):
            p = tmp_path / f"node{n}-drive{d}"
            p.mkdir()
            dirs.append(str(p))
        specs.append(NodeSpec(f"node{n}", dirs))
    nodes = start_cluster(specs, "obs-secret", set_drive_count=4,
                          parity=1, block_size=16 * 1024,
                          backend="numpy")
    srv = S3Server(nodes[0].layer, access_key="ck", secret_key="cs")
    srv.start()
    try:
        assert not trace.active()
        c = S3Client(srv.endpoint, "ck", "cs")
        c.make_bucket("treebkt")
        c.put_object("treebkt", "tobj", b"q" * 200_000)
        # the handler stamps its completion record after flushing
        rid = ""
        for _ in range(50):
            recs = [r for r in srv.flightrec.query(limit=50)
                    if r.get("api") == "PutObject"]
            if recs:
                rid = recs[-1]["requestID"]
                break
            time.sleep(0.05)
        assert rid, "PutObject never landed in the flight recorder"
        trees = tracetree.assemble(tracetree.local_spans(rid=rid))
        assert len(trees) == 1
        root = trees[0]
        assert root["spanID"] == rid and root["type"] == "http"
        assert not root.get("partial")
        # flatten with parent links intact
        flat = []

        def walk(node):
            flat.append(node)
            for ch in node.get("children", ()):
                walk(ch)

        walk(root)
        names = [s["name"] for s in flat]
        # the quorum critical-path span carries its gating row even
        # though nobody subscribed during the request
        gated = [s for s in flat if s["name"] == "quorum.write"]
        assert gated and all("gating" in s for s in gated), names
        g = gated[0]["gating"]
        assert g["k"] >= 1 and g["wallNs"] >= g["kthNs"] >= 0
        # internode client spans made it into the tree...
        inode = [s for s in flat if s["type"] == "internode"]
        assert inode, names
        # ...and node1's drive-local ops (labels under its drive
        # roots) rode the wire context: present AND knitted — their
        # parentID resolved to a live span, never the orphan rewire
        node1_roots = tuple(specs[1].drive_dirs)
        peer_disk = [s for s in flat if s["type"] == "storage"
                     and s.get("label", "").startswith(node1_roots)]
        assert peer_disk, "no peer drive span in the assembled tree"
        assert not any(s.get("orphan") for s in peer_disk), peer_disk
        # every peer drive op's parent chain reaches the http root
        by_sid = {s["spanID"]: s for s in flat}
        parents = {}
        for s in flat:
            for ch in s.get("children", ()):
                parents[ch["spanID"]] = s["spanID"]
        for s in peer_disk:
            sid, hops = s["spanID"], 0
            while sid != rid and hops < 64:
                sid = parents.get(sid, rid)
                hops += 1
            assert sid == rid
        assert all(s["spanID"] in by_sid for s in peer_disk)
    finally:
        srv.stop()
        for node in nodes:
            node.stop()


# -- legs and link bytes of the device codec path (ISSUE 23) -----------------

def _leg_counts() -> dict:
    """{(op, leg): observations} of mt_tpu_leg_seconds."""
    from minio_tpu.admin.metrics import GLOBAL
    out = {}
    for (name, labels, buckets), h in GLOBAL.hist_snapshot().items():
        if name == "mt_tpu_leg_seconds":
            d = dict(labels)
            out[(d["op"], d["leg"])] = h[len(buckets)]
    return out


def _tpu_counters() -> dict:
    """{(family, op, dir or backend or kind): value} of the mt_tpu_*
    counters."""
    from minio_tpu.admin.metrics import GLOBAL
    out = {}
    for (name, labels), v in GLOBAL.snapshot().items():
        if name.startswith("mt_tpu_"):
            d = dict(labels)
            out[(name, d.get("op"), d.get("dir") or d.get("backend")
                 or d.get("kind"))] = v
    return out


def test_device_put_counts_legs_and_link_bytes(tmp_path):
    """A device-codec PUT of a known size and geometry moves
    mt_tpu_leg_seconds and mt_tpu_link_bytes_total by exactly what the
    code dispatches: 2+2, 64 KiB blocks, 200,000 B = 3 full blocks and
    a 3,392 B tail.  The full blocks cross the link once: ONE fused
    encode+bitrot submission that goes out as ONE stripe-group program
    (3 of G = 32 stripes at 2+2: each real stripe up as one array, the
    zero stripes made on the device, each real stripe's results down).
    The tail block keeps the two-dispatch route: its RS
    dispatch, then the device bitrot leg.  Link bytes are array nbytes,
    padding included (the XLA forms stage a fused stripe at its exact
    width)."""
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="tpu")
    layer.make_bucket("linkb")
    legs0, ctr0 = _leg_counts(), _tpu_counters()
    layer.put_object("linkb", "obj", b"k" * 200_000)
    legs = {k: v - legs0.get(k, 0) for k, v in _leg_counts().items()}
    ctr = {k: v - ctr0.get(k, 0) for k, v in _tpu_counters().items()}

    # fused: per real stripe one upload and two fetches (parity,
    # digests), one launch for the group; the tail's RS dispatch: one
    # of each
    assert legs[("encode", "upload")] == 3 + 1, legs
    assert legs[("encode", "launch")] == 1 + 1, legs
    assert legs[("encode", "fetch")] == 6 + 1, legs
    assert legs[("encode", "dispatch")] == 1, legs
    # the staged full blocks; the tail's split and its lane pad
    assert legs[("encode", "prep")] == 3, legs
    assert legs[("encode-bitrot-group", "batch")] == 1, legs
    assert legs[("encode", "batch")] == 1, legs
    # the tail's device bitrot leg, and the two framings
    for leg in ("dispatch", "prep", "upload", "launch", "fetch"):
        assert legs[("hash", leg)] == 1, (leg, legs)
    assert legs[("hash", "frame")] == 2, legs

    k, m, shard, tail_shard = 2, 2, 32768, 1696    # ceil(3392 / 2)
    lanes = -(-tail_shard // 128) * 128            # lane pad: 1792
    link = {(op, d): ctr[("mt_tpu_link_bytes_total", op, d)]
            for op in ("encode", "hash") for d in ("h2d", "d2h")}
    # full blocks: data up once, parity and 32 B per shard down; the
    # tail stripe goes up and comes down at its lane-padded width
    assert link[("encode", "h2d")] == 3 * k * shard + k * lanes
    assert link[("encode", "d2h")] == \
        3 * (m * shard + (k + m) * 32) + m * lanes
    # the tail's shards go up again, data and parity, unpadded; 32 B
    # per shard come down
    assert link[("hash", "h2d")] == (k + m) * tail_shard
    assert link[("hash", "d2h")] == (k + m) * 32
    assert ctr[("mt_tpu_ops_total", "hash", "tpu")] == 1
    assert ctr[("mt_tpu_bytes_total", "hash", "tpu")] == \
        (k + m) * tail_shard
    assert ctr[("mt_tpu_ops_total", "encode", "tpu")] == 1
    assert ctr[("mt_tpu_bytes_total", "encode", "tpu")] == 200_000
    # rows: 4 shards per real fused stripe, then the 4 tail rows; the
    # XLA forms hash what they are handed: the group's 32 stripes
    assert ctr[("mt_tpu_hash_rows_total", None, "real")] == 16
    assert ctr[("mt_tpu_hash_rows_total", None, "hashed")] == 32 * 4 + 4
    # and what it wrote reads back
    assert bytes(layer.get_object("linkb", "obj")[1]) == b"k" * 200_000


def test_stage_sums_do_not_depend_on_the_vec_label(served):
    """``vec`` splits mt_s3_stage_seconds into its serial and async
    vectors; a selector without it (the benchmark's ``stage`` reader)
    still reads the per-{api,stage} totals the flight recorder holds."""
    from minio_tpu.admin.metrics import GLOBAL

    def sums():
        out = {}
        for (name, labels, _b), h in GLOBAL.hist_snapshot().items():
            if name == "mt_s3_stage_seconds":
                d = dict(labels)
                assert d["vec"] in ("serial", "async"), d
                key = (d["api"], d["stage"])
                out[key] = out.get(key, 0.0) + h[-1]
        return out
    before = sums()
    c = S3Client(served.endpoint, "ok", "os")
    c.make_bucket("vecbkt")
    c.put_object("vecbkt", "obj", b"v" * (1 << 20))
    c.get_object("vecbkt", "obj")
    want: dict = {}
    got: dict = {}
    for _ in range(100):    # the last observation trails the response
        recs = served.flightrec.query(limit=50)
        want = {}
        for r in recs:
            for vec in (r["stages"], r["asyncStages"]):
                for stage, ns in vec.items():
                    key = (r["api"], stage)
                    want[key] = want.get(key, 0.0) + ns / 1e9
        after = sums()
        got = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in want}
        if len(recs) == 3 and got == pytest.approx(want, abs=1e-9):
            break
        time.sleep(0.02)
    assert len(recs) == 3, recs
    assert got == pytest.approx(want, abs=1e-9)
    # the ETag md5 of a 1 MiB PUT ran, as async detail only
    put = next(r for r in recs if r["api"] == "PutObject")
    assert "md5" in put["asyncStages"] and "md5" not in put["stages"]

"""Causal trace plane (ISSUE 17 tentpole): quorum critical-path
attribution with a planted straggler, the always-on idle contract for
the span ring + gating engine, the reconciliation invariant
``kth_ns <= wall_ns <= enclosing-stage wall``, tree assembly semantics
(orphans, evicted roots), the admin ``trace-tree`` route, and the OTLP
export mapping.
"""

import json
import time

import pytest

from minio_tpu.admin.metrics import GLOBAL
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.obs import critpath, stages, trace, tracetree
from minio_tpu.s3.client import S3Client
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.faulty import SlowDisk
from minio_tpu.storage.xl_storage import XLStorage


def _gating_counts(plane: str) -> dict[str, float]:
    """{drive: count} for mt_quorum_gating_total on one plane."""
    out = {}
    for (name, labels), v in GLOBAL.snapshot().items():
        if name != "mt_quorum_gating_total":
            continue
        d = dict(labels)
        if d.get("plane") == plane:
            out[d.get("drive", "")] = v
    return out


# -- critpath.record unit tier -----------------------------------------------

def test_record_attributes_kth_and_straggler():
    t0 = 1_000_000
    labels = ["d0", "d1", "d2", "d3"]
    ends = [t0 + 5_000_000, t0 + 1_000_000, t0 + 2_000_000,
            t0 + 9_000_000]
    row = critpath.record("write", 3, labels, ends, t0)
    assert row is not None
    # quorum k=3: third completion is d0 at +5ms; the wall ended on the
    # straggler d3 at +9ms, trailing the quorum point by 4ms
    assert row[critpath.G_KTH_DRIVE] == "d0"
    assert row[critpath.G_DRIVE] == "d3"
    assert row[critpath.G_KTH_NS] == 5_000_000
    assert row[critpath.G_WALL_NS] == 9_000_000
    assert row[critpath.G_TRAIL_NS] == 4_000_000
    assert row[critpath.G_K] == 3 and row[critpath.G_N] == 4
    r = critpath.render_row(row)
    assert r["drive"] == "d3" and r["kthDrive"] == "d0"
    assert r["trailNs"] == 4_000_000


def test_record_excludes_errored_children_and_clamps_to_t0():
    t0 = 1_000_000
    labels = ["a", "b", "c"]
    # c finished LAST but errored: it cannot be the quorum decider or
    # the gating drive; b completed before the reduction began (drain
    # vectors) and clamps to t0
    ends = [t0 + 3_000_000, t0 - 500_000, t0 + 9_000_000]
    row = critpath.record("write_drain", 2, labels, ends,
                          t0, errs=[None, None, RuntimeError("boom")])
    assert row is not None
    assert row[critpath.G_DRIVE] == "a"
    assert row[critpath.G_KTH_DRIVE] == "a"
    assert row[critpath.G_KTH_NS] == 3_000_000
    assert row[critpath.G_TRAIL_NS] == 0
    # below quorum (1 survivor, k=2 clamps to survivors): row still
    # attributes; with ZERO completions there is no critical path
    assert critpath.record("write", 2, labels, [0, 0, 0], t0) is None


def test_record_rides_ring_and_stage_clock():
    clock = stages.StageClock()
    stages.set_clock(clock)
    trace.set_request_id("gat-rid-1")
    try:
        t0 = critpath.now_ns()
        row = critpath.record("read", 1, ["dx"], [t0 + 1000], t0)
        assert row is not None
    finally:
        trace.set_request_id("")
        stages.clear()
    assert clock.gatings and clock.gatings[0] is row
    rows = [r for r in trace.SPANS.snapshot()
            if r[trace._R_RID] == "gat-rid-1"]
    assert rows, "gating span missing from the ring"
    assert rows[-1][trace._R_NAME] == "quorum.read"
    assert rows[-1][trace._R_EXTRA] is row


# -- planted straggler --------------------------------------------------------

def test_planted_slowdisk_dominates_write_gating(tmp_path):
    """The ISSUE 17 acceptance: wrap ONE drive of six in SlowDisk and
    storm the write path — that drive must dominate
    mt_quorum_gating_total{plane="write"} (it ends every fan-out wall)
    while the puts themselves stay fast: quorum completion never waits
    for the straggler, which is the entire point of the attribution."""
    disks = []
    for i in range(6):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    slow_ep = disks[3].endpoint()
    delay_s = 0.03
    disks[3] = SlowDisk(disks[3], delay_s=delay_s)
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    # on a 1-core CI host the layer serializes fan-outs (the pool buys
    # nothing for local drive ops) — but serial execution makes the
    # LAST drive in the shuffled order end every wall, which is
    # exactly the positional noise attribution must not measure.
    # Force the pooled fan-out: sleeps overlap fine on one core, so
    # the planted delay (not the shuffle) decides who ends last — the
    # same regime as any real multi-core / remote-drive deployment.
    layer._serial_fanout = False
    layer.make_bucket("slowb")
    # the process's first put pays the one-off warm-up (native
    # libraries loaded or built, pools started): seconds on a loaded
    # worker, and nothing to do with the straggler — keep it untimed
    layer.put_object("slowb", "warmup", b"s" * 64_000)
    before = _gating_counts("write")
    n = 10
    durs = []
    for i in range(n):
        t0 = time.monotonic()
        # inline-sized (< 128 KiB): the commit is one per-drive
        # write_metadata fan-out with no etag gate parking every
        # drive's end on the same release point
        layer.put_object("slowb", f"o{i}", b"s" * 64_000)
        durs.append(time.monotonic() - t0)
    after = _gating_counts("write")
    delta = {d: after.get(d, 0) - before.get(d, 0) for d in after}
    assert delta.get(slow_ep, 0) >= n, delta
    others = [v for d, v in delta.items() if d != slow_ep]
    assert delta[slow_ep] > max(others, default=0), delta
    # the puts stay fast: each waits out the planted delay about once
    # (its commit fan-out), never once per drive.  The bound is
    # relative to that delay and on the MEDIAN put, not an absolute
    # second on the slowest: puts parked by a loaded ``-n 6`` worker
    # must not trip it
    durs.sort()
    assert durs[n // 2] < 6 * delay_s, durs


# -- idle contract ------------------------------------------------------------

def test_gating_idle_contract_no_span_dicts(tmp_path, monkeypatch):
    """Zero subscribers: a put's quorum reductions and drive ops build
    not one span dict — compact ring tuples only — yet the gating rows
    still land in the ring, queryable after the fact."""
    calls = {"span": 0, "trace": 0}
    real_span = trace.make_span
    monkeypatch.setattr(
        trace, "make_span",
        lambda *a, **k: (calls.__setitem__("span", calls["span"] + 1),
                         real_span(*a, **k))[1])
    real_trace = trace.make_trace
    monkeypatch.setattr(
        trace, "make_trace",
        lambda *a, **k: (calls.__setitem__("trace", calls["trace"] + 1),
                         real_trace(*a, **k))[1])
    assert not trace.active()
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    trace.set_request_id("idle-rid-7")
    try:
        layer.make_bucket("idleb")
        layer.put_object("idleb", "obj", b"i" * 200_000)
    finally:
        trace.set_request_id("")
    assert calls == {"span": 0, "trace": 0}, \
        "span dicts built with no consumer"
    mine = [r for r in trace.SPANS.snapshot()
            if r[trace._R_RID] == "idle-rid-7"]
    assert any(r[trace._R_NAME] == "quorum.write" for r in mine), \
        [r[trace._R_NAME] for r in mine]
    assert all(isinstance(r, tuple) for r in mine)


# -- reconciliation -----------------------------------------------------------

def test_gating_reconciles_with_stage_clock(tmp_path):
    """The tentpole invariant: every gating row's offsets are measured
    on the StageClock's monotonic clock, so
    kth_ns <= wall_ns <= enclosing-stage wall holds EXACTLY — the
    critical path is a decomposition of the stage vector, not a second
    clock drifting beside it."""
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    layer.make_bucket("recb")
    clock = stages.StageClock()
    stages.set_clock(clock)
    t0 = time.monotonic_ns()
    try:
        layer.put_object("recb", "obj", b"r" * 200_000)
        layer.get_object("recb", "obj")
    finally:
        dur = time.monotonic_ns() - t0
        stage_ns, _async_ns, _un = clock.finish(dur)
        gatings = list(clock.gatings)
        stages.clear()
    assert gatings, "no quorum reduction recorded"
    planes = {g[critpath.G_PLANE] for g in gatings}
    assert "write" in planes
    # read_meta's fan-out runs before the shard stream opens, outside
    # any named stage (it reconciles into "other"), so it carries no
    # enclosing-stage bound here.  The bound is the enclosing stage's
    # WALL; the serial vector holds exclusive self times, so the stages
    # charged inside it (an enqueue that parks > 0.5 ms is
    # ``write_enqueue``, the governor's admission ``memgov``) are added
    # back — the gating wall spans them too.
    enclosing = {"write": "drive_commit", "write_drain": "write_drain",
                 "commit": "drive_commit", "read": "drive_read"}
    nested = {"drive_commit": ("write_enqueue", "memgov")}
    for g in gatings:
        assert 0 <= g[critpath.G_KTH_NS] <= g[critpath.G_WALL_NS]
        assert g[critpath.G_TRAIL_NS] == \
            g[critpath.G_WALL_NS] - g[critpath.G_KTH_NS]
        assert g[critpath.G_WALL_NS] <= dur
        st = enclosing.get(g[critpath.G_PLANE])
        if st and st in stage_ns:
            wall = stage_ns[st] + sum(stage_ns.get(n, 0)
                                      for n in nested.get(st, ()))
            assert g[critpath.G_WALL_NS] <= wall, (g, st, stage_ns)


# -- tree assembly ------------------------------------------------------------

def _span(rid, sid, parent, name="op", start=100, typ="storage"):
    return {"requestID": rid, "spanID": sid, "parentID": parent,
            "type": typ, "name": name, "startNs": start,
            "durationNs": 10}


def test_assemble_knits_children_and_marks_orphans():
    spans = [
        _span("r1", "r1", "", name="PutObject", typ="http", start=1),
        _span("r1", "c1", "r1", start=5),
        _span("r1", "c2", "c1", start=7),
        _span("r1", "lost", "evicted-parent", start=9),
        _span("r2", "solo", "r2", start=20),     # root aged out
    ]
    trees = tracetree.assemble(spans)
    assert len(trees) == 2
    t1 = trees[0]
    assert t1["spanID"] == "r1" and t1["name"] == "PutObject"
    kids = {c["spanID"]: c for c in t1["children"]}
    assert set(kids) == {"c1", "lost"}
    assert kids["lost"].get("orphan") is True
    assert [g["spanID"] for g in kids["c1"]["children"]] == ["c2"]
    t2 = trees[1]
    assert t2.get("partial") is True and t2["name"] == "(root evicted)"
    assert [c["spanID"] for c in t2["children"]] == ["solo"]
    assert tracetree.span_count(t1) == 4


def test_filter_trees_api_duration_errors():
    trees = tracetree.assemble([
        dict(_span("a", "a", "", name="PutObject", typ="http",
                   start=10), durationNs=50_000_000),
        dict(_span("b", "b", "", name="GetObject", typ="http",
                   start=20), durationNs=1_000, status=503),
    ])
    assert [t["requestID"] for t in
            tracetree.filter_trees(trees)] == ["b", "a"]
    assert [t["requestID"] for t in
            tracetree.filter_trees(trees, api="PutObject")] == ["a"]
    assert [t["requestID"] for t in
            tracetree.filter_trees(trees, min_duration_ms=1.0)] == ["a"]
    assert [t["requestID"] for t in
            tracetree.filter_trees(trees, errors_only=True)] == ["b"]


def test_otlp_mapping_ids_parents_and_status():
    trees = tracetree.assemble([
        dict(_span("rx", "rx", "", name="PutObject", typ="http",
                   start=1000), status=200),
        dict(_span("rx", "k1", "rx", name="storage.create"),
             error="boom"),
    ])
    doc = tracetree.to_otlp(trees, node="n0")
    res = doc["resourceSpans"][0]
    attrs = {a["key"]: a["value"] for a in res["resource"]["attributes"]}
    assert attrs["service.name"]["stringValue"] == "minio-tpu"
    spans = res["scopeSpans"][0]["spans"]
    assert len(spans) == 2
    by_name = {s["name"]: s for s in spans}
    root = by_name["PutObject"]
    child = by_name["storage.create"]
    assert len(root["traceId"]) == 32 and len(root["spanId"]) == 16
    assert child["traceId"] == root["traceId"]
    assert child["parentSpanId"] == root["spanId"]
    assert root["kind"] == 2 and child["kind"] == 1
    assert child["status"]["code"] == 2
    assert int(child["endTimeUnixNano"]) - \
        int(child["startTimeUnixNano"]) == 10


# -- the admin route (single node) -------------------------------------------

@pytest.fixture
def served(tmp_path):
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="numpy")
    srv = S3Server(layer, access_key="tk", secret_key="ts")
    srv.start()
    yield srv
    srv.stop()


def _route(c, qs):
    r = c.request("GET", "/minio-tpu/admin/v1/trace-tree", qs)
    return json.loads(r.body)


def test_trace_tree_route_serves_assembled_trees(served):
    c = S3Client(served.endpoint, "tk", "ts")
    c.make_bucket("ttb")
    c.put_object("ttb", "obj", b"t" * 200_000)
    doc = {}
    for _ in range(40):       # root lands after the response flushes
        doc = _route(c, "api=PutObject&limit=5")
        if doc.get("trees"):
            break
        time.sleep(0.05)
    assert doc["trees"], doc
    tree = doc["trees"][0]
    assert tree["name"] == "PutObject" and tree["status"] == 200
    assert tree["spanID"] == tree["requestID"]
    names = set()

    def walk(n):
        names.add(n["name"])
        for ch in n.get("children", ()):
            assert ch["parentID"], ch
            walk(ch)
    walk(tree)
    assert "quorum.write" in names, names
    assert any(n.startswith("storage.") for n in names), names
    # ?rid= narrows to exactly that request
    rid = tree["requestID"]
    one = _route(c, f"rid={rid}")
    assert [t["requestID"] for t in one["trees"]] == [rid]
    # OTLP shape on demand
    otlp = _route(c, f"rid={rid}&format=otlp")
    assert otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
    # query counter moved
    assert GLOBAL.snapshot().get(
        ("mt_trace_tree_query_total", ()), 0) > 0


# -- device legs in the always-on ring (ISSUE 23) -----------------------------

@pytest.fixture
def served_device(tmp_path):
    """The served path on a DEVICE codec (XLA:CPU here: conftest pins
    JAX to the CPU, the explicit opt-in of ops/device.py)."""
    disks = []
    for i in range(4):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=64 * 1024,
                           backend="tpu")
    srv = S3Server(layer, access_key="tk", secret_key="ts")
    srv.start()
    yield srv
    srv.stop()


def test_device_put_leaves_leg_spans_under_its_root(served_device):
    """No ``admin trace`` subscriber, no profiler: a PUT through the S3
    front on a device codec still leaves its ``encode.*`` and
    ``hash.*`` legs in the ring, parented under the request, and the
    ``trace-tree`` route renders them as children of the PutObject
    root."""
    assert not trace.active()
    c = S3Client(served_device.endpoint, "tk", "ts")
    c.make_bucket("legb")
    c.put_object("legb", "obj", b"l" * 200_000)
    doc = {}
    for _ in range(40):       # root lands after the response flushes
        doc = _route(c, "api=PutObject&limit=5")
        if doc.get("trees"):
            break
        time.sleep(0.05)
    assert doc["trees"], doc
    tree = doc["trees"][0]
    assert tree["name"] == "PutObject"
    rid = tree["requestID"]
    kids = [ch for ch in tree["children"] if ch["type"] == "tpu"]
    names = {ch["name"] for ch in kids}
    want = {"encode.dispatch", "encode.prep", "encode.upload",
            "encode.launch", "encode.fetch", "hash.dispatch",
            "hash.prep", "hash.upload", "hash.launch", "hash.fetch",
            "hash.frame", "encode-bitrot-group.batch"}
    assert want <= names, sorted(want - names)
    for ch in kids:
        assert ch["parentID"] == rid and ch["requestID"] == rid, ch
        assert ch["durationNs"] >= 0 and "orphan" not in ch, ch
    # the same records, straight from the ring: compact tuples
    mine = [r for r in trace.SPANS.snapshot()
            if r[trace._R_RID] == rid and r[trace._R_TYPE] == "tpu"]
    assert {r[trace._R_NAME] for r in mine} == names
    assert all(isinstance(r, tuple) for r in mine)

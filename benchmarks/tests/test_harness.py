"""Self-tests of the benchmark's own arithmetic and loaders.  Run by hand:

    python -m pytest benchmarks/tests -q

(the repo's tier-1 run collects ``tests/`` only).  The end-to-end
rehearsals in ``test_rehearse.py`` start real servers on the CPU and take
a few minutes.
"""

import copy
import http.server
import json
import os
import socket
import struct
import threading
import time

import pytest

from benchmarks.harness import (client, manifest, readers, stats,
                                trace_reduce, traffic)
from benchmarks.harness.deploy import parse_scrape
from benchmarks.harness.reducers import device

BENCH = manifest.BENCH_DIR


# -- manifest ----------------------------------------------------------------

def test_manifest_loads_and_every_cell_resolves():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"])
        assert any(e["name"] == "setup_s" for e in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        moved = {e["name"] for e in cell.end_to_end}
        assert all(e["moves"] in moved for e in cell.per_layer), \
            "a per-layer metric is reported only where the metric it moves is"


@pytest.mark.parametrize("path,value", [
    (("workloads", 0, "name"), "n16 put"),          # space
    (("workloads", 0, "name"), "n16/put"),          # slash
    (("workloads", 0, "traffic"), "a,b"),           # comma
    (("end_to_end", 0, "name"), "x" * 65),          # too long
    (("end_to_end", 0, "name"), "-lead"),           # starts with -
    (("end_to_end", 0, "unit"), "ops per s"),       # space in a unit
    (("end_to_end", 0, "unit"), "\u00b5s"),    # the Greek letter
    (("end_to_end", 0, "unit"), "x" * 17),
    (("end_to_end", 0, "better"), "faster"),
    (("end_to_end", 0, "bound"), 0.5),
    (("end_to_end", 0, "source"), "program_span"),  # not for end-to-end
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 0, "layer"), "two\nlines"),
    (("configs", 0, "reduced"), ["bad key"]),
    (("run_seconds",), 52),
    (("command",), ["python3", "/abs/run.py"]),
    (("command",), ["python3", "../out/run.py"]),
])
def test_manifest_refuses(path, value):
    m = copy.deepcopy(manifest.load_manifest())
    obj = m
    for p in path[:-1]:
        obj = obj[p]
    obj[path[-1]] = value
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m)


def test_manifest_refuses_unknown_key_and_duplicates():
    m = copy.deepcopy(manifest.load_manifest())
    m["per_layer"][0]["why"] = "no such key on a metric"
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m)
    m = copy.deepcopy(manifest.load_manifest())
    m["workloads"].append(dict(m["workloads"][0], name="again"))
    with pytest.raises(manifest.ManifestError):     # same config/traffic pair
        manifest.validate(m)


# -- metric arithmetic ---------------------------------------------------------

def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 50) is None


@pytest.mark.parametrize("n,q,beyond", [(200, 95, 10), (199, 95, 9),
                                        (20, 50, 10), (1, 50, 0)])
def test_samples_beyond(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond


def test_tail_percentile_needs_ten_beyond():
    assert stats.tail_percentile(list(range(199)), 95) is None
    assert stats.tail_percentile(list(range(200)), 95) == 189


def _rec(t, op, t0, t1, ok=True, size=10):
    return [t, op, t0, t1, size, ok]


def test_window_counts_completions_and_rate():
    recs = [_rec(0, "PUT", 0.0, 0.9), _rec(0, "PUT", 0.9, 1.5),
            _rec(1, "GET", 1.0, 2.0), _rec(1, "GET", 2.0, 3.1),
            _rec(1, "GET", 1.2, 1.3, ok=False)]
    win = stats.in_window(recs, 1.0, 3.0)
    assert len(win) == 3                       # by completion time
    assert stats.rate_per_s(win, 2.0) == 1.0   # failed op not counted
    assert stats.latencies_ms(win, "GET") == [1000.0]
    v, n = stats.evaluate({"stat": "latency_percentile", "op": "PUT",
                           "q": 50}, win, 2.0, 0.0)
    assert (round(v, 6), n) == (600.0, 1)
    assert stats.evaluate({"stat": "setup"}, win, 2.0, 12.5) == (12.5, 1)


def test_generator_overhead_share():
    # one thread, window [0, 10]: busy 0-4 and 5-10, so 1 s of 10 is gap
    recs = [_rec(0, "PUT", 0.0, 4.0), _rec(0, "PUT", 5.0, 10.0)]
    assert stats.generator_overhead_share(recs, 0.0, 10.0) == \
        pytest.approx(0.1)


SCRAPE0 = """# HELP x
mt_s3_stage_seconds_sum{api="PutObject",stage="encode"} 10.0
mt_s3_stage_seconds_sum{api="PutObject",stage="other"} 1.0
mt_s3_stage_seconds_sum{api="GetObject",stage="decode"} 2.0
mt_s3_requests_api_total{api="PutObject"} 100
mt_s3_requests_api_total{api="GetObject"} 50
mt_cache_hits_total 10
mt_cache_misses_total 10
"""
SCRAPE1 = SCRAPE0.replace("} 10.0", "} 16.0").replace(
    '"PutObject"} 100', '"PutObject"} 120').replace(
    "hits_total 10", "hits_total 40")


def _ctx():
    def parse(t):
        out = {}
        for fam, labels, v in parse_scrape(t):
            out.setdefault(fam, []).append((labels, v))
        return out
    return {"scrape0": parse(SCRAPE0), "scrape1": parse(SCRAPE1),
            "info0": [{"a": {"b": 3}}, {"a": {"b": 1}}],
            "info1": [{"a": {"b": 5}}, {"a": {"b": 1}}]}


def test_scrape_differences():
    ctx = _ctx()
    stage = {"name": "m", "reader": {"kind": "stage", "api": "PutObject",
             "stages": ["encode"], "per": "PutObject", "scale": 1000}}
    assert readers.read(stage, ctx) == pytest.approx(6.0 / 20 * 1000)
    hit = {"name": "m", "reader": {"kind": "counter", "scale": 100,
           "num": [{"family": "mt_cache_hits_total"}],
           "den": [{"family": "mt_cache_hits_total"},
                   {"family": "mt_cache_misses_total"}]}}
    assert readers.read(hit, ctx) == pytest.approx(100.0)   # 30 of 30 new
    info = {"name": "m", "reader": {"kind": "info", "path": "a.b"}}
    assert readers.read(info, ctx) == 2.0
    # nothing to read -> None, and the harness leaves the metric out
    none = {"name": "m", "reader": {"kind": "counter",
            "num": [{"family": "mt_node_rpc_tx_bytes_total"}]}}
    assert readers.read(none, ctx) is None
    idle = {"name": "m", "reader": {"kind": "stage", "api": "GetObject",
            "stages": ["decode"], "per": "GetObject"}}
    assert readers.read(idle, ctx) is None      # no GET in the window


# -- trace reduction ---------------------------------------------------------------

def _fixture():
    with open(os.path.join(BENCH, "fixtures", "trace_small.json")) as f:
        return json.load(f)


def test_trace_reduce_busy_programs_gaps():
    s = trace_reduce.summarize(_fixture())
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(1.0)
    # 100-200, 250-300 (copy.3 nested inside fusion.7), 600-700 ms
    assert s["busy_s"] == pytest.approx(0.25)
    progs = {p[0]: (p[1], p[2]) for p in s["programs"]}
    assert progs["jit__run_nat"] == (pytest.approx(0.2), 2)
    assert progs["jit__gf2_apply_bm"] == (pytest.approx(0.05), 1)
    ops = dict(s["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.12)
    gaps = dict(s["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(0.75)
    # the 300-600 ms gap lies inside the host's TransferToDevice (310-590)
    assert gaps["host: TransferToDevice"] == pytest.approx(0.3)
    # the 0-100 ms gap: BufferFromHostBuffer (20-80) covers most of it
    assert gaps["host: BufferFromHostBuffer"] == pytest.approx(0.1)
    # no host event fits 200-250 ms: filed under the program that ran next
    assert gaps["waiting for jit__gf2_apply_bm"] == pytest.approx(0.05)
    assert gaps["after the last program of the slice"] == pytest.approx(0.3)


def test_op_label_shortens_hlo_text():
    hlo = ('%_run_nat.1 = u32[1,32,1,128]{3,2,1,0:T(1,128)} custom-call('
           'u8[128,874496]{1,0:T(8,128)(4,1)} %x2d.1), custom_call_target='
           '"tpu_custom_call"')
    assert trace_reduce.op_label(hlo) == "_run_nat (custom-call)"
    assert trace_reduce.op_label("%pad.1 = u8[128,8]{1,0} pad(u8[16,8]{1,0} "
                                 "%a, u8[] %c), padding=0_112") == "pad (pad)"
    assert trace_reduce.op_label("fusion.7") == "fusion.7"


def test_trace_reduce_clips_to_the_shims_slice():
    p0 = 1700000000000000000
    s = trace_reduce.summarize(_fixture(),
                               (p0 + 150_000_000, p0 + 650_000_000))
    assert s["window_s"] == pytest.approx(0.5)
    assert s["busy_s"] == pytest.approx(0.05 + 0.05 + 0.05)


def test_trace_without_a_tpu_plane_is_refused_outside_rehearsal():
    planes = [p for p in _fixture() if not p["name"].startswith("/device")]
    with pytest.raises(trace_reduce.TraceError, match="no /device:TPU:"):
        trace_reduce.summarize(planes)
    # the rehearsal's CPU trace: no XLA:CPU thunk either -> nothing to read
    assert trace_reduce.summarize(planes, rehearse=True) is None


def test_device_events_outside_the_slice_are_refused_outside_rehearsal():
    p0 = 1700000000000000000
    late = (p0 + 800_000_000, p0 + 900_000_000)     # after the last op
    with pytest.raises(trace_reduce.TraceError, match="outside the slice"):
        trace_reduce.summarize(_fixture(), late)
    # between two ops, inside the events' extent: an idle slice, no error
    assert trace_reduce.summarize(
        _fixture(), (p0 + 350_000_000, p0 + 550_000_000)) is None


def test_device_reducer_roofline_and_idle():
    s = trace_reduce.summarize(_fixture())
    size, k, m = 10 * 2**20, 12, 4
    out = device.reduce({"summaries": [s], "puts": [size] * 5, "k": k,
                         "m": m, "peaks": {"hbm_bytes_per_s": 819e9}})
    assert out["device_idle_pct"] == pytest.approx(75.0)
    assert out["device_ms_per_put"] == pytest.approx(50.0)
    least = 5 * size * 16 / 12 / 819e9
    assert out["codec_roofline_pct"] == pytest.approx(100 * least / 0.25)
    assert device.reduce({"summaries": [None]}) == {}


# -- traffic -------------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["warp-put-10m", "warp-mixed-10m",
                                 "small-zipf"])
def test_op_stream_is_a_function_of_the_seed(mix):
    m = manifest.load_data("traffic", mix)

    def walk(seed):
        s = traffic.OpStream(m, seed, 3)
        for c, key, size, b in traffic.preload_plan(m, seed):
            if c == 3:
                s.add(key, size, b)
        out = []
        for _ in range(400):
            op, key, size, b = s.next()
            out.append((op, key, size, b))
            if op == "PUT":
                s.add(key, size, b)
            elif op == "DELETE":
                s.remove(key)
            else:
                assert key in s.live
        return out
    a, b = walk(5), walk(5)
    assert a == b and a != walk(6)
    shares = {op: sum(1 for x in a if x[0] == op) / len(a)
              for op in traffic.OPS}
    want = {op: m["ops"].get(op, 0) / sum(m["ops"].values())
            for op in traffic.OPS}
    for op in traffic.OPS:
        assert abs(shares[op] - want[op]) < 0.08
    assert {x[2] for x in a} <= set(m["sizes"])


def test_zipf_prefers_old_keys():
    m = dict(manifest.load_data("traffic", "small-zipf"), ops={"GET": 1},
             read_keys={"dist": "zipf", "s": 0.99})     # the client's own
    s = traffic.OpStream(m, 1, 0)
    for i in range(50):
        s.add(f"k{i:02d}", 3000, 0)
    hits = [s.next()[1] for _ in range(4000)]
    assert hits.count("k00") > 5 * hits.count("k25") > 0


def test_zipf_is_one_law_over_the_pool_all_clients_share():
    m = manifest.load_data("traffic", "small-zipf")
    shared = [k for k, _, _ in traffic.shared_pool(m, 9)]
    assert len(shared) == m["preload_objects"] \
        - m["own_preloaded"] * m["clients"] == 200
    reads: dict = {}
    for c in range(m["clients"]):
        s = traffic.OpStream(m, 9, c)
        for pc, key, size, b in traffic.preload_plan(m, 9):
            if pc == c:
                s.add(key, size, b)
        assert len(s.pool) == m["own_preloaded"]    # the rest is shared
        for _ in range(1500):
            op, key, size, b = s.next()
            if op == "PUT":
                assert key not in s.live            # a new key, its own
                s.add(key, size, b)
            elif op == "DELETE":
                assert key not in shared            # the pool is read only
                s.remove(key)
            else:
                reads[key] = reads.get(key, 0) + 1
    total = sum(reads.values())
    h = sum(r ** -0.99 for r in range(1, 201))
    # rank 1 of the deployment, not of a client: ~17% of ALL reads
    assert max(reads, key=reads.get) == shared[0]
    assert reads[shared[0]] / total == pytest.approx(1 / h, rel=0.1)
    assert reads[shared[1]] / total == pytest.approx(2 ** -0.99 / h, rel=0.1)
    top10 = sum(reads.get(k, 0) for k in shared[:10]) / total
    assert top10 == pytest.approx(
        sum(r ** -0.99 for r in range(1, 11)) / h, rel=0.1)
    assert sum(reads.get(k, 0) for k in shared) / total > 0.93


def test_bodies_are_the_same_in_every_process():
    assert traffic.body_for(3, 1000, 1) == traffic.body_for(3, 1000, 1)
    assert traffic.body_for(3, 1000, 1) != traffic.body_for(4, 1000, 1)


# -- client: connections -----------------------------------------------------

class _Front(http.server.ThreadingHTTPServer):
    """A stand-in S3 front that records how many requests it holds at
    once and can end a connection without saying so."""
    daemon_threads = True

    def __init__(self, drop_after_reply=False):
        self.inside = self.most = self.accepted = 0
        self.mu = threading.Lock()
        front = self

        class H(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def setup(self):
                super().setup()
                with front.mu:
                    front.accepted += 1

            def do_HEAD(self):
                with front.mu:
                    front.inside += 1
                    front.most = max(front.most, front.inside)
                time.sleep(0.02)
                with front.mu:
                    front.inside -= 1
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()
                self.close_connection = drop_after_reply

            def log_message(self, *a):
                pass
        super().__init__(("127.0.0.1", 0), H)
        threading.Thread(target=self.serve_forever, daemon=True).start()

    @property
    def endpoint(self):
        return f"http://127.0.0.1:{self.server_address[1]}"


def test_connect_opens_one_connection_at_a_time():
    front = _Front()
    try:
        gen = client.Generator({
            "seed": 1, "bucket": "b", "access_key": "k", "secret_key": "s",
            "mix": {"sizes": [100], "bodies_per_size": 1, "ops": {"PUT": 1}},
            "clients": [(c, front.endpoint) for c in range(6)]})
        assert gen.connect() == {"connections": 6, "repeated": 0}
        assert front.most == 1 and front.accepted == 6
        # proven connections are kept: a second round opens none
        assert gen.connect() == {"connections": 6, "repeated": 0}
        assert front.accepted == 6
        gen.finish()
    finally:
        front.shutdown()
        front.server_close()


def test_a_connection_the_server_closed_is_reopened_not_written_to():
    front = _Front(drop_after_reply=True)
    try:
        conn = client.S3Conn(front.endpoint, "k", "s")
        assert conn.open("/b") == 0
        time.sleep(0.2)                 # the server's FIN has arrived
        assert conn.request("HEAD", "/b")[0] == 200
        assert front.accepted == 2
        conn.close()
    finally:
        front.shutdown()
        front.server_close()


def test_open_repeats_a_reset_attempt_and_counts_it():
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(5)

    def serve():
        first, _ = lst.accept()         # reset the first connection
        first.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         struct.pack("ii", 1, 0))
        first.close()
        second, _ = lst.accept()
        second.recv(65536)
        second.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: 0\r\n\r\n")
        second.close()
    t = threading.Thread(target=serve, daemon=True)
    t.start()
    conn = client.S3Conn(f"http://127.0.0.1:{lst.getsockname()[1]}", "k", "s")
    assert conn.open("/b") == 1
    conn.close()
    t.join(5)
    lst.close()
    with pytest.raises(OSError):        # nobody listens: the last is raised
        conn.open("/b", tries=2)

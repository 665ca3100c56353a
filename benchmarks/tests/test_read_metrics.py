"""The thirteen read-side per-layer metrics (PR 36) as data: each file
loads through ``manifest.Cell`` in the cells its entry lists and in no
other, validates with its entry appended, and reads the hand-computed
value from two synthetic scrapes; from a scrape of a program without the
families (the parent commit, or a PUT-only window's delta) each reads
nothing and is left out.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q
"""

import pytest

from benchmarks.harness import manifest, readers
from benchmarks.harness.deploy import parse_scrape

MIXED = ["n16.mixed-10m", "n16.small-zipf", "d4x4.mixed-10m"]
ALL = ["n16.put-10m", "n16.mixed-10m", "n16.small-zipf",
       "d4x4.mixed-10m", "n4.put-10m", "mesh4.put-10m"]
READ = "read fan-out + verify"

# one window of the warp mix on 16 drives, 4 of them local to the node
# that is asked (d4x4): per 100 operations 45 GETs, 30 HEADs, 15 PUTs,
# 10 DELETEs; 80 metadata reads of 20 ms (16 children each: they wait
# 2 ms for a thread; the call takes 3 ms on its owner, 9 ms through
# the wire for the 12 remote ones); 40 GETs read shards (5 are hits):
# 12 children each, 30 ms of drive read and 8 ms of verify of which 6 on
# a CPU, then 5 + 3 ms of copies; a GET's request thread waits 70 ms for
# its producer, a HEAD spends 18 ms in its metadata read, a DELETE 25 ms
# in its fan-out; the PUTs' codec legs: 40 ms of wall, 10 on a CPU
OPS = {"GetObject": 45, "HeadObject": 30, "PutObject": 15,
       "DeleteObject": 10}
META, READS = 80, 40
WANT = {
    "meta_reads_per_op": 0.8, "meta_read_ms": 20.0, "meta_queue_ms": 2.0,
    "meta_drive_ms": 3.0, "meta_rpc_ms": 9.0, "head_meta_ms": 18.0,
    "get_verify_ms": 8.0, "get_verify_cpu_pct": 75.0, "get_io_ms": 30.0,
    "get_copy_ms": 1000 * READS * 0.008 / 45, "get_stream_wait_ms": 70.0,
    "delete_commit_ms": 25.0, "codec_legs_cpu_pct": 25.0,
}
CELLS = {name: MIXED for name in WANT}
CELLS["meta_rpc_ms"] = ["d4x4.mixed-10m"]
CELLS["codec_legs_cpu_pct"] = ALL
LAYER = {name: READ for name in WANT}
LAYER.update(meta_rpc_ms="internode RPC",
             delete_commit_ms="writer plane + commit",
             codec_legs_cpu_pct="codec facade")


def _hist(fam, labels, n, each):
    lab = ",".join(f'{k}="{v}"' for k, v in labels.items())
    return [f"{fam}_sum{{{lab}}} {n * each}", f"{fam}_count{{{lab}}} {n}"]


def _scrape(windows: int, read_side: bool = True) -> dict:
    """The cumulative scrape after ``windows`` such windows."""
    w = windows
    lines = [f'mt_s3_requests_api_total{{api="{a}"}} {n * w}'
             for a, n in OPS.items()]
    stage = "mt_s3_stage_seconds"
    lines += _hist(stage, {"api": "PutObject", "stage": "drive_commit",
                           "vec": "serial"}, 15 * w, 0.3)
    lines += _hist(stage, {"api": "GetObject", "stage": "meta_read",
                           "vec": "async"}, 40 * w, 0.02)
    for op, legs in (("encode", ("prep", "upload", "launch", "fetch")),
                     ("hash", ("frame",))):
        for leg in legs:
            lab = {"op": op, "leg": leg}
            lines += _hist("mt_tpu_leg_seconds", lab, 15 * w, 0.008)
            if read_side:
                # one span in 16 read both clocks
                lines += _hist("mt_tpu_leg_cpu_seconds",
                               dict(lab, clock="cpu"), w, 0.002)
                lines += _hist("mt_tpu_leg_cpu_seconds",
                               dict(lab, clock="wall"), w, 0.008)
    # a dispatch leg is in the family and in no metric
    lines += _hist("mt_tpu_leg_seconds", {"op": "encode",
                                          "leg": "dispatch"}, 15 * w, 1.0)
    if read_side:
        lines += _hist(stage, {"api": "HeadObject", "stage": "meta_read",
                               "vec": "serial"}, 30 * w, 0.018)
        lines += _hist(stage, {"api": "GetObject", "stage": "stream_wait",
                               "vec": "serial"}, 45 * w, 0.07)
        lines += _hist(stage, {"api": "DeleteObject",
                               "stage": "drive_commit",
                               "vec": "serial"}, 10 * w, 0.025)
        read = "mt_read_leg_seconds"
        lines += _hist(read, {"op": "meta", "leg": "fanout"}, META * w,
                       0.02)
        lines += _hist(read, {"op": "meta", "leg": "pick"}, META * w,
                       0.001)
        lines += _hist(read, {"op": "meta", "leg": "queue"},
                       16 * META * w, 0.002)
        lines += _hist(read, {"op": "get", "leg": "queue"},
                       12 * READS * w, 0.004)
        lines += _hist(read, {"op": "get", "leg": "verify"},
                       12 * READS * w, 0.008)
        lines += _hist("mt_read_leg_cpu_seconds",
                       {"op": "get", "leg": "verify", "clock": "cpu"},
                       30 * w, 0.006)
        lines += _hist("mt_read_leg_cpu_seconds",
                       {"op": "get", "leg": "verify", "clock": "wall"},
                       30 * w, 0.008)
        lines += _hist(read, {"op": "get", "leg": "assemble"}, READS * w,
                       0.005)
        lines += _hist(read, {"op": "get", "leg": "copy_out"}, READS * w,
                       0.003)
        call = "mt_drive_call_seconds"
        lines += _hist(call, {"op": "read_version", "kind": "local"},
                       16 * META * w, 0.003)
        lines += _hist(call, {"op": "read_version", "kind": "remote"},
                       12 * META * w, 0.009)
        lines += _hist(call, {"op": "read_file_stream", "kind": "local"},
                       8 * READS * w, 0.03)
        lines += _hist(call, {"op": "read_segment", "kind": "local"},
                       4 * READS * w, 0.03)
        lines += _hist(call, {"op": "write_data_commit", "kind": "local"},
                       16 * 15 * w, 0.2)
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _cells():
    m = manifest.load_manifest()
    return {w["name"]: manifest.Cell(m, w["name"])
            for w in m["workloads"]}


@pytest.mark.parametrize("name", sorted(WANT))
def test_read_metric_loads_where_listed_and_reads_its_value(name):
    ctx = {"scrape0": _scrape(3), "scrape1": _scrape(5)}
    for cell_name, cell in _cells().items():
        spec = next((e for e in cell.per_layer if e["name"] == name), None)
        if cell_name not in CELLS[name]:
            assert spec is None, f"{cell_name} reports {name}"
            continue
        assert spec is not None, f"{cell_name} does not report {name}"
        assert spec["moves"] == "ops_per_s"
        assert spec["layer"] == LAYER[name]
        assert spec["reader"]["kind"] in ("counter", "stage")
        assert readers.read(spec, ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_read_metric_reads_nothing_from_a_program_without_it(name):
    """The driver lays these files over the parent's checkout too: its
    scrape has neither family nor stage, and the line leaves the metric
    out instead of failing the run."""
    ctx = {"scrape0": _scrape(3, read_side=False),
           "scrape1": _scrape(5, read_side=False)}
    spec = next(e for e in _cells()[CELLS[name][0]].per_layer
                if e["name"] == name)
    assert readers.read(spec, ctx) is None


def test_a_put_only_cell_loads_none_of_the_read_side():
    cells = _cells()
    for cell_name in ("n16.put-10m", "n4.put-10m", "mesh4.put-10m"):
        names = {e["name"] for e in cells[cell_name].per_layer}
        assert names & set(WANT) == {"codec_legs_cpu_pct"}, cell_name
        # and the files it had are the files it has
        assert {"front_ms", "put_other_ms", "commit_queue_ms"} <= names


def test_manifest_validates_with_the_thirteen_entries():
    """In the order ISSUE 36 lists them, after every entry the manifest
    had (by name: a later PR appends after them)."""
    m = manifest.load_manifest()
    names = [e["name"] for e in m["per_layer"]]
    at = [names.index(n) for n in (
        "mesh_chip_busy_skew_pct",
        "meta_reads_per_op", "meta_read_ms", "meta_queue_ms",
        "meta_drive_ms", "meta_rpc_ms", "head_meta_ms", "get_verify_ms",
        "get_verify_cpu_pct", "get_io_ms", "get_copy_ms",
        "get_stream_wait_ms", "delete_commit_ms", "codec_legs_cpu_pct")]
    assert at == sorted(at)
    for e in m["per_layer"]:
        if e["name"] in WANT:
            assert e["workloads"] == CELLS[e["name"]]
            assert e["layer"] == LAYER[e["name"]]

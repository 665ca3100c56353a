"""The ``mesh4-ec12p4`` configuration and its cell ``mesh4.put-10m``
(PR 33): the manifest with the new entries, the arithmetic of
``reducers/mesh.py`` on hand-made summaries, and a CPU rehearsal of the
cell.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest, readers
from benchmarks.harness.reducers import device as device_reducer
from benchmarks.harness.reducers import mesh as mesh_reducer
from benchmarks.tests.tail_cells import tail_block_cells

CELL = "mesh4.put-10m"
NEW = ("rs_fused_roofline", "mesh_collective_ms_per_put",
       "mesh_chip_busy_skew_pct")
SIZE = 10485760
PEAKS = {"hbm_bytes_per_s": 819e9}


# -- the manifest with the new entries ----------------------------------------

def test_manifest_holds_the_configuration_and_its_cell():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    entry = next(c for c in m["configs"] if c["name"] == "mesh4-ec12p4")
    assert cell.chips == 4 and cell.traffic["name"] == "warp-put-10m"
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert "d4x4.mixed-10m" in four and CELL in four
    assert [w for w in m["workloads"] if w["name"] == CELL] == [{
        "name": CELL, "config": "mesh4-ec12p4", "traffic": "warp-put-10m",
        "chips": 4, "why": cell.file["why"]}]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"]) == \
        ["duration", "objects"]
    for ref in ("format-erasure.go:896-906", "object-api-common.go:32",
                "README", "--backend mesh", "warp put"):
        assert ref in entry["source"], ref
    cfg = cell.config
    n16 = manifest.load_data("configs", "n16-ec12p4")
    assert cfg["drives"] == 16 and cfg["chips"] == 4
    assert len(cfg["processes"]) == 1           # ONE process over the chips
    proc = cfg["processes"][0]
    assert proc["drives"] == list(range(16)) and proc["env"] == {}
    assert proc["argv"] == n16["processes"][0]["argv"][:-1] + ["mesh"]
    f = cfg["fixes"]
    assert f["backend"] == "mesh" and f["mesh"] == {"stripe": 1, "shard": 4}
    assert f["data_shards_per_chip"] * f["mesh"]["shard"] == f["data_shards"]
    # everything but the backend and its layout is n16-ec12p4's
    assert {k: v for k, v in f.items() if k not in (
        "backend", "mesh", "data_shards_per_chip")} == \
        {k: v for k, v in n16["fixes"].items() if k != "backend"}
    assert cfg["guarantees"] == n16["guarantees"]       # unweakened
    assert {e["name"] for e in cell.end_to_end} == {"ops_per_s", "setup_s"}


def test_the_new_metrics_are_the_cells_alone_and_hash_launch_is_not_its():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    mine = {e["name"]: e for e in cell.per_layer}
    for name in NEW:
        entry = next(e for e in m["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert mine[name]["moves"] == "ops_per_s"
        assert mine[name]["source"] == "device_trace"
        assert mine[name]["reader"]["reducer"] == "mesh"
    assert (mine["rs_fused_roofline"]["unit"],
            mine["rs_fused_roofline"]["layer"]) == ("%", "kernels")
    assert mine["mesh_chip_busy_skew_pct"]["layer"] == "device"
    # the fused route launches no second hash program: the accepted
    # metric keeps the cells whose bodies end in a tail block
    assert "hash_launch_ms" not in mine
    tails = tail_block_cells(m)
    assert CELL not in tails
    assert next(e for e in m["per_layer"]
                if e["name"] == "hash_launch_ms")["workloads"] == tails
    for w in m["workloads"]:
        if w["name"] == CELL:
            continue
        got = {e["name"] for e in manifest.Cell(m, w["name"]).per_layer}
        assert ("hash_launch_ms" in got) == (w["name"] in tails)
        assert not got & set(NEW)


# -- reducers/mesh.py on hand-made summaries ------------------------------------

def _summary(ops: list, busy: list[float]) -> dict:
    return {"chips": len(busy), "window_s": 5.0,
            "busy_s": sum(busy) / len(busy), "busy_s_total": sum(busy),
            "per_chip": [{"chip": f"/device:TPU:{i}", "busy_s": b}
                         for i, b in enumerate(busy)],
            "device_ops": ops, "idle_gaps": [], "programs": []}


def _ctx(ops, busy, puts=100):
    return {"summaries": [_summary(ops, busy)], "puts": [SIZE] * puts,
            "k": 12, "m": 4, "peaks": PEAKS}


MESH_OPS = [
    ["mt_rs_fused (custom-call)", 0.020],
    ["mt_hh256 (custom-call)", 0.050],
    ["fusion (fusion)", 0.030],
    ["collective-permute-start (collective-permute-start)", 0.0010],
    ["collective-permute-done (collective-permute-done)", 0.0030],
    ["all_gather (all-gather)", 0.0005],
    ["all-gather-start (all-gather-start)", 0.0002],
    ["all-reduce (all-reduce)", 0.0003],
    ["permute_like (copy)", 0.5],              # an opcode decides, not a name
]


def test_fused_roofline_is_least_bytes_over_the_kernels_chip_seconds():
    got = mesh_reducer.reduce(_ctx(MESH_OPS, [0.4] * 4))
    least_s = 100 * device_reducer.codec_min_bytes(SIZE, 12, 4) / 819e9
    # per-chip mean 0.020 s on 4 chips = 0.080 chip-seconds
    assert got["rs_fused_roofline_pct"] == \
        pytest.approx(100.0 * least_s / 0.080)
    assert got["rs_fused_roofline_pct"] < 100.0


def test_collectives_are_matched_by_opcode_with_start_and_done():
    got = mesh_reducer.reduce(_ctx(MESH_OPS, [0.4] * 4, puts=50))
    mean_s = 0.0010 + 0.0030 + 0.0005 + 0.0002 + 0.0003
    assert got["collective_ms_per_put"] == pytest.approx(1e3 * mean_s / 50)


@pytest.mark.parametrize("busy,want", [
    ([0.4, 0.4, 0.4, 0.4], 0.0),
    ([0.5, 0.4, 0.4, 0.3], 50.0),
    ([0.8, 0.0, 0.0, 0.0], 400.0),              # one chip does it all
], ids=["equal", "spread", "one-chip"])
def test_busy_skew(busy, want):
    got = mesh_reducer.reduce(_ctx(MESH_OPS, busy))
    assert got["chip_busy_skew_pct"] == pytest.approx(want)


def test_processes_weigh_by_their_chips():
    """Two traced processes: chip-seconds add, the mean is per chip."""
    ctx = _ctx(MESH_OPS, [0.4] * 4)
    ctx["summaries"].append(_summary(
        [["mt_rs_fused (custom-call)", 0.040],
         ["all-gather (all-gather)", 0.010]], [0.4]))
    got = mesh_reducer.reduce(ctx)
    least_s = 100 * device_reducer.codec_min_bytes(SIZE, 12, 4) / 819e9
    assert got["rs_fused_roofline_pct"] == \
        pytest.approx(100.0 * least_s / (0.080 + 0.040))
    assert got["collective_ms_per_put"] == \
        pytest.approx(1e3 * (0.005 * 4 + 0.010) / 5 / 100)


@pytest.mark.parametrize("ops,puts,absent", [
    ([["mt_hh256 (custom-call)", 0.4], ["mt_rs_gf2 (custom-call)", 0.01]],
     100, ("rs_fused_roofline_pct", "collective_ms_per_put")),
    (MESH_OPS, 0, ("rs_fused_roofline_pct", "collective_ms_per_put")),
    ([["mt_rs_fused_mesh (fusion)", 0.4]], 100,
     ("rs_fused_roofline_pct",)),               # the program is not the kernel
], ids=["one-chip-route", "no-put", "other-name"])
def test_reads_nothing_where_there_is_nothing_to_read(ops, puts, absent):
    got = mesh_reducer.reduce(_ctx(ops, [0.4] * 4, puts))
    for key in absent:
        assert key not in got
    assert mesh_reducer.reduce(
        {"summaries": [None], "puts": [SIZE], "k": 12, "m": 4,
         "peaks": PEAKS}) == {}
    # one chip has no skew to speak of
    assert "chip_busy_skew_pct" not in mesh_reducer.reduce(
        _ctx(ops, [0.4], puts))


def test_the_metric_files_read_the_reducer_through_the_harness():
    cell = manifest.Cell(manifest.load_manifest(), CELL)
    specs = {e["name"]: e for e in cell.per_layer if e["name"] in NEW}
    ctx = {"trace": _ctx(MESH_OPS, [0.5, 0.4, 0.4, 0.3])}
    got = {n: readers.read(s, ctx) for n, s in specs.items()}
    assert got["mesh_chip_busy_skew_pct"] == pytest.approx(50.0)
    assert 0 < got["rs_fused_roofline"] < 100
    assert got["mesh_collective_ms_per_put"] == pytest.approx(0.05)
    # a one-chip route's trace: every new metric is left out
    ctx = {"trace": _ctx([["mt_hh256 (custom-call)", 0.4]], [0.4])}
    assert [readers.read(s, ctx) for s in specs.values()] == [None] * 3


# -- the cell, rehearsed on the CPU --------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse(trace):
    """One CPU device is a 1x1 mesh: the route, its counters and legs
    are the cell's, the ring and the kernel are the chip's to run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MT_FSYNC", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147499033", "--seconds", "6", "--trace", str(trace),
         "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    if trace:
        got = set(last["metrics"])
        for name in ("codec_dispatch_ms", "codec_prep_ms", "codec_upload_ms",
                     "rs_launch_ms", "codec_fetch_ms", "bitrot_frame_ms",
                     "link_bytes_per_byte", "hash_lane_fill_pct",
                     "batch_occupancy", "device_idle_pct"):
            assert name in got, name
        assert "hash_launch_ms" not in got
        # no named kernel and no collective in XLA:CPU's thunks
        assert "rs_fused_roofline" not in got
        # one upload of the data, parity + digests down: 1 + 4/12 + a bit
        assert last["metrics"]["link_bytes_per_byte"]["value"] == \
            pytest.approx(4 / 3, rel=0.05)
    else:
        assert set(last["metrics"]) == {"ops_per_s", "setup_s"}

"""The ``n12-ec8p4-1m`` configuration and its cell ``n12.put-10m``: the
plain reference at 8+4, the manifest with the four new entries,
``fused_stripes_per_launch`` and ``rs_fused_group_roofline`` as data, and
a CPU rehearsal of the cell.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q
"""

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import manifest, readers, reference
from benchmarks.harness.deploy import parse_scrape
from benchmarks.harness.reducers import device as device_reducer
from benchmarks.tests.tail_cells import tail_block_cells

K, M = 8, 4
BS = 1 << 20
CONFIG = "n12-ec8p4-1m"
CELL = "n12.put-10m"
NEW = ("fused_stripes_per_launch", "rs_fused_group_roofline")
SIZE = 10485760


# -- the plain reference at this geometry -------------------------------------

@pytest.mark.parametrize("n", [1, 1500, BS // K])
def test_reference_agrees_with_the_programs_own_at_8p4(n):
    """Two independent copies of the same mathematics (poly 0x11d,
    systematic Vandermonde) give the same parity for seeded shards, up
    to one 1 MiB block's shard of 131,072 B."""
    from minio_tpu.ops import gf8_ref
    data = np.random.default_rng(n).integers(0, 256, (K, n), dtype=np.uint8)
    want = gf8_ref.encode_parity(data, M)
    assert np.array_equal(reference.encode_parity(data, M), want)


def test_any_eight_of_twelve_rows_invert():
    """survives_lost_drives = 4: every one of the 495 choices of 8 rows
    of the 12x8 matrix inverts and gives the data back."""
    data = np.random.default_rng(12).integers(0, 256, (K, 257),
                                              dtype=np.uint8)
    full = np.concatenate([data, reference.encode_parity(data, M)])
    matrix = reference.rs_matrix(K, K + M)
    kept = list(itertools.combinations(range(K + M), K))
    assert len(kept) == 495
    for rows in kept:
        inv = reference._invert(matrix[list(rows)])
        assert np.array_equal(reference._matmul(inv, full[list(rows)]),
                              data), rows


# -- the manifest with the new entries ----------------------------------------

def test_manifest_validates_with_the_four_entries_appended():
    """The four entries are there once each and valid, wherever later
    entries put them."""
    m = manifest.load_manifest()
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    assert [w for w in m["workloads"] if w["config"] == CONFIG] == [{
        "name": CELL, "config": CONFIG, "traffic": "warp-put-10m",
        "chips": 1, "why": manifest.load_data("workloads", CELL)["why"]}]
    for name in NEW:
        mine = [e for e in m["per_layer"] if e["name"] == name]
        assert len(mine) == 1 and mine[0]["workloads"] == [CELL]
    # whole blocks only: no tail block, so no second hash program
    hl = next(e for e in m["per_layer"] if e["name"] == "hash_launch_ms")
    assert CELL not in hl["workloads"]
    assert hl["workloads"] == tail_block_cells(m)


def test_manifest_holds_the_configuration_and_its_cell():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert cell.chips == 1 and cell.traffic["name"] == "warp-put-10m"
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"]) == \
        ["duration", "objects"]
    assert len(entry["source"]) <= 200
    for ref in ("BASELINE.json config 2", "format-erasure.go:896-906",
                "blockSizeV2", "warp put"):
        assert ref in entry["source"], ref
    cfg = cell.config
    assert cfg["drives"] == 12 and cfg["chips"] == 1
    assert [p["drives"] for p in cfg["processes"]] == [list(range(12))]
    n16 = manifest.load_data("configs", "n16-ec12p4")
    proc = cfg["processes"][0]
    assert proc["argv"] == n16["processes"][0]["argv"] + [
        "--block-size", "1048576"]
    assert proc["env"] == {}
    f, g = cfg["fixes"], cfg["guarantees"]
    assert (f["set_drive_count"], f["data_shards"], f["parity_shards"],
            f["block_size"]) == (12, K, M, BS)
    geometry = ("set_drive_count", "data_shards", "parity_shards",
                "block_size")
    # everything but the geometry and the block is n16-ec12p4's
    assert {k: v for k, v in f.items() if k not in geometry} == \
        {k: v for k, v in n16["fixes"].items() if k not in geometry}
    assert cfg["assumed"]["blockSizeV2"] == BS
    # k > m: the write quorum is k, and the set survives m lost drives
    assert g["write_quorum"] == K
    assert g["shards_expected_on_healthy_drives"] == K + M
    assert g["survives_lost_drives"] == M
    assert g["fsync_before_ack"] and g["read_your_write"] and g["byte_exact"]
    assert {e["name"] for e in cell.end_to_end} == {"ops_per_s", "setup_s"}


def test_the_new_metrics_are_the_cells_alone():
    m = manifest.load_manifest()
    mine = {e["name"]: e for e in manifest.Cell(m, CELL).per_layer}
    assert (mine["fused_stripes_per_launch"]["layer"],
            mine["fused_stripes_per_launch"]["source"],
            mine["fused_stripes_per_launch"]["unit"]) == (
        "device form", "program_counter", "stripes/program")
    assert (mine["rs_fused_group_roofline"]["layer"],
            mine["rs_fused_group_roofline"]["unit"],
            mine["rs_fused_group_roofline"]["reader"]) == (
        "kernels", "%", {"kind": "trace", "reducer": "mesh",
                         "key": "rs_fused_roofline_pct"})
    for name in NEW:
        assert mine[name]["moves"] == "ops_per_s"
    for w in m["workloads"]:
        if w["name"] != CELL:
            got = {e["name"] for e in manifest.Cell(m, w["name"]).per_layer}
            assert not got & set(NEW), w["name"]


# -- fused_stripes_per_launch as data ------------------------------------------

def _scrape(programs: dict, stripes: dict) -> dict:
    lines = [f'mt_tpu_fused_programs_total{{form="{f}"}} {n}'
             for f, n in programs.items()]
    lines += [f'mt_tpu_fused_stripes_total{{form="{f}"}} {n}'
              for f, n in stripes.items()]
    lines.append('mt_tpu_ops_total{op="encode",backend="tpu"} 1')
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _spec(name: str) -> dict:
    cell = manifest.Cell(manifest.load_manifest(), CELL)
    return next(e for e in cell.per_layer if e["name"] == name)


@pytest.mark.parametrize("d_programs,d_stripes,want", [
    ({"group": 300}, {"group": 3000}, 10.0),
    ({"stripe": 3000}, {"stripe": 3000}, 1.0),
    ({"group": 200, "stripe": 100}, {"group": 2000, "stripe": 100}, 7.0),
], ids=["groups", "one-stripe-programs", "both-forms"])
def test_stripes_per_launch_arithmetic(d_programs, d_stripes, want):
    """Δstripes / Δprograms, both forms summed, over a scrape pair."""
    spec = _spec("fused_stripes_per_launch")
    base = {"stripe": 40, "group": 7}
    s0 = _scrape(base, {"stripe": 40, "group": 70})
    s1 = _scrape({f: base[f] + d_programs.get(f, 0) for f in base},
                 {"stripe": 40 + d_stripes.get("stripe", 0),
                  "group": 70 + d_stripes.get("group", 0)})
    assert readers.read(spec, {"scrape0": s0, "scrape1": s1}) == \
        pytest.approx(want)


def test_stripes_per_launch_reads_nothing_from_a_parent():
    """The metric file is read against a parent commit's program too:
    a program without the families reads nothing, and a window without
    a launch neither."""
    spec = _spec("fused_stripes_per_launch")
    parent = _scrape({}, {})
    assert readers.read(spec, {"scrape0": parent, "scrape1": parent}) is None
    idle = _scrape({"group": 5}, {"group": 50})
    assert readers.read(spec, {"scrape0": idle, "scrape1": idle}) is None


# -- rs_fused_group_roofline through the accepted reducer ----------------------

def _trace_ctx(ops: list, puts: int = 40) -> dict:
    return {"trace": {
        "summaries": [{"chips": 1, "window_s": 5.0, "busy_s": 0.4,
                       "busy_s_total": 0.4,
                       "per_chip": [{"chip": "/device:TPU:0",
                                     "busy_s": 0.4}],
                       "device_ops": ops, "idle_gaps": [], "programs": []}],
        "puts": [SIZE] * puts, "k": K, "m": M,
        "peaks": {"hbm_bytes_per_s": 819e9}}}


def test_group_roofline_is_least_bytes_over_the_kernels_seconds():
    spec = _spec("rs_fused_group_roofline")
    got = readers.read(spec, _trace_ctx(
        [["mt_rs_fused (custom-call)", 0.030], ["fusion (fusion)", 0.002]]))
    least_s = 40 * device_reducer.codec_min_bytes(SIZE, K, M) / 819e9
    assert got == pytest.approx(100.0 * least_s / 0.030)
    assert 0 < got < 100
    # no kernel of that name in the slice (the XLA forms): left out
    assert readers.read(spec, _trace_ctx(
        [["fusion (fusion)", 0.03]])) is None


# -- the cell, rehearsed on the CPU --------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse(trace):
    """The rehearsal's bodies (``warp-put-10m``'s 1,060,921 B) are one
    1 MiB block and a tail: the route, the counters and the legs of the
    cell, the group program is the chip's to run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MT_FSYNC", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147499039", "--seconds", "6", "--trace", str(trace),
         "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    if trace:
        m = manifest.load_manifest()
        want = {e["name"] for e in manifest.metrics_for(m, "per_layer", CELL)}
        got = set(last["metrics"])
        # no named kernel in XLA:CPU's thunks
        assert got == want - {"rs_fused_group_roofline"}, got ^ want
        assert last["metrics"]["fused_stripes_per_launch"]["value"] == 1.0
    else:
        assert set(last["metrics"]) == {"ops_per_s", "setup_s"}

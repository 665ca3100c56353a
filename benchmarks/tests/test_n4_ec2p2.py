"""The ``n4-ec2p2`` configuration and its cell ``n4.put-10m`` (PR 31):
the plain reference at 2+2, the manifest with the new entries, a CPU
rehearsal of the cell, and ``hash_lane_fill_pct`` as data.  Run by hand
with the rest:

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

K = M = 2
CELL = "n4.put-10m"


# -- the plain reference at this geometry -------------------------------------

@pytest.mark.parametrize("n", [1, 1500, 5242880 // 64])
def test_reference_agrees_with_the_programs_own_at_2p2(n):
    """Two independent copies of the same mathematics (poly 0x11d,
    systematic Vandermonde) give the same parity for seeded shards."""
    from minio_tpu.ops import gf8_ref
    data = np.random.default_rng(n).integers(0, 256, (K, n), dtype=np.uint8)
    want = gf8_ref.encode_parity(data, M)
    assert np.array_equal(reference.encode_parity(data, M), want)


@pytest.mark.parametrize("kept", list(itertools.combinations(range(4), 2)),
                         ids=lambda p: f"kept{p[0]}{p[1]}")
def test_reference_recovers_from_every_two_of_four(kept):
    """survives_lost_drives = 2: any two rows of the 4x2 matrix invert."""
    data = np.random.default_rng(sum(kept)).integers(
        0, 256, (K, 4099), dtype=np.uint8)
    full = np.concatenate([data, reference.encode_parity(data, M)])
    rows = reference.rs_matrix(K, K + M)[list(kept)]
    got = reference._matmul(reference._invert(rows), full[list(kept)])
    assert np.array_equal(got, data)


# -- the manifest with the new entries ----------------------------------------

def test_manifest_holds_the_configuration_and_its_cell():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    entry = next(c for c in m["configs"] if c["name"] == "n4-ec2p2")
    assert cell.chips == 1 and cell.traffic["name"] == "warp-put-10m"
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])
    for ref in ("endpoint-ellipses.go:44", "format-erasure.go:896-906",
                "erasure-object.go:631-642", "warp put"):
        assert ref in entry["source"], ref
    cfg = cell.config
    assert cfg["drives"] == 4 and cfg["chips"] == 1
    assert [p["drives"] for p in cfg["processes"]] == [[0, 1, 2, 3]]
    n16 = manifest.load_data("configs", "n16-ec12p4")
    assert cfg["processes"][0]["argv"] == n16["processes"][0]["argv"]
    f, g = cfg["fixes"], cfg["guarantees"]
    assert (f["set_drive_count"], f["data_shards"], f["parity_shards"],
            f["block_size"]) == (4, K, M, 10485760)
    assert f["fsync"] is True and f["backend"] == "auto"
    # everything but the geometry is n16-ec12p4's
    assert {k: v for k, v in f.items() if k not in (
        "set_drive_count", "data_shards", "parity_shards")} == \
        {k: v for k, v in n16["fixes"].items() if k not in (
            "set_drive_count", "data_shards", "parity_shards")}
    # k == m: the write quorum is k + 1, a write survives one lost drive
    assert g["write_quorum"] == K + 1
    assert g["shards_expected_on_healthy_drives"] == K + M
    assert g["survives_lost_drives"] == M
    assert g["fsync_before_ack"] and g["read_your_write"] and g["byte_exact"]
    assert {e["name"] for e in cell.end_to_end} == {"ops_per_s", "setup_s"}


def test_every_cell_reports_the_lane_fill():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        spec = next((e for e in manifest.Cell(m, w["name"]).per_layer
                     if e["name"] == "hash_lane_fill_pct"), None)
        assert spec is not None, w["name"]
        assert (spec["layer"], spec["moves"], spec["unit"],
                spec["better"]) == ("kernels", "ops_per_s", "%", "higher")


# -- hash_lane_fill_pct as data ------------------------------------------------

def _scrape(dispatches: int, rows: int, tile: int = 128) -> dict:
    lines = [f'mt_tpu_hash_rows_total{{kind="real"}} {dispatches * rows}',
             f'mt_tpu_hash_rows_total{{kind="hashed"}} {dispatches * tile}',
             f'mt_tpu_ops_total{{op="hash",backend="tpu"}} {dispatches}']
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _lane_fill_spec() -> dict:
    cell = manifest.Cell(manifest.load_manifest(), CELL)
    return next(e for e in cell.per_layer
                if e["name"] == "hash_lane_fill_pct")


@pytest.mark.parametrize("rows,want", [(4, 3.125), (16, 12.5)],
                         ids=["2p2", "12p4"])
def test_lane_fill_arithmetic(rows, want):
    spec = _lane_fill_spec()
    ctx = {"scrape0": _scrape(40, rows), "scrape1": _scrape(700, rows)}
    assert readers.read(spec, ctx) == pytest.approx(want)


def test_lane_fill_reads_nothing_from_a_program_without_the_family():
    """The driver lays this file over the parent's checkout too."""
    spec = _lane_fill_spec()

    def parent(n):
        s = _scrape(n, 4)
        del s["mt_tpu_hash_rows_total"]
        return s
    assert readers.read(spec, {"scrape0": parent(40),
                               "scrape1": parent(700)}) is None


# -- the cell, rehearsed on the CPU --------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MT_FSYNC", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147499003", "--seconds", "6", "--trace", str(trace),
         "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    if trace:
        m = manifest.load_manifest()
        want = {e["name"] for e in manifest.metrics_for(m, "per_layer", CELL)}
        assert set(last["metrics"]) == want
        # the XLA form pads no rows; 3.125 is the chip's reading
        assert last["metrics"]["hash_lane_fill_pct"]["value"] == 100.0
        assert last["metrics"]["link_bytes_per_byte"]["value"] == \
            pytest.approx(4.0, rel=0.1)
    else:
        assert set(last["metrics"]) == {"ops_per_s", "setup_s"}

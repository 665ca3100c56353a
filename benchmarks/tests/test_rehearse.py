"""End-to-end rehearsals on the CPU: ``run.py --rehearse`` for a one-server
cell (traced) and the four-node cell.  They start real servers (the device
codec on XLA:CPU) and take a few minutes; nothing they print is a device
number, and ``correct`` is false by construction.

    python -m pytest benchmarks/tests/test_rehearse.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest


@pytest.mark.parametrize("cell,trace", [("n16.small-zipf", 1),
                                        ("n16.put-10m", 0),
                                        ("d4x4.mixed-10m", 0)])
def test_rehearse(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MT_FSYNC", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3", "--seconds", "6", "--trace", str(trace), "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "checks"} | ({"breakdown"} if trace
                                                else set())
    # every number compared, beside its limit: last on the line and on
    # stderr
    assert list(last)[-1] == "checks"
    for name, c in last["checks"].items():
        (rel, limit), = [(k, v) for k, v in c.items() if k != "value"]
        assert rel in ("at_most", "at_least"), name
        assert (c["value"] <= limit) if rel == "at_most" \
            else (c["value"] >= limit), name
    assert {"wrong_in_window", "wrong_on_disk",
            "encode_dispatches"} <= set(last["checks"])
    tail = p.stderr.strip().splitlines()[-len(last["checks"]):]
    assert [x.split(":")[0] for x in tail] == \
        [f"check {n}" for n in last["checks"]]
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    m = manifest.load_manifest()
    section = "per_layer" if trace else "end_to_end"
    allowed = {e["name"] for e in manifest.metrics_for(m, section, cell)}
    assert set(last["metrics"]) <= allowed and last["metrics"]
    if trace:
        assert last["device"]["busy_s"] > 0
        assert last["device"]["window_s"] > 0
    else:
        assert "setup_s" in last["metrics"]


def test_refuses_fsync_off():
    env = dict(os.environ, JAX_PLATFORMS="cpu", MT_FSYNC="0")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "n16.put-10m",
         "--seed", "1", "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip().splitlines()[-1].startswith("{")

"""``commit_body_calls_per_op`` (PR 32) as data: the file loads through
``manifest.Cell`` in every cell, reads Δ``mt_commit_body_calls_total`` /
Δ``mt_commit_body_seconds_count`` from two synthetic scrapes, and reads
nothing from a scrape of a program without the family (the parent
commit), whose line then leaves the metric out.  A file of its own
beside ``test_commit_metrics.py`` because a PR may add to the benchmark
and edit nothing it has.  Run by hand with the rest:

    python -m pytest benchmarks/tests -q
"""

import pytest

from benchmarks.harness import manifest, readers
from benchmarks.harness.deploy import parse_scrape
from benchmarks.tests.tail_cells import tail_block_cells

NAME = "commit_body_calls_per_op"
OPS = 16            # drive ops per PUT at 12+4


def _scrape(puts: float, calls_per_op: float | None) -> dict:
    lines = [f'mt_s3_requests_api_total{{api="PutObject"}} {puts}',
             f'mt_commit_body_seconds_sum {0.02 * OPS * puts}',
             f'mt_commit_body_seconds_count {OPS * puts}']
    if calls_per_op is not None:
        lines.append(
            f'mt_commit_body_calls_total {calls_per_op * OPS * puts}')
    out: dict = {}
    for fam, labels, v in parse_scrape("\n".join(lines)):
        out.setdefault(fam, []).append((labels, v))
    return out


def _cells():
    m = manifest.load_manifest()
    return [manifest.Cell(m, w["name"]) for w in m["workloads"]]


@pytest.mark.parametrize("calls_per_op", [2.0, 10.0, 2.25],
                         ids=["native", "os_form", "mixed"])
def test_calls_per_op_is_the_ratio_of_the_two_deltas(calls_per_op):
    """One native call per landed file reads 2.0, the os.* sequence 10;
    the base of 40 PUTs before the window cancels out."""
    ctx = {"scrape0": _scrape(40, calls_per_op),
           "scrape1": _scrape(50, calls_per_op)}
    for cell in _cells():
        spec = next((e for e in cell.per_layer if e["name"] == NAME), None)
        assert spec is not None, f"{cell.name} does not report {NAME}"
        assert spec["moves"] == "ops_per_s" and spec["unit"] == "calls/op"
        assert spec["better"] == "lower"
        assert spec["layer"] == "writer plane + commit"
        assert spec["reader"]["kind"] == "counter"
        assert readers.read(spec, ctx) == pytest.approx(calls_per_op)


def test_calls_per_op_reads_nothing_from_a_program_without_it():
    """The driver lays this file over the parent's checkout too: its
    scrape has no ``mt_commit_body_calls_total`` and nothing raises."""
    ctx = {"scrape0": _scrape(40, None), "scrape1": _scrape(50, None)}
    spec = next(e for e in _cells()[0].per_layer if e["name"] == NAME)
    assert readers.read(spec, ctx) is None


def test_manifest_validates_with_the_entry_appended_last():
    """The entry is there and valid wherever later entries put it."""
    m = manifest.load_manifest()
    mine = [e for e in m["per_layer"] if e["name"] == NAME]
    assert mine == [{"name": NAME, "unit": "calls/op", "better": "lower",
                     "source": "program_counter",
                     "layer": "writer plane + commit",
                     "moves": "ops_per_s"}]
    assert "workloads" not in mine[0]       # every cell has drive ops
    hl = next(e for e in m["per_layer"] if e["name"] == "hash_launch_ms")
    assert hl["workloads"] == tail_block_cells(m)

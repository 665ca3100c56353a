"""Which cells launch a second hash program: a body that ends in a tail
block, on the one-chip route (the mesh route hashes inside its fused
program).  ``hash_launch_ms`` lists exactly these cells; the self-tests
that check its list share this rule."""

from benchmarks.harness import manifest


def has_tail_block(cell: manifest.Cell) -> bool:
    fixes = cell.config["fixes"]
    return fixes["backend"] != "mesh" and any(
        size % fixes["block_size"] for size in cell.traffic["sizes"])


def tail_block_cells(m: dict) -> list[str]:
    return [w["name"] for w in m["workloads"]
            if has_tail_block(manifest.Cell(m, w["name"]))]

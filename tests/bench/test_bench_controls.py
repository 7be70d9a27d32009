"""Each cell's control, the plain reference put in the system's place with
one stated guarantee broken, has to fail the cell's limit. At a size a test
run holds; the chip readings at the cells' own sizes are in PERF.md."""
import pytest

from bench_util import bench_json
from bench import control
from bench.graph500 import graph500_csr
from bench.run import Cell

CELLS = [w["name"] for w in bench_json()["workloads"]]
# the smallest scale at which each control has hubs wide enough to bite
SCALE = {"partition": 16, "pagerank_mesh": 12}


def _limit(cell: Cell) -> float:
    if cell.traffic["job"] == "partition":
        return 0  # every vertex must match
    return cell.traffic["max_rel_err"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_fails_the_limit(cell_name):
    cell = Cell.load(cell_name)
    config = dict(cell.config, scale=SCALE[cell.traffic["job"]])
    name, value = control.reading(config, cell.traffic, seed=2**32 + 5)
    assert value > _limit(cell), (name, value)


def test_reference_meets_its_own_limit():
    """The comparison itself is sound: the reference against itself reads 0."""
    from bench import reference

    indptr, indices = graph500_csr(3, 5, 12)
    a = reference.cuttana_parallel(indptr, indices, 8, 5, num_shards=4)
    b = reference.cuttana_parallel(indptr, indices, 8, 5, num_shards=4)
    assert (a != b).sum() == 0

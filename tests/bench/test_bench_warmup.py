"""Set-up compiles the score kernel for every launch shape a partition
cell's window can meet: the shapes come from the traffic's ranges through
the stream engine's width rounding and the kernel's own tiling rule."""
import pytest

from bench_util import bench_json
from bench.jobs.partition import launch_shapes
from bench.run import Cell
from repro.kernels.partition_score.ops import kernel_tiling

CELLS = [w["name"] for w in bench_json()["workloads"]
         if Cell.load(w["name"]).traffic["job"] == "partition"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_launch_of_the_ranges_is_warmed(cell_name):
    traffic = Cell.load(cell_name).traffic
    rows, widths = traffic["warm_rows"], traffic["warm_widths"]
    shapes = set(launch_shapes(rows, widths))
    for c in range(rows[0], rows[1] + 1):
        for d in range(1, widths[1] + 1):
            # the engine's width: a power of two, at least the lower end
            w = max(widths[0], 1 << (d - 1).bit_length())
            assert kernel_tiling(c, w)[2:] in shapes, (c, d)
    # each warmed shape launches as it is, with no further padding
    assert all(kernel_tiling(c, w)[2:] == (c, w) for c, w in shapes)


def test_shape_counts():
    # 16 row tiles of 8 up to 128 and 9 of 128 beyond, by 8 widths
    assert len(launch_shapes([1, 1280], [8, 1024])) == 25 * 8
    assert launch_shapes([512, 512], [8, 16]) == [(512, 8), (512, 16)]

"""Each cell of BENCHMARK.json rehearsed end to end on the CPU: tiny graph,
score kernel in interpret mode, four virtual devices for the mesh cell. The
last line must be the result the contract fixes."""
import pytest

from bench_util import bench_json, run_bench

CELLS = [w["name"] for w in bench_json()["workloads"]]
# device-trace metrics find no device on the CPU and are left out
CPU_LAYER = {"program_counter", "program_span"}


def _expected(cell: str, trace: int) -> set[str]:
    b = bench_json()
    if not trace:
        return {
            m["name"] for m in b["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]
        }
    return {
        m["name"] for m in b["per_layer"]
        if cell in m.get("workloads", []) and m["source"] in CPU_LAYER
    }


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_with_a_result_line(cell, trace):
    line, err = run_bench(
        ["--workload", cell, "--seed", str(2**33 + 17), "--seconds", "0.3",
         "--trace", str(trace), "--rehearse"]
    )
    assert line is not None, err[-3000:]
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == _expected(cell, trace)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # every compared number is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    for text, (name, c) in zip(tail, line["checks"].items()):
        assert text.startswith(f"check {name} = ") and f"limit {c['limit']!r}" in text


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    line, err = run_bench(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    )
    assert line is None
    assert "no TPU" in err


def test_without_the_program_the_run_fails(tmp_path):
    import shutil

    from bench_util import ROOT

    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    line, err = run_bench(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse"], root=tmp_path,
    )
    assert line is None
    assert "no system under test" in err

"""The trace reduction: busy and idle time, op time by name or origin, the
longest idle gaps named by host spans, checked on a small trace."""
import json
from pathlib import Path

import pytest

import bench_util  # noqa: F401  (puts the repo root on sys.path)
from bench.trace import Trace, hlo_ops_from, op_label

DATA = Path(__file__).with_name("data")


def _small() -> Trace:
    """Window 0..100 ns. Device 0: ops at 10-30 and 20-40 (overlapping) and
    60-70; device 1: 0-50. Host: a job span over 5-95 with a dispatch span
    inside at 45-55."""
    host = [("bench.window", 0.0, 100.0), ("bench.job", 5.0, 90.0),
            ("PjitFunction(step)", 45.0, 10.0), ("before", -50.0, 40.0)]
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    a2a = "%all_to_all.3 = f32[4,2]{1,0} all-to-all(f32[4,2]{1,0} %s)"
    kern = "%fennel_scores_sharded_pallas.1 = f32[1,8,8]{2,1,0} custom-call(s32[1,8,8] %n)"
    devices = {
        "/device:TPU:0": [(op_label(fusion), 10.0, 20.0, fusion),
                          (op_label(a2a), 20.0, 20.0, a2a),
                          (op_label(kern), 60.0, 10.0, kern),
                          (op_label(fusion), -20.0, 25.0, fusion)],
        "/device:TPU:1": [(op_label(fusion), 0.0, 50.0, fusion),
                          ("while.10 (s32[]", 0.0, 60.0, "%while.10 = (s32[]) while()")],
    }
    return Trace(devices=devices, host=host)


def test_busy_is_the_union_of_ops_inside_the_window():
    t = _small()
    assert t.window() == (0.0, 100.0)
    assert t.busy_intervals("/device:TPU:0") == [(0.0, 5.0), (10.0, 40.0), (60.0, 70.0)]
    assert t.busy_s("/device:TPU:0") == pytest.approx(45e-9)
    assert t.mean_busy_s() == pytest.approx((45e-9 + 60e-9) / 2)
    assert t.window_s() == pytest.approx(100e-9)


def test_op_time_by_name_or_origin_on_the_busiest_device():
    t = _small()
    assert t.op_seconds("/device:TPU:0", ("%fusion.1 = ",)) == pytest.approx(25e-9)
    assert t.max_op_seconds(("%fusion.1 = ",)) == pytest.approx(50e-9)
    assert t.max_op_seconds(("%fennel_scores_sharded_pallas",)) == pytest.approx(10e-9)
    assert t.max_op_seconds((" all-to-all(",)) == pytest.approx(20e-9)
    assert t.max_op_seconds(("nothing",)) is None


def test_labels_and_fused_op_lookup():
    assert op_label("%copy-done = f32[1,8]{1,0:T(1,128)} copy-done((f32") == "copy-done f32[1,8]"
    hlo = (
        '  %fusion.19 = f32[73]{0} fusion(%a), kind=kCustom, metadata={op_name='
        '"jit(f)/while/body/scatter-add"}\n'
        '  ROOT %add.2 = f32[] add(%x, %y), metadata={op_name="add"}\n'
    )
    assert hlo_ops_from(hlo, "scatter") == {"fusion.19"}


def test_top_ops_and_idle_gaps():
    t = _small()
    top = t.top_ops()
    assert top[0][0] == "fusion.1 f32[8]"
    assert top[0][1] == pytest.approx((25e-9 + 50e-9) / 2)
    gaps = t.idle_gaps()
    # device 0 idles 70-100, 40-60 and 5-10; each named by the innermost
    # host span around its midpoint
    assert [g[0] for g in gaps] == ["bench.job", "PjitFunction(step)", "bench.job"]
    assert [round(g[1] * 1e9) for g in gaps] == [30, 20, 5]


def test_a_trace_without_a_window_span_is_an_error():
    with pytest.raises(ValueError):
        Trace(devices={}, host=[("bench.job", 0.0, 1.0)]).window()


def test_recorded_chip_trace_reduces_to_sane_numbers():
    """A few hundred events cut from a traced run of the fennel cell on a
    TPU v5e (see PERF.md): busy time is inside the window and the score
    kernel's events are found."""
    raw = json.loads((DATA / "trace_fennel_v5e.json").read_text())
    t = Trace(devices={k: [tuple(e) for e in v] for k, v in raw["devices"].items()},
              host=[tuple(e) for e in raw["host"]])
    assert 0 < t.mean_busy_s() < t.window_s()
    assert t.max_op_seconds(("%fennel_scores_sharded_pallas",)) > 0
    assert len(t.idle_gaps()) == 10

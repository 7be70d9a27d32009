"""The readers of the program's spans, counters and named scopes, on
hand-built runs: each reads what it names, and returns None where the
program has no such span, counter or scope (as a program without them)."""
from types import SimpleNamespace

import pytest

import bench_util
from bench.run import Run, load_module
from bench.trace import Trace, op_label

METRICS = bench_util.ROOT / "bench" / "metrics"


def _reader(name):
    return load_module(METRICS / f"{name}.py", f"test_metric_{name}").read


def _job_record(spans=None, **counters):
    tel = dict(counters)
    if spans is not None:
        tel["spans"] = {n: {"s": s, "n": 1} for n, s in spans.items()}
    return {"telemetry": tel}


def _run(records, job=None, trace=None):
    return Run(job=job, records=records, trace=trace, device_kind="TPU v5 lite")


def test_span_readers_average_over_the_window_jobs():
    records = [
        _job_record({"engine.place": 2.0, "engine.ingest": 1.0, "score.pack": 0.5,
                     "score.launch": 1.0, "score.hubs": 0.25, "engine.merge": 9.0}),
        _job_record({"engine.place": 4.0, "engine.ingest": 3.0, "score.pack": 0.5,
                     "score.launch": 2.0}),
    ]
    run = _run(records)
    assert _reader("engine_place_s")(run) == pytest.approx(3.0)
    assert _reader("engine_ingest_s")(run) == pytest.approx(2.0)
    assert _reader("score_host_s")(run) == pytest.approx((1.75 + 2.5) / 2)


@pytest.mark.parametrize("name", ["engine_place_s", "engine_ingest_s", "score_host_s"])
def test_span_readers_find_nothing_without_spans(name):
    assert _reader(name)(_run([_job_record(), _job_record()])) is None
    assert _reader(name)(_run([_job_record({"engine.merge": 1.0})])) is None


def test_pad_ratio_is_padded_over_true_slots_of_the_window():
    read = _reader("score_pad_ratio")
    run = _run([_job_record(score_slots_true=100, score_slots_padded=3000),
                _job_record(score_slots_true=300, score_slots_padded=9000)])
    assert read(run) == pytest.approx(30.0)
    assert read(_run([_job_record()])) is None
    # the host path launches nothing
    assert read(_run([_job_record(score_slots_true=0, score_slots_padded=0)])) is None


def _mesh_trace():
    gather = "%fusion.25 = f32[64]{0} fusion(f32[8] %s, s32[64] %c), kind=kLoop"
    scatter = "%fusion.26 = f32[9]{0} fusion(f32[64] %m), kind=kLoop"
    ops = [(op_label(gather), 10.0, 30.0, gather), (op_label(scatter), 40.0, 20.0, scatter)]
    return Trace(
        devices={"/device:TPU:0": ops,
                 "/device:TPU:1": [(op_label(gather), 10.0, 50.0, gather)]},
        host=[("bench.window", 0.0, 100.0)],
    )


def _compiled(scope: str):
    hlo = (
        f'  %fusion.25 = f32[64]{{0}} fusion(%s, %c), kind=kLoop, metadata={{op_name='
        f'"jit(f)/while/body/{scope}/div"}}\n'
        '  %fusion.26 = f32[9]{0} fusion(%m), kind=kLoop, metadata={op_name='
        '"jit(f)/while/body/vp.reduce/scatter-add"}\n'
    )
    return SimpleNamespace(as_text=lambda: hlo)


def test_edge_gather_reads_the_scoped_ops_on_the_busiest_device():
    read = _reader("edge_gather_ms")
    job = SimpleNamespace(iters=5, compiled=_compiled("vp.gather"))
    # 50 ns on device 1 over 2 jobs x 5 iterations
    assert read(_run([{}, {}], job, _mesh_trace())) == pytest.approx(50e-9 / 10 * 1e3)


class _Engine:
    """Builds a program whose fresh compile carries ``scope``."""

    def __init__(self, scope):
        self.scope = scope

    def build_sharded(self, mesh, iters):
        compiled = _compiled(self.scope)
        lowered = SimpleNamespace(compile=lambda: compiled)
        return SimpleNamespace(lower=lambda *args: lowered), None


def _mesh_job(served: str, fresh: str):
    return SimpleNamespace(iters=5, compiled=_compiled(served), engine=_Engine(fresh),
                           mesh=None, state0=None, arrays=())


def test_edge_gather_finds_nothing_without_the_scope():
    read = _reader("edge_gather_ms")
    assert read(_run([{}], _mesh_job("message", "message"), _mesh_trace())) is None
    freed = SimpleNamespace(iters=5, compiled=None)
    assert read(_run([{}], freed, _mesh_trace())) is None


def test_edge_gather_compiles_again_when_the_cache_served_other_names():
    """A cached executable may carry the metadata of an equal program
    without the scope: the reader then takes the names from a fresh
    compile."""
    read = _reader("edge_gather_ms")
    run = _run([{}, {}], _mesh_job("message", "vp.gather"), _mesh_trace())
    assert read(run) == pytest.approx(50e-9 / 10 * 1e3)

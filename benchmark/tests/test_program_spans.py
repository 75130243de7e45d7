"""Device idle charged to the program's own host spans
(``benchmark/program_spans.py``), on synthetic traces, on the recorded
circuit trace of ``test_trace_reduce.py`` and on a served trace recorded
on a TPU v5e chip (``record_served_trace.py``: 0.15 s of the 10-qubit
QAOA cell of ``tiny.py``); and the service metrics read from the
program's own counters."""

import gzip
import os

import pytest

from benchmark import program_spans, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_circuit_trace_idle_sums_to_the_idle():
    pd = trace_reduce.load(os.path.join(DATA, "rcs-tiny.xplane.pb"))
    reduced = trace_reduce.reduce_trace(pd)
    idle = program_spans.idle_by_program(pd)
    assert set(idle) <= {"none", "quest_tpu.circuits.run"}
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)


class _Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _ops(*busy):
    return _Line(trace_reduce.OPS_LINE,
                 [_Ev("%fusion.1 = f32[2] fusion()", s, e) for s, e in busy])


def _synthetic(devices):
    """A window of 1000 ns; the dispatcher thread holds an issue span with
    a nested prepare span, a second thread a wait span overlapping the
    issue's end and then a fan-out span; 800-1000 has no program span."""
    dispatcher = _Line("dispatcher", [
        _Ev("quest_tpu.serve.issue", 150, 400),
        _Ev("quest_tpu.circuits.prepare", 250, 300)])
    other = _Line("completion", [
        _Ev("quest_tpu.serve.wait", 350, 500),
        _Ev("quest_tpu.serve.fan_out#seq=1#", 700, 800)])
    main = _Line("main", [_Ev(trace_reduce.WINDOW, 0, 1000),
                          _Ev("bench.wait_due", 0, 1000)])
    host = _Plane("/host:CPU", [main, dispatcher, other])
    return _Profile([host] + [_Plane(f"{trace_reduce.DEVICE_PREFIX}{i}",
                                     [_ops(*busy)])
                              for i, busy in enumerate(devices)])


def test_idle_is_charged_to_the_shortest_covering_program_span():
    got = program_spans.idle_by_program(
        _synthetic([[(100, 200), (600, 700)]]))
    want = {"none": 100 + 100 + 200, "quest_tpu.serve.issue": 50 + 50,
            "quest_tpu.circuits.prepare": 50, "quest_tpu.serve.wait": 150,
            "quest_tpu.serve.fan_out": 100}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_idle_by_program_averages_over_devices():
    pd = _synthetic([[(100, 200), (600, 700)], [(0, 1000)]])
    reduced = trace_reduce.reduce_trace(pd)
    got = program_spans.idle_by_program(pd)
    # the second device is never idle: each charge is halved
    assert got["quest_tpu.serve.wait"] == pytest.approx(75e-9)
    assert sum(got.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    # the trace reduction's own labels are the generator's span alone
    assert {g for g, _ in reduced["idle_gaps"]} == {"bench.wait_due"}


def test_a_trace_without_window_or_device_reads_nothing():
    pd = _synthetic([[(0, 10)]])
    no_device = _Profile([p for p in pd.planes
                          if not p.name.startswith("/device")])
    no_window = _Profile([p for p in pd.planes
                          if not p.name.startswith("/host")])
    for bad in (no_device, no_window):
        with pytest.raises(trace_reduce.TraceError):
            program_spans.idle_by_program(bad)


def test_program_spans_are_kept_per_host_line():
    spans = program_spans.program_spans(_synthetic([[(0, 10)]]))
    assert sorted(spans) == ["/host:CPU:1:dispatcher",
                             "/host:CPU:2:completion"]
    assert [n for n, _, _ in spans["/host:CPU:1:dispatcher"]] == [
        "quest_tpu.serve.issue", "quest_tpu.circuits.prepare"]
    assert program_spans.span_kind(
        "quest_tpu.serve.dispatch:energy:b64:env") \
        == "quest_tpu.serve.dispatch"


NEW_SERVICE_METRICS = ("serve.device_wait_ms", "serve.host_ms_per_dispatch")


@pytest.mark.parametrize("metric", NEW_SERVICE_METRICS)
def test_new_service_metrics_read_nothing_without_input(metric):
    from benchmark.registry import Registry
    from benchmark.tests.tiny import REPO
    read = Registry(REPO).reader(metric)
    assert read({}) is None
    # a service without the phase counters (the program before them)
    # reads nothing either
    assert read({"service": {"batches": 25, "batch_occupancy": 39.2}}) \
        is None


def test_new_service_metrics_read_the_phases():
    from benchmark.registry import Registry
    from benchmark.tests.tiny import REPO
    reg = Registry(REPO)
    ctx = {"service": {"batches": 4, "dispatch_wait_s": 1.6,
                       "dispatch_host_s": 0.1}}
    assert reg.reader("serve.device_wait_ms")(ctx) == pytest.approx(400.0)
    assert reg.reader("serve.host_ms_per_dispatch")(ctx) \
        == pytest.approx(25.0)


@pytest.fixture(scope="module")
def served():
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "serve-tiny.xplane.pb.gz"),
                   "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_served_idle_is_charged_to_the_dispatch_phases(served):
    reduced = trace_reduce.reduce_trace(served)
    idle = program_spans.idle_by_program(served)
    phases = {k for k in idle if k.startswith("quest_tpu.serve.")}
    assert "quest_tpu.serve.wait" in phases
    assert phases & {"quest_tpu.serve.coalesce", "quest_tpu.serve.issue",
                     "quest_tpu.serve.complete", "quest_tpu.serve.fan_out"}
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    # the idle under the dispatcher's own work is part of the idle share
    work = sum(s for k, s in idle.items()
               if k not in ("none", "quest_tpu.serve.wait"))
    assert 0 < 100 * work / reduced["window_s"] \
        <= trace_reduce.idle_share(reduced)
    # the trace reduction's labels still name the generator's spans
    assert all(g.startswith("bench.") for g, _ in reduced["idle_gaps"])

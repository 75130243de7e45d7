"""The trace reduction on a small trace recorded on a TPU v5e chip
(``record_trace.py``: one run of the 12-qubit random circuit of
``tiny.py``)."""

import os

import pytest

from benchmark import trace_reduce
from benchmark.peaks import peaks, state_pass_bytes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "rcs-tiny.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    return trace_reduce.load(DATA)


@pytest.fixture(scope="module")
def reduced(profile):
    return trace_reduce.reduce_trace(profile)


def test_window_and_busy(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_per_device_s"] == [reduced["busy_s"]]
    idle = trace_reduce.idle_share(reduced)
    assert 0 <= idle < 100


def test_ops_are_named_by_instruction(reduced):
    names = set(reduced["op_s"])
    assert any(n.startswith("pallas_layer_") for n in names)
    assert not any(" = " in n or n.startswith("%") for n in names)
    # device_ops is the per-op time, longest first
    times = [s for _, s in reduced["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert len(reduced["device_ops"]) <= 10


def test_union_never_exceeds_the_sum(reduced):
    assert reduced["busy_s"] <= sum(reduced["op_s"].values()) + 1e-9


def test_nested_events_are_charged_self_time():
    ops = [("while", 0, 100), ("fusion", 10, 30), ("copy", 40, 90),
           ("fusion", 50, 60), ("add", 100, 110)]
    got = trace_reduce.self_times(ops)
    assert got == [("while", pytest.approx(30e-9)),
                   ("fusion", pytest.approx(20e-9)),
                   ("copy", pytest.approx(40e-9)),
                   ("fusion", pytest.approx(10e-9)),
                   ("add", pytest.approx(10e-9))]


def test_gaps_are_labelled_with_bench_spans(reduced):
    assert reduced["idle_gaps"]
    labels = {label for label, _ in reduced["idle_gaps"]}
    assert labels <= {"bench.circuit_run", "bench.block_until_ready",
                      "outside any bench span"}
    gaps = [s for _, s in reduced["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    total_idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps) <= total_idle + 1e-9


def test_layer_roofline_reads_under_the_peak(reduced):
    from benchmark.registry import Registry
    from benchmark.tests.tiny import REPO
    read = Registry(REPO).reader("kernel.layer_roofline")
    ctx = {"trace": reduced, "num_qubits": 12, "chips": 1,
           "peaks": peaks("TPU v5 lite")}
    share = read(ctx)
    assert share is not None and 0 < share <= 100
    events = trace_reduce.events_matching(reduced, ("pallas_layer_",))
    moved = len(events) * state_pass_bytes(12)
    seconds = sum(s for _, s in events)
    assert share == pytest.approx(100 * moved / seconds / 819e9)


def test_no_collectives_on_one_chip(reduced):
    assert reduced["collective_per_device_s"] == [0.0]
    assert trace_reduce.is_collective("all-to-all")
    assert trace_reduce.is_collective("collective-permute-start")
    assert not trace_reduce.is_collective("fusion")


def test_a_trace_without_device_plane_reads_nothing(tmp_path):
    import jax
    logdir = str(tmp_path)
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        pass
    jax.profiler.stop_trace()
    pd = trace_reduce.load(trace_reduce.find_xplane(logdir))
    if trace_reduce.device_ops(pd):
        pytest.skip("this host has a TPU device plane")
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce_trace(pd)


def test_a_trace_without_window_reads_nothing(profile):
    class NoWindow:
        planes = [p for p in profile.planes if not p.name.startswith("/host")]
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce_trace(NoWindow())


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_op_name():
    assert trace_reduce.op_name(
        "%pallas_layer_3gates.10 = (f32[2]) custom-call(f32[2] %b.1)") \
        == "pallas_layer_3gates"
    assert trace_reduce.op_name("fusion.7") == "fusion"
    assert trace_reduce.op_name("copy-start") == "copy-start"

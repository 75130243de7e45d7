"""The row-gate roofline reader on the recorded ``rcs-tiny`` trace (which
predates the row-gate kernel) and on a reduced trace built by hand."""

import os

import pytest

from benchmark import trace_reduce
from benchmark.peaks import peaks, state_pass_bytes
from benchmark.registry import Registry
from benchmark.tests.tiny import REPO

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "rcs-tiny.xplane.pb")


@pytest.fixture(scope="module")
def read():
    return Registry(REPO).reader("kernel.rowgate_roofline")


def test_recorded_trace_without_row_gates_reads_nothing(read):
    reduced = trace_reduce.reduce_trace(trace_reduce.load(DATA))
    ctx = {"trace": reduced, "num_qubits": 12, "chips": 1,
           "peaks": peaks("TPU v5 lite")}
    assert not trace_reduce.events_matching(reduced, ("pallas_rowgate_",))
    assert read(ctx) is None


def test_share_of_synthetic_events_is_exact(read):
    # three row-gate passes over a 28-qubit state, 6 ms each; a layer
    # event and a fusion that the reader must not count
    reduced = {"op_events": {
        "pallas_rowgate_2q": [(0, 0.006)],
        "pallas_rowgate_4q": [(0, 0.006), (0, 0.006)],
        "pallas_layer_3gates": [(0, 0.050)],
        "fusion": [(0, 0.100)]}}
    ctx = {"trace": reduced, "num_qubits": 28, "chips": 1,
           "peaks": peaks("TPU v5 lite")}
    expected = 100 * 3 * state_pass_bytes(28) / 0.018 / 819e9
    assert read(ctx) == pytest.approx(expected, rel=1e-12)
    assert 0 < read(ctx) <= 100


def test_nothing_to_read_without_a_trace(read):
    assert read({"trace": None, "num_qubits": 28, "chips": 1}) is None
    assert read({"trace": {"op_events": {}}, "num_qubits": 28, "chips": 1,
                 "peaks": peaks("TPU v5 lite")}) is None

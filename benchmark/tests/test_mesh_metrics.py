"""The exchange's per-layer metrics (``mesh.collective_share``,
``mesh.exchange_roofline``, ``mesh.relayouts``) on synthetic reduced
traces: each reads nothing, never 0, where it finds nothing, and a known
share where the trace and the plan give one."""

import pytest

from benchmark import collectives
from benchmark.registry import Registry
from benchmark.tests import tiny

PEAKS = {"ici_bits_per_s": 1600e9}
PLAN = {"relayouts": 34, "collective_launches": 34,
        "cross_shard_exchanges": 0, "comm_bytes_planned": 8e9}
PLANES = [f"/device:TPU:{i}" for i in range(4)]


@pytest.fixture(scope="module")
def reader():
    return Registry(tiny.REPO).reader


def _trace(per_chip, window_s=10.0, name="all_to_all"):
    """A reduced trace whose chips spend ``per_chip`` seconds in ops
    named ``name`` (two events each) beside a fusion of 1 s."""
    events = {"fusion": [(p, 1.0) for p in PLANES]}
    if per_chip:
        events[name] = [(p, s / 2) for p, s in zip(PLANES, per_chip)
                        for _ in range(2)]
    return {"devices": len(PLANES), "window_s": window_s,
            "busy_s": window_s, "collective_per_device_s": [0.0] * 4,
            "op_events": events}


def _ctx(trace, plan=PLAN, circuits=2, chips=4):
    return {"trace": trace, "plan": plan, "circuits": circuits,
            "chips": chips, "peaks": PEAKS, "num_qubits": 30}


@pytest.mark.parametrize("name", ["all_to_all", "all-to-all",
                                  "collective-permute-done",
                                  "collective_permute"])
def test_collectives_in_either_spelling(name):
    assert collectives.is_collective(name)
    assert collectives.seconds_per_chip(_trace([0.2] * 4, name=name)) == \
        pytest.approx(0.2)


@pytest.mark.parametrize("name", ["fusion", "copy", "pallas_layer_3gates",
                                  "dynamic-update-slice"])
def test_other_ops_are_not_collectives(name):
    assert not collectives.is_collective(name)


@pytest.mark.parametrize("metric", ["mesh.collective_share",
                                    "mesh.exchange_roofline"])
def test_no_collectives_reads_nothing(reader, metric):
    assert reader(metric)(_ctx(_trace([]))) is None
    assert reader(metric)(_ctx(_trace([0.0] * 4))) is None
    assert reader(metric)(_ctx(None)) is None


@pytest.mark.parametrize("metric", ["mesh.exchange_roofline",
                                    "mesh.relayouts"])
def test_no_counters_reads_nothing(reader, metric):
    trace = _trace([0.1] * 4)
    assert reader(metric)(_ctx(trace, plan=None)) is None
    assert reader(metric)(_ctx(trace, plan={})) is None


def test_no_bytes_or_circuits_read_no_roofline(reader):
    read = reader("mesh.exchange_roofline")
    trace = _trace([0.1] * 4)
    assert read(_ctx(trace, plan=dict(PLAN, comm_bytes_planned=0.0))) \
        is None
    assert read(_ctx(trace, circuits=0)) is None


def test_collective_share_is_the_mean_over_chips(reader):
    trace = _trace([0.4, 0.6, 0.5, 0.5], window_s=10.0)
    assert reader("mesh.collective_share")(_ctx(trace)) == \
        pytest.approx(5.0)


def test_exchange_roofline_known_share(reader):
    # 8e9 bytes a circuit over 4 chips, 2 circuits: 4e9 bytes a chip in
    # 0.1 s of collectives, 40 GB/s against a 200 GB/s peak: 20%
    trace = _trace([0.1, 0.1, 0.1, 0.1])
    assert reader("mesh.exchange_roofline")(_ctx(trace)) == \
        pytest.approx(20.0)


def test_relayouts_read_the_plan(reader):
    assert reader("mesh.relayouts")(_ctx(None)) == 34


def test_metrics_are_listed_for_the_mesh_cell_only():
    registry = Registry(tiny.REPO)
    names = {m["name"] for m in registry.metrics("per_layer", "rcs30-mesh4")}
    assert {"mesh.collective_share", "mesh.exchange_roofline",
            "mesh.relayouts"} <= names
    for cell in ("rcs28-circuit", "qaoa18-serve"):
        assert not any(m["name"].startswith("mesh.")
                       for m in registry.metrics("per_layer", cell))

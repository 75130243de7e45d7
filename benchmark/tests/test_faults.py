"""A run whose timed path is broken underneath reads ``correct: false``.

Each test skips the harness's look for a chip, drives a small cell on
whatever JAX has, and plants one fault in the program's timed entry:
``CompiledCircuit.run`` for the circuit cells,
``CompiledCircuit.expectation_sweep`` under the service for the served
cell."""

import numpy as np
import pytest

from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def cc_class():
    from quest_tpu.circuits import CompiledCircuit
    return CompiledCircuit


@pytest.mark.parametrize("workload,seconds", [("rcs-tiny", 1.0),
                                              ("qaoa-tiny", 1.0),
                                              ("rcs-tiny", 1e-6)],
                         ids=["rcs-tiny", "qaoa-tiny", "rcs-tiny-one-run"])
def test_sound_run_is_correct(root, capsys, workload, seconds):
    result = tiny.run_tiny(root, workload, capsys, seconds=seconds)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert list(result)[-1] == "checks"


def _circuit_unchanged(orig):
    def run(self, qureg, params=None):
        return None
    return run


def _circuit_altered(orig):
    def run(self, qureg, params=None):
        orig(self, qureg, params)
        qureg.state = qureg.state.at[0, 0].add(1e-2)
    return run


@pytest.mark.parametrize("seconds", [1.0, 1e-6], ids=["runs", "one_run"])
@pytest.mark.parametrize("fault", [_circuit_unchanged, _circuit_altered],
                         ids=["state_unchanged", "amplitude_altered"])
def test_circuit_fault_is_caught(root, capsys, monkeypatch, cc_class,
                                 fault, seconds):
    monkeypatch.setattr(cc_class, "run", fault(cc_class.run))
    result = tiny.run_tiny(root, "rcs-tiny", capsys, seconds=seconds)
    assert result["correct"] is False
    check = result["checks"]["state_rel_err"]
    assert check["value"] > check["limit"]


def _energy_unchanged(orig):
    def sweep(self, pm, ham, *a, **k):
        return orig(self, np.zeros_like(np.asarray(pm)), ham, *a, **k)
    return sweep


def _energy_half_batch(orig):
    def sweep(self, pm, ham, *a, **k):
        pm = np.asarray(pm)
        half = max(1, len(pm) // 2)
        kept = np.asarray(orig(self, pm[:half], ham, *a, **k))
        rest = np.full(len(pm) - half, kept.mean())
        return np.concatenate([kept, rest])
    return sweep


def _energy_altered(orig):
    def sweep(self, pm, ham, *a, **k):
        out = np.array(orig(self, pm, ham, *a, **k), dtype=np.float64)
        out[0] += 1e-2
        return out
    return sweep


@pytest.mark.parametrize("fault", [_energy_unchanged, _energy_half_batch,
                                   _energy_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_served_fault_is_caught(root, capsys, monkeypatch, cc_class, fault):
    monkeypatch.setattr(cc_class, "expectation_sweep",
                        fault(cc_class.expectation_sweep))
    result = tiny.run_tiny(root, "qaoa-tiny", capsys, seconds=2.0)
    assert result["correct"] is False
    check = result["checks"]["energy_max_abs_err"]
    assert check["value"] > check["limit"]

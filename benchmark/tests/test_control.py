"""The control, the reference at the precision below the configuration's
(three bfloat16 passes) put in the program's place, reads
``correct: false``.

Each test skips the harness's look for a chip and drives a small cell of
``tiny.py`` with the timed entry replaced by the control:
``CompiledCircuit.run`` for the circuit cell,
``CompiledCircuit.expectation_sweep`` under the service for the served
cell."""

import numpy as np
import pytest

from benchmark.registry import Registry
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def cc_class():
    from quest_tpu.circuits import CompiledCircuit
    return CompiledCircuit


def test_circuit_control_is_not_correct(root, capsys, monkeypatch,
                                        cc_class):
    from benchmark.reference import rcs
    apply = rcs.make_apply(Registry(root).config("rcs-tiny"), "bf16_3x")

    def run(self, qureg, params=None):
        qureg.state = rcs.to_planes(apply(qureg.state))

    monkeypatch.setattr(cc_class, "run", run)
    result = tiny.run_tiny(root, "rcs-tiny", capsys)
    assert result["correct"] is False
    check = result["checks"]["state_rel_err"]
    assert check["value"] > check["limit"]


def test_served_control_is_not_correct(root, capsys, monkeypatch,
                                       cc_class):
    from benchmark.reference import qaoa
    energies = qaoa.make_energies(Registry(root).config("qaoa-tiny"),
                                  "bf16_3x")

    def sweep(self, pm, ham, *a, **k):
        return energies(np.asarray(pm))

    monkeypatch.setattr(cc_class, "expectation_sweep", sweep)
    result = tiny.run_tiny(root, "qaoa-tiny", capsys, seconds=2.0)
    assert result["failed"] == 0
    assert result["correct"] is False
    check = result["checks"]["energy_max_abs_err"]
    assert check["value"] > check["limit"]

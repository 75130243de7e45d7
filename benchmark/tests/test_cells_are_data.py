"""A cell, a configuration, a traffic mix and a per-layer metric are
added by adding files and entries alone; without a TPU the benchmark
prints no result and fails."""

import hashlib
import json
import os
import subprocess
import sys

from benchmark.registry import Registry
from benchmark.tests import tiny

METRIC = '''"""Gates the timed program was recorded with."""


def read(ctx):
    plan = ctx.get("plan")
    return plan["gates_in"] if plan else None
'''


def _digests(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_are_picked_up(tmp_path, capsys):
    root = tiny.make_root(str(tmp_path))
    before = _digests(root)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "rcs-tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(grid=[3, 3], qubits=9, cycles=4, structure_seed=99)
    with open(os.path.join(bench, "configs", "rcs-new.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "closed-loop-new.json"),
              "w") as f:
        json.dump({"kind": "circuit_loop", "why": "test"}, f)
    with open(os.path.join(bench, "metrics", "plan.gates_in.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "rcs-new", "source": "test",
                            "file": "benchmark/configs/rcs-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "rcs-new", "config": "rcs-new",
                              "traffic": "closed-loop-new", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "plan.gates_in", "unit": "gates",
                              "better": "lower", "source": "program_counter",
                              "layer": "circuit planner",
                              "moves": "circuit_s",
                              "workloads": ["rcs-new"]})
    for m in spec["end_to_end"]:
        if "rcs-tiny" in m.get("workloads", ()):
            m["workloads"].append("rcs-new")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    registry = Registry(root)
    assert registry.config("rcs-new")["qubits"] == 9
    assert registry.traffic("closed-loop-new")["kind"] == "circuit_loop"
    assert [m["name"] for m in registry.metrics("per_layer", "rcs-new")] \
        == ["plan.gates_in"]
    assert registry.reader("plan.gates_in")(
        {"plan": {"gates_in": 7}}) == 7
    result = tiny.run_tiny(root, "rcs-new", capsys)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"circuit_s", "setup_s"}


def _run_script(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rcs28-circuit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    proc = _run_script(tiny.REPO, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_alone_prints_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    has no program to run."""
    import shutil
    root = tmp_path / "alone"
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), root)
    proc = _run_script(str(root), {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

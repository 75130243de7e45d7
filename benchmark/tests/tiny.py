"""A copy of the benchmark with small cells added, for tests that drive
runs on the CPU: a 12-qubit random circuit (2x6 grid, 6 cycles) and a
10-qubit QAOA served at 40 requests/s, each with limits of its own. Only new files and new entries
are added to the copy."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the limits sit between the tiny cells' own readings on the CPU: the
# program's gap (rcs 6.1e-7, qaoa 5.2e-6 at most over three seeds) and the
# control's (rcs 1.02e-5, qaoa 2.6e-5 at least)
TINY = {"rcs-tiny": ("sycamore-rcs-28",
                     {"qubits": 12, "grid": [2, 6], "cycles": 6,
                      "limits": {"state_rel_err": 3e-6}}, "closed-loop"),
        "qaoa-tiny": ("qaoa-maxcut-18",
                      {"qubits": 10, "limits": {"energy_max_abs_err": 1.2e-5}},
                      "poisson-tiny")}
LIKE = {"rcs-tiny": "rcs28-circuit", "qaoa-tiny": "qaoa18-serve"}


def make_root(tmp: str) -> str:
    """A checkout-like tree under ``tmp`` holding the tiny cells."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "traffic", "poisson-qaoa18.json")) as f:
        traffic = json.load(f)
    traffic["rate_per_s"] = 40
    with open(os.path.join(bench, "traffic", "poisson-tiny.json"), "w") as f:
        json.dump(traffic, f)
    for name, (base, changes, traffic_name) in TINY.items():
        with open(os.path.join(bench, "configs", f"{base}.json")) as f:
            cfg = json.load(f)
        cfg.update(changes)
        path = f"benchmark/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        spec["configs"].append({"name": name, "source": "test",
                                "file": path, "reduced": sorted(set(changes) - {"limits"}),
                                "why": "test"})
        spec["workloads"].append({"name": name, "config": name,
                                  "traffic": traffic_name, "chips": 1,
                                  "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if LIKE[name] in m.get("workloads", ()):
                m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return root


def run_tiny(root: str, workload: str, capsys, seed: int = 3000000017,
             seconds: float = 1.0) -> dict:
    """One run of a tiny cell on the CPU, skipping the look for a chip;
    returns its result line."""
    import jax
    from benchmark import run as bench_run
    from benchmark.registry import Registry
    registry = Registry(root)
    args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds)])
    capsys.readouterr()
    rc = bench_run.run_cell(registry, registry.workload(workload), args,
                            jax.devices())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

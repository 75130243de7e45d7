"""The benchmark's four-chip random-circuit cell (``rcs30-mesh4``) at a
small size on four of the suite's CPU devices: its sharded plain
reference, the program against it, the control against it, a whole run
through the harness, and the exchange counters the cell's metrics read
against the collectives of the lowered program.

Nothing here times anything: the cell's numbers come from the chip."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPS = 4
# between the program's gap (at most 6.1e-7 on these grids) and the
# control's (at least 1.02e-5), as in the benchmark's one-chip tiny cell
LIMIT = 3e-6
SEED = 3000000017


def _config(**changes):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sycamore-rcs-30.json")) as f:
        cfg = json.load(f)
    cfg.update(changes)
    return cfg


def _tiny(grid):
    return _config(qubits=12, grid=list(grid), cycles=6)


@pytest.fixture(scope="module")
def env():
    return qt.createQuESTEnv(num_devices=CHIPS, precision=qt.SINGLE)


def _planes(cfg, env):
    from benchmark.families import rcs_mesh
    return rcs_mesh.initial_planes(cfg["qubits"], SEED, env.sharding())


@pytest.mark.parametrize("grid", [(3, 4), (2, 6)])
def test_sharded_reference_matches_plain_reference(env, grid):
    from benchmark.reference import rcs, rcs_mesh
    cfg = _tiny(grid)
    planes = _planes(cfg, env)
    plain = np.asarray(rcs.make_apply(cfg, "highest")(
        np.asarray(jax.device_get(planes))))
    psi = rcs_mesh.make_apply(cfg, "highest")(planes)
    assert psi.shape == (1 << 12,)
    assert psi.sharding.is_equivalent_to(env.sharding_flat(), 1)
    got = np.asarray(jax.device_get(psi))
    assert np.linalg.norm(got - plain) / np.linalg.norm(plain) < 1e-6


def _program_gap(cfg, env, run):
    """The gap of ``run(qureg)``'s output to the sharded reference, both
    from the seed's initial state."""
    from benchmark.reference import rcs_mesh
    q = qt.createQureg(cfg["qubits"], env)
    q.state = _planes(cfg, env)
    run(q)
    psi = rcs_mesh.make_apply(cfg, "highest")(_planes(cfg, env))
    return rcs_mesh.relative_error(q.state, psi)


def test_program_within_limit_of_sharded_reference(env):
    from benchmark.families import rcs_mesh
    cfg = _tiny((3, 4))
    cc = rcs_mesh.build_program(qt, cfg).compile(env)
    assert cc.dispatch_stats().relayouts > 0
    gap = _program_gap(cfg, env, cc.run)
    assert 0 < gap < LIMIT


def test_host_planes_are_placed_under_the_reference_sharding(env):
    from benchmark.reference import rcs_mesh
    cfg = _tiny((2, 6))
    psi = rcs_mesh.make_apply(cfg, "highest")(_planes(cfg, env))
    planes = rcs_mesh.to_planes(psi)
    assert rcs_mesh.relative_error(planes, psi) == 0.0
    assert rcs_mesh.relative_error(np.asarray(jax.device_get(planes)),
                                   psi) == 0.0


def test_control_fails_the_limit(env):
    from benchmark.reference import rcs_mesh
    cfg = _tiny((3, 4))
    control = rcs_mesh.make_apply(cfg, "bf16_3x")

    def run(q):
        q.state = rcs_mesh.to_planes(control(q.state))

    assert _program_gap(cfg, env, run) > LIMIT


# -- the cell through the harness ---------------------------------------------

@pytest.fixture
def harness_config():
    """``run_cell`` turns x64 off and JAX's persistent cache on, for the
    process; put both back for the tests that follow."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_enable_x64", "jax_compilation_cache_dir",
            "jax_compilation_cache_max_size",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_tiny_mesh_cell_runs_correct(tmp_path, capsys, harness_config):
    from benchmark import run as bench_run
    from benchmark.registry import Registry
    from benchmark.tests import tiny
    root = tiny.make_root(str(tmp_path))
    path = "benchmark/configs/rcs-mesh-tiny.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(dict(_tiny((3, 4)), limits={"state_rel_err": LIMIT}), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "rcs-mesh-tiny", "source": "test",
                            "file": path, "reduced": ["qubits", "grid",
                                                      "cycles"],
                            "why": "test"})
    spec["workloads"].append({"name": "rcs-mesh-tiny",
                              "config": "rcs-mesh-tiny",
                              "traffic": "closed-loop-mesh",
                              "chips": CHIPS, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rcs30-mesh4" in m.get("workloads", ()):
            m["workloads"].append("rcs-mesh-tiny")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    registry = Registry(root)
    args = bench_run.parse(["--workload", "rcs-mesh-tiny", "--seed",
                            str(SEED), "--seconds", "1"])
    capsys.readouterr()
    rc = bench_run.run_cell(registry, registry.workload("rcs-mesh-tiny"),
                            args, jax.devices())
    assert rc == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"circuit_s", "setup_s"}
    assert result["device"]["count"] == len(jax.devices())
    plan = next(line["plan"] for line in lines if "plan" in line)
    assert plan["relayouts"] > 0
    assert plan["collective_launches"] >= plan["relayouts"]
    assert plan["comm_bytes_planned"] > 0
    assert result["checks"]["state_rel_err"]["value"] < LIMIT


# -- the exchange counters against the lowered program ------------------------

ITEMSIZE = {"f32": 4, "f64": 8, "complex<f32>": 8, "complex<f64>": 16}
COLLECTIVE = re.compile(r'"stablehlo\.(all_to_all|collective_permute)"')
OPERAND = re.compile(r"\(tensor<((?:\d+x)*)([a-z0-9<>]+)>\)")


def lowered_collectives(text: str, num_devices: int) -> tuple:
    """``(collective ops, bytes one chip sends on average)`` of a lowered
    program's ``all_to_all`` and ``collective_permute`` ops, from their
    operand shapes: an all-to-all over groups of ``g`` keeps ``1/g`` of
    its operand, a permute sends its operand from every source that is
    not its own target."""
    ops, sent = 0, 0.0
    for line in text.splitlines():
        m = COLLECTIVE.search(line)
        if not m:
            continue
        dims, elem = OPERAND.search(line).groups()
        size = ITEMSIZE[elem] * int(np.prod(
            [int(d) for d in dims.split("x") if d]))
        if m.group(1) == "all_to_all":
            g = int(re.search(r"split_count = (\d+)", line).group(1))
            sent += size * (g - 1) / g
        else:
            pairs = json.loads(re.search(
                r"source_target_pairs = dense<(\[\[.*?\]\])>", line).group(1))
            sent += size * sum(s != t for s, t in pairs) / num_devices
        ops += 1
    return ops, sent


def _rcs_circuit(grid):
    from benchmark.families import rcs_mesh
    return rcs_mesh.build_program(qt, _config(qubits=12, grid=list(grid)))


def _circuit(build):
    c = qt.Circuit(12)
    build(c)
    return c


CIRCUITS = {
    "rcs 3x4": lambda: _rcs_circuit((3, 4)),
    "rcs 2x6": lambda: _rcs_circuit((2, 6)),
    # each relayout followed by the gate it serves runs as two slabs,
    # one all_to_all each
    "rcs 3x4 overlapped": lambda: _rcs_circuit((3, 4)),
    # a lone 1-qubit gate on a device qubit: a cross-shard pair exchange
    "cross-shard rx": lambda: _circuit(lambda c: c.rx(11, 0.3)),
    # a SWAP of the two device qubits: a permute that moves half the
    # chips' chunks
    "device swap": lambda: _circuit(
        lambda c: (c.h(0), c.swap(10, 11), c.h(1))),
    # SWAPs that leave a residual device permutation after an exchange
    "exchange and residual permute": lambda: _circuit(
        lambda c: (c.h(0), c.swap(10, 3), c.swap(11, 4), c.swap(3, 11),
                   c.h(1))),
}


@pytest.mark.parametrize("name", sorted(CIRCUITS))
def test_exchange_counters_match_lowered_collectives(env, name):
    cc = CIRCUITS[name]().compile(env, overlap=name.endswith("overlapped"))
    stats = cc.dispatch_stats()
    n = cc.num_qubits
    state = jax.ShapeDtypeStruct((2, 1 << n), jnp.float32,
                                 sharding=env.sharding())
    params = jax.ShapeDtypeStruct((len(cc.param_names),), jnp.float32)
    ops, sent = lowered_collectives(
        cc._jitted.lower(state, params).as_text(), CHIPS)
    assert ops > 0
    assert ops == stats.collective_launches
    assert sent == pytest.approx(stats.comm_bytes_planned / CHIPS)

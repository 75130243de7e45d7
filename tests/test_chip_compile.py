"""Compiles for a described TPU v5e chip: what the chip's compiler would
refuse, and how much of the chip's 16 GB a program needs, without a chip.

Nothing here runs: the programs are lowered from shapes
(``jax.ShapeDtypeStruct`` with the described sharding) and compiled by the
TPU compiler that ships with ``libtpu``. The topology is described inside
the ``topo`` fixture, never at import, and every test that needs it skips
from there when it cannot be described. JAX's persistent cache is off
around these tests: an entry written for a described chip cannot be read
back without one. So is x64, as on the chip: under x64 a Pallas index map
returns 64-bit indices, which Mosaic refuses.

The gate cases guard the compile-time finding of ``core/apply.py``: each
full-width (28-qubit) operator compiles in seconds. Before the lane view,
the in-place split and the pass boundaries, one Hadamard on qubit 0 of 20
qubits took 95 s, a CNOT with adjacent control and target at 28 qubits
630 s, and a whole 28-qubit circuit did not finish in ten minutes.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

HBM_BYTES = 16 * 10 ** 9      # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def f32_env():
    import quest_tpu as qt
    return qt.createQuESTEnv(num_devices=1, precision=qt.SINGLE, seed=[1])


def _compile_for_tpu(monkeypatch, circ, env):
    """``circ.compile(env)`` as on a TPU: circuits switch the Pallas pass
    on only where JAX's default backend is a TPU, and under a described
    chip it is the CPU."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return circ.compile(env)


def _planes(n, sharding, batch=None):
    shape = (2, 1 << n) if batch is None else (batch, 2, 1 << n)
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _fits(compiled, per_device=HBM_BYTES):
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert need <= per_device, need
    return need


# -- the XLA gate path at full width ------------------------------------------

_X = np.array([[0, 1], [1, 0]], np.complex64)
_SWAP4 = np.eye(4, dtype=np.complex64)[::-1].copy()
_PERM16 = np.eye(16, dtype=np.complex64)[::-1].copy()

GATES = {
    # name: (operator, targets, control mask)
    "lane target": (_X, (0,), 0),
    "row target": (_X, (27,), 0),
    "lane control, top target": (_X, (27,), 1 << 0),
    "top control, lane target": (_X, (0,), 1 << 27),
    "lane and row targets": (_SWAP4, (3, 17), 0),
    "two row targets apart": (_SWAP4, (9, 23), 0),
    "four row targets": (_PERM16, (8, 9, 22, 23), 0),
    "adjacent row control": (_X, (17,), 1 << 16),
    "nineteen row controls": (_X, (27,), sum(1 << q for q in range(7, 26))),
}


@pytest.mark.parametrize("name", sorted(GATES))
def test_gate_compiles_at_28_qubits(topo, one_chip, name):
    from quest_tpu.core.apply import apply_unitary
    from quest_tpu.core.packing import pack, unpack
    u, targets, cmask = GATES[name]

    def step(s):
        return pack(apply_unitary(unpack(s), 28, u, targets, cmask))

    compiled = jax.jit(step, donate_argnums=0).lower(
        _planes(28, one_chip)).compile()
    _fits(compiled)


@pytest.mark.parametrize("qubits", [(0,), (27, 0), (20, 6, 2)])
def test_diagonal_compiles_at_28_qubits(topo, one_chip, qubits):
    from quest_tpu.core.apply import apply_diagonal
    from quest_tpu.core.packing import pack, unpack
    d = np.exp(1j * np.arange(1 << len(qubits))).astype(np.complex64)
    d = d.reshape((2,) * len(qubits))

    def step(s):
        return pack(apply_diagonal(unpack(s), 28, qubits, d))

    compiled = jax.jit(step, donate_argnums=0).lower(
        _planes(28, one_chip)).compile()
    _fits(compiled)


# -- the Pallas kernels at the shapes chip_smoke.py drives -------------------

def _layer():
    from quest_tpu.ops import pallas_kernels as pk
    lane = pk.embed_lane_matrix(_X, (0,))
    return pk.LayerOp(28, 3, [
        ("lane", lane),
        ("row", 9, np.array([[0, 1], [1, 0]]), 0, 0, 0, 0),
        ("rowdiag", np.ones((2, 128), np.complex128), (3,))])


def test_apply_layer_compiles(topo, one_chip):
    from quest_tpu.core.packing import pack, unpack
    from quest_tpu.ops import pallas_kernels as pk
    layer = _layer()

    def step(s):
        return pack(pk.apply_layer(unpack(s), 28, layer))

    compiled = jax.jit(step, donate_argnums=0).lower(
        _planes(28, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's name is the op name the device trace reduction
    # matches (benchmark/metrics/kernel.layer_roofline.py)
    assert "%pallas_layer_3gates." in compiled.as_text()
    _fits(compiled)


def test_apply_layer_batched_compiles(topo, one_chip):
    from quest_tpu.core.packing import pack, unpack
    from quest_tpu.ops import pallas_kernels as pk
    layer = _layer()

    def step(s):
        z = jax.lax.complex(s[:, 0], s[:, 1])
        out = pk.apply_layer_batched(z, 20, layer)
        return jnp.stack([jnp.real(out), jnp.imag(out)], axis=1)

    compiled = jax.jit(step).lower(_planes(20, one_chip, batch=4)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%pallas_layer_b4_3gates." in compiled.as_text()
    _fits(compiled)


def test_apply_mxu_tile_compiles(topo, one_chip):
    from quest_tpu.ops import pallas_kernels as pk
    u = np.kron(_X, _X)
    spec = jax.ShapeDtypeStruct((1 << 28,), jnp.complex64,
                                sharding=one_chip)
    compiled = jax.jit(lambda z: pk.apply_mxu_tile(
        z, 28, u, (0, 8))).lower(spec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%pallas_mxu_tile_256." in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("targets", [(26, 27), (17, 18, 19),
                                     (10, 11, 17, 18), (20, 27)])
def test_apply_rowgate_planes_compiles(topo, one_chip, targets):
    """The row gate at 28 qubits on target sets of the benchmark's random
    circuit: contiguous, split, and the lowest target qubit 10. It
    writes the planes in place (the input aliases the output)."""
    from quest_tpu.ops import pallas_kernels as pk
    k = len(targets)
    u = np.linalg.qr(np.arange(4 ** k).reshape(1 << k, 1 << k)
                     + 1j * np.eye(1 << k))[0]
    spec = jax.ShapeDtypeStruct((1 << 21, 128), jnp.float32,
                                sharding=one_chip)
    compiled = jax.jit(lambda re, im: pk.apply_rowgate_planes(
        re, im, 28, u, targets), donate_argnums=(0, 1)).lower(
        spec, spec).compile()
    assert f"%pallas_rowgate_{k}q" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 2 * 4 * (1 << 28)
    assert m.temp_size_in_bytes < 1 << 20
    _fits(compiled)


def test_fused_kraus_apply_batched_compiles(topo, one_chip):
    from quest_tpu.ops import pallas_kernels as pk
    p = 0.1
    ops = [np.sqrt(1 - p) * np.eye(2), np.sqrt(p) * np.diag([1.0, -1.0])]
    kstack = np.stack([pk.embed_lane_matrix(k, (2,)) for k in ops])
    T, n = 4, 20
    states = jax.ShapeDtypeStruct((T, 1 << n), jnp.complex64,
                                  sharding=one_chip)
    probs = jax.ShapeDtypeStruct((T, 2), jnp.float32, sharding=one_chip)
    u01 = jax.ShapeDtypeStruct((T,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda s, pr, u: pk.fused_kraus_apply_batched(
        s, n, kstack, pr, u)).lower(states, probs, u01).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%pallas_kraus_t4_k2." in compiled.as_text()
    _fits(compiled)


# -- whole programs -------------------------------------------------------------

def _smoke():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    import chip_smoke
    return chip_smoke


def test_statevector_program_fits_one_chip(topo, one_chip, f32_env,
                                           monkeypatch):
    """chip_smoke.py's compiled 28-qubit program, thinned so the compile
    stays short (each Pallas layer stage adds Mosaic compile time): lane
    and row rotations and CNOTs fused into a Pallas layer, XLA gates on
    the qubits above the kernel's block range, and the controlled phases
    reaching qubit 27."""
    import quest_tpu as qt
    cs = _smoke()
    n = 28
    keep = {0, 1, 2, 8, 9, n - 3, n - 2, n - 1}
    spec = [g for g in cs.unitary_spec(n, layers=1)
            if (g[0] == "rot" and g[1] in keep)
            or (g[0] == "cnot" and g[1] in (0, 8))
            or (g[0] == "h" and g[1] == n - 1)
            or (g[0] == "cphase" and g[2] == n - 1 and g[1] in (0, 9, n - 2))]
    circ = cs.build_circuit(qt, n, spec)
    cc = _compile_for_tpu(monkeypatch, circ, f32_env)
    assert cs.pallas_layers(cc) > 0
    vec = jax.ShapeDtypeStruct((0,), jnp.float32, sharding=one_chip)
    compiled = cc._jitted.lower(_planes(n, one_chip), vec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_sharded_program_fits_v5e_2x2(topo, f32_env, monkeypatch):
    """The 30-qubit register over a described 2x2 mesh: 2 GiB of state
    per chip, with the exchanges the planner put in as collectives."""
    import quest_tpu as qt
    from jax.sharding import Mesh
    from quest_tpu.env import AMP_AXIS
    cs = _smoke()
    n = 30
    mesh = Mesh(np.asarray(topo.devices), (AMP_AXIS,))
    env = qt.QuESTEnv(precision=qt.SINGLE, mesh=mesh, key=f32_env.key)
    spec = [g for g in cs.unitary_spec(n, layers=1)
            if g[0] != "rot" or g[1] >= n - 3][:12]
    cc = _compile_for_tpu(monkeypatch, cs.build_circuit(qt, n, spec), env)
    state = jax.ShapeDtypeStruct(
        (2, 1 << n), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec(None, AMP_AXIS)))
    vec = jax.ShapeDtypeStruct((0,), jnp.float32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    compiled = cc._jitted.lower(state, vec).compile()
    need = _fits(compiled)
    assert need >= 2 * 4 * (1 << n) // 4        # the 2 GiB shard is there
    text = compiled.as_text()
    assert any(op in text for op in ("all-to-all", "collective-permute"))


def test_sharded_program_names_its_layers(topo, f32_env, monkeypatch):
    """Over a described 2x2 mesh the ``shard_map`` body's Pallas layers
    keep their kernel names (``pallas_layer_<k>gates``): a four-chip
    cell's trace shows its layers under the names a single chip's does."""
    import quest_tpu as qt
    from jax.sharding import Mesh
    from quest_tpu.env import AMP_AXIS
    cs = _smoke()
    n = 22
    mesh = Mesh(np.asarray(topo.devices), (AMP_AXIS,))
    env = qt.QuESTEnv(precision=qt.SINGLE, mesh=mesh, key=f32_env.key)
    keep = {0, 1, 2, 8, 9, n - 2, n - 1}
    spec = [g for g in cs.unitary_spec(n, layers=1)
            if (g[0] == "rot" and g[1] in keep)
            or (g[0] == "cnot" and g[1] in (0, 8, n - 2))]
    cc = _compile_for_tpu(monkeypatch, cs.build_circuit(qt, n, spec), env)
    assert cs.pallas_layers(cc) > 0
    assert cc.dispatch_stats().relayouts > 0
    state = jax.ShapeDtypeStruct(
        (2, 1 << n), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec(None, AMP_AXIS)))
    vec = jax.ShapeDtypeStruct((0,), jnp.float32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    text = cc._jitted.lower(state, vec).compile().as_text()
    assert "%pallas_layer_" in text
    assert "all-to-all" in text


def test_sharded_reference_fits_v5e_2x2(topo):
    """The plain reference of the benchmark's four-chip cell
    (``benchmark/reference/rcs_mesh.py``) at its 30 qubits over a
    described 2x2 mesh: a chip holds its 2 GiB share and one step's
    temporaries, and the state moves between chips only by all-to-all,
    never gathered whole."""
    import json
    from jax.sharding import Mesh
    from benchmark.reference import rcs_mesh
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmark", "configs",
                           "sycamore-rcs-30.json")) as f:
        cfg = json.load(f)
    n = cfg["qubits"]
    mesh = Mesh(np.asarray(topo.devices), ("amps",))
    planes = jax.ShapeDtypeStruct(
        (2, 1 << n), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec(None, "amps")))
    compiled = rcs_mesh.make_apply(cfg, "highest").lower(planes).compile()
    need = _fits(compiled)
    assert need < 6 * 2 * 4 * (1 << n) // 4     # a few 2 GiB buffers
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "all-gather" not in text

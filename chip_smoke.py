"""Bring-up smoke test of the simulator on TPU chips.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: mesh, 30 qubits, replicas

Everything runs in this one process, through the public package surface
(``import quest_tpu as qt``), in single precision. Without a TPU it exits
non-zero before any phase runs. Each phase prints one JSON line with its
compile and run seconds, whether the compile hit JAX's persistent cache,
and ``peak_bytes_in_use``; a phase whose check fails raises, and the
script exits non-zero. The last line is the contract line
``{"ok": true, "device": {"platform", "kind", "count"}}``.

One-chip phases:

(a) 28-qubit statevector: a seeded U (brickwork plus a block of
    controlled phases reaching qubit 27) followed by U-dagger in one
    compiled program, which must return |0...0>; a compiled GHZ-28; then
    imperative gates and a seeded measurement on the same register.
(b) the compiled U at 16 qubits against the float64 numpy oracle
    (``tests/oracle.py``), every amplitude to 1e-5.
(c) a 14-qubit density matrix (2^28 entries) with gates and dephasing,
    damping and depolarising channels in one compiled program; the same
    program at 6 qubits against the oracle.
(d) a ``SimulationService`` on a 20-qubit hardware-efficient ansatz:
    expectation values, samples and final states, each equal to the
    direct ``CompiledCircuit`` batched value.

Four-chip checks (``--chips 4``): the 28-qubit U on a 4-device mesh
against one chip (64 amplitudes, and the outcome probabilities of eight
lane, row and device-bit qubits); U U-dagger at 30 qubits on the mesh; and a
``ServiceRouter`` over four one-chip replicas, each on its own device.
No timing here is a speed: the circuits are checks, not benchmarks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SV_QUBITS = 28
PARITY_QUBITS = 16
DENSITY_QUBITS = 14
DENSITY_ORACLE_QUBITS = 6
SERVE_QUBITS = 20
MESH_QUBITS = 30
REPLICA_QUBITS = 12     # the router check is placement and routing; each
                        # replica compiles its own batched program
SEED = 2026


def emit(doc: dict) -> None:
    print(json.dumps(doc, default=float), flush=True)


class CompileCounter:
    """Counts JAX persistent-cache hits and misses, so each phase can say
    whether its compiles were cold or warm."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.hits, self.misses


class Phase:
    """Times one phase and prints its line on success."""

    def __init__(self, name: str, counter: CompileCounter, device):
        self.name, self.counter, self.device = name, counter, device
        self.doc = {"phase": name}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.counter.snapshot()
        return self

    def timed(self, key: str, fn):
        """Run ``fn`` and record its seconds under ``key``."""
        t0 = time.perf_counter()
        out = fn()
        self.doc[key] = time.perf_counter() - t0
        return out

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        hits, misses = self.counter.snapshot()
        self.doc["cache_hits"] = hits - self.c0[0]
        self.doc["cache_misses"] = misses - self.c0[1]
        self.doc["phase_s"] = time.perf_counter() - self.t0
        stats = self.device.memory_stats() or {}
        self.doc["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        self.doc["ok"] = True
        emit(self.doc)
        return False


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- circuits as gate lists, built into Circuits and into the oracle -------

def unitary_spec(n: int, layers: int = 2) -> list:
    """U: ``layers`` of the bench brickwork (a seeded rotation on every
    qubit, then CNOTs on alternating pairs), then a QFT-style block of
    controlled phases onto the top two qubits, which reaches qubit n-1."""
    rng = np.random.default_rng(SEED)
    spec = []
    for layer in range(layers):
        for q in range(n):
            spec.append(("rot", q, float(rng.uniform(0, 2 * np.pi)),
                         tuple(float(x) for x in rng.normal(size=3))))
        for q in range(layer % 2, n - 1, 2):
            spec.append(("cnot", q, q + 1))
    for top in (n - 1, n - 2):
        spec.append(("h", top))
        for q in range(top):
            spec.append(("cphase", q, top, np.pi / 2 ** (top - q)))
    return spec


def noise_spec(n: int) -> list:
    """One brickwork layer with a channel after each gate column."""
    spec = unitary_spec(n, layers=1)[: n + (n - 1) // 2 + 1]
    for q in range(n):
        spec.append(("dephase", q, 0.05))
        spec.append(("damp", q, 0.03))
        spec.append(("depolarise", q, 0.02))
    return spec


def build_circuit(qt, n: int, spec: list):
    c = qt.Circuit(n)
    for g in spec:
        if g[0] == "rot":
            c.rotate(g[1], g[2], g[3])
        elif g[0] == "cnot":
            c.cnot(g[1], g[2])
        elif g[0] == "h":
            c.h(g[1])
        elif g[0] == "cphase":
            c.cphase(g[1], g[2], g[3])
        elif g[0] == "dephase":
            c.dephase(g[1], g[2])
        elif g[0] == "damp":
            c.damp(g[1], g[2])
        elif g[0] == "depolarise":
            c.depolarise(g[1], g[2])
        else:
            raise ValueError(g)
    return c


_X = np.array([[0, 1], [1, 0]], dtype=np.complex128)
_Y = np.array([[0, -1j], [1j, 0]], dtype=np.complex128)
_Z = np.diag([1.0, -1.0]).astype(np.complex128)


def _rotation(angle: float, axis) -> np.ndarray:
    ax = np.asarray(axis, dtype=np.float64)
    ax = ax / np.linalg.norm(ax)
    return (np.cos(angle / 2) * np.eye(2)
            - 1j * np.sin(angle / 2) * (ax[0] * _X + ax[1] * _Y + ax[2] * _Z))


def _gate(g):
    """(matrix, targets, controls) of one unitary gate of a spec."""
    if g[0] == "rot":
        return _rotation(g[2], g[3]), (g[1],), ()
    if g[0] == "cnot":
        return _X, (g[2],), (g[1],)
    if g[0] == "h":
        return np.array([[1, 1], [1, -1]]) / np.sqrt(2), (g[1],), ()
    if g[0] == "cphase":
        return np.diag([1.0, np.exp(1j * g[3])]), (g[2],), (g[1],)
    raise ValueError(g)


def _kraus(g) -> list:
    p = g[2]
    if g[0] == "dephase":
        return [np.sqrt(1 - p) * np.eye(2), np.sqrt(p) * _Z]
    if g[0] == "damp":
        return [np.array([[1, 0], [0, np.sqrt(1 - p)]]),
                np.array([[0, np.sqrt(p)], [0, 0]])]
    if g[0] == "depolarise":
        return [np.sqrt(1 - p) * np.eye(2)] + [np.sqrt(p / 3) * m
                                               for m in (_X, _Y, _Z)]
    raise ValueError(g)


def _oracle():
    sys.path.insert(0, os.path.join(HERE, "tests"))
    import oracle
    return oracle


def oracle_state(n: int, spec: list) -> np.ndarray:
    oracle = _oracle()
    psi = np.zeros(1 << n, dtype=np.complex128)
    psi[0] = 1.0
    for g in spec:
        u, targets, controls = _gate(g)
        psi = oracle.apply_gate(psi, n, u, targets, controls)
    return psi


def oracle_density(n: int, spec: list) -> np.ndarray:
    oracle = _oracle()
    rho = np.zeros((1 << n, 1 << n), dtype=np.complex128)
    rho[0, 0] = 1.0
    for g in spec:
        if g[0] in ("dephase", "damp", "depolarise"):
            rho = oracle.apply_channel(rho, n, _kraus(g), (g[1],))
        else:
            u, targets, controls = _gate(g)
            rho = oracle.apply_dm(rho, n, u, targets, controls)
    return rho


def hea_circuit(qt, n: int):
    """The bench's hardware-efficient ansatz: per layer a named ry+rz
    column and a CNOT ring."""
    c = qt.Circuit(n)
    for layer in range(2):
        for q in range(n):
            c.ry(q, c.parameter(f"y{layer}_{q}"))
            c.rz(q, c.parameter(f"z{layer}_{q}"))
        for q in range(n):
            c.cnot(q, (q + 1) % n)
    return c


def hamiltonian(n: int):
    """A Pauli sum over low, middle and top qubits (codes 1=X 2=Y 3=Z)."""
    terms = [[(0, 3)], [(1, 3), (2, 3)], [(5, 1), (n - 1, 1)],
             [(7, 2), (n - 2, 2)], [(n - 1, 3)]]
    return terms, [0.5, -0.25, 0.75, 0.125, 1.0]


# -- one-chip phases ---------------------------------------------------------

def pallas_layers(cc) -> int:
    return sum(getattr(op, "kind", None) == "layer" for op in cc._ops)


def phase_statevector(qt, env, n: int, counter, device) -> None:
    import jax
    with Phase(f"a: statevector {n}q, U then U-dagger", counter,
               device) as ph:
        u = build_circuit(qt, n, unitary_spec(n))
        circ = u.extend(u.inverse())
        cc = ph.timed("compile_s", lambda: circ.compile(env).precompile())
        layers = pallas_layers(cc)
        hlo = cc._aot.as_text()
        ph.doc["gates"] = circ.depth
        ph.doc["pallas_layers"] = layers
        check(layers > 0, "the compiled program holds no Pallas layer")
        if jax.devices()[0].platform == "tpu":
            check("tpu_custom_call" in hlo,
                  "the executable holds no Pallas custom call")
        q = qt.createQureg(n, env)
        qt.initZeroState(q)

        def run():
            cc.run(q)
            q.state.block_until_ready()
        ph.timed("run_s", run)
        amp = qt.getAmp(q, 0)
        total = qt.calcTotalProb(q)
        ph.doc["amp0"] = [amp.real, amp.imag]
        ph.doc["total_prob"] = total
        check(amp.real >= 1 - 1e-4, f"<0|U^dag U|0> = {amp}")
        check(abs(total - 1) <= 1e-5, f"total probability {total}")

    with Phase(f"a: GHZ-{n}", counter, device) as ph:
        ghz = qt.Circuit(n).h(0)
        for k in range(n - 1):
            ghz.cnot(k, k + 1)
        cg = ph.timed("compile_s", lambda: ghz.compile(env).precompile())
        qt.initZeroState(q)

        def run():
            cg.run(q)
            q.state.block_until_ready()
        ph.timed("run_s", run)
        for k in (0, n - 1):
            p0 = qt.calcProbOfOutcome(q, k, 0)
            ph.doc[f"p0_q{k}"] = p0
            check(abs(p0 - 0.5) <= 1e-5, f"P(q{k}=0) = {p0}")

    with Phase(f"a: imperative gates and measurement, {n}q", counter,
               device) as ph:
        # each call compiles its own program at full width: the seconds
        # below include that compile
        ph.timed("hadamard_s", lambda: qt.hadamard(q, 0))
        ph.timed("controlledNot_s", lambda: qt.controlledNot(q, 0, n - 1))
        ph.timed("rotateY_s", lambda: qt.rotateY(q, 5, 0.3))
        env.seed([SEED])
        outcome = ph.timed("measure_s", lambda: qt.measure(q, n - 1))
        p = qt.calcProbOfOutcome(q, n - 1, outcome)
        total = qt.calcTotalProb(q)
        ph.doc.update(outcome=outcome, p_outcome=p, total_prob=total)
        check(abs(p - 1) <= 1e-5, f"P(measured outcome) = {p}")
        check(abs(total - 1) <= 1e-5, f"total probability {total}")
    del q


def phase_parity(qt, env, n: int, counter, device) -> None:
    with Phase(f"b: {n}q compiled U against the f64 oracle", counter,
               device) as ph:
        spec = unitary_spec(n)
        cc = ph.timed("compile_s",
                      lambda: build_circuit(qt, n, spec).compile(env)
                      .precompile())
        ph.doc["pallas_layers"] = pallas_layers(cc)
        q = qt.createQureg(n, env)
        qt.initZeroState(q)

        def run():
            cc.run(q)
            q.state.block_until_ready()
        ph.timed("run_s", run)
        got = q.to_numpy()
        want = oracle_state(n, spec)
        err = float(np.max(np.abs(got - want)))
        ph.doc["max_amp_error"] = err
        check(err <= 1e-5, f"max |amp - oracle| = {err}")


def phase_density(qt, env, n: int, n_oracle: int, counter,
                  device) -> None:
    with Phase(f"c: density {n}q with noise", counter, device) as ph:
        spec = noise_spec(n)
        cc = ph.timed("compile_s",
                      lambda: build_circuit(qt, n, spec)
                      .compile(env, density=True).precompile())
        d = qt.createDensityQureg(n, env)
        qt.initZeroState(d)

        def run():
            cc.run(d)
            d.state.block_until_ready()
        ph.timed("run_s", run)
        total = qt.calcTotalProb(d)
        purity = qt.calcPurity(d)
        ph.doc.update(total_prob=total, purity=purity)
        check(abs(total - 1) <= 1e-4, f"trace {total}")
        check(0 < purity <= 1 + 1e-6, f"purity {purity}")
        del d

    with Phase(f"c: density {n_oracle}q against the f64 oracle", counter,
               device) as ph:
        spec = noise_spec(n_oracle)
        cc = build_circuit(qt, n_oracle, spec).compile(env, density=True)
        d = qt.createDensityQureg(n_oracle, env)
        qt.initZeroState(d)
        cc.run(d)
        dim = 1 << n_oracle
        got = d.to_numpy().reshape(dim, dim).T   # flat[r + c*dim] = rho[r,c]
        err = float(np.max(np.abs(got - oracle_density(n_oracle, spec))))
        ph.doc["max_entry_error"] = err
        check(err <= 1e-5, f"max |rho - oracle| = {err}")


def phase_serving(qt, env, n: int, counter, device) -> None:
    with Phase(f"d: SimulationService, {n}q HEA", counter, device) as ph:
        circ = hea_circuit(qt, n)
        cc = circ.compile(env)
        ham = hamiltonian(n)
        rng = np.random.default_rng(SEED)
        pm = rng.uniform(0, 2 * np.pi, size=(4, len(cc.param_names)))

        def direct():
            return (np.asarray(cc.expectation_sweep(pm, ham)),
                    np.asarray(cc.sweep(pm)))
        energies, planes = ph.timed("direct_s", direct)
        svc = qt.SimulationService(env, warm_cache=False, perf_ledger=False)
        try:
            def serve():
                fe = [svc.submit(cc, row, observables=ham) for row in pm]
                fs = [svc.submit(cc, row, shots=64) for row in pm]
                fp = [svc.submit(cc, row) for row in pm]
                return ([f.result(timeout=600) for f in fe],
                        [f.result(timeout=600) for f in fs],
                        [f.result(timeout=600) for f in fp])
            e_got, s_got, p_got = ph.timed("serve_s", serve)
        finally:
            svc.close()
        e_err = float(np.max(np.abs(np.asarray(e_got) - energies)))
        p_err = max(float(np.max(np.abs(np.asarray(g) - w)))
                    for g, w in zip(p_got, planes))
        probs = planes[:, 0] ** 2 + planes[:, 1] ** 2
        n_err = max(abs(float(tot) - float(probs[i].sum()))
                    for i, (_, tot) in enumerate(s_got))
        ph.doc.update(energy_error=e_err, state_error=p_err,
                      sample_norm_error=n_err, requests=3 * len(pm))
        check(e_err <= 1e-5, f"served energies differ by {e_err}")
        check(p_err <= 1e-5, f"served states differ by {p_err}")
        check(n_err <= 1e-5, f"served sample norms differ by {n_err}")
        for i, (idx, _) in enumerate(s_got):
            idx = np.asarray(idx)
            check(idx.shape == (64,) and bool(np.all(probs[i][idx] > 0)),
                  "a served sample has zero probability")


def run_one_chip(qt, env, counter, device, sizes=None) -> None:
    s = {"sv": SV_QUBITS, "parity": PARITY_QUBITS,
         "density": DENSITY_QUBITS, "density_oracle": DENSITY_ORACLE_QUBITS,
         "serve": SERVE_QUBITS, **(sizes or {})}
    phase_statevector(qt, env, s["sv"], counter, device)
    phase_parity(qt, env, s["parity"], counter, device)
    phase_density(qt, env, s["density"], s["density_oracle"], counter,
                  device)
    phase_serving(qt, env, s["serve"], counter, device)


# -- four-chip checks ----------------------------------------------------------

def run_four_chips(qt, precision, counter, devices, sizes=None) -> None:
    s = {"sv": SV_QUBITS, "mesh": MESH_QUBITS, "serve": REPLICA_QUBITS,
         **(sizes or {})}
    n = s["sv"]
    mesh_env = qt.createQuESTEnv(num_devices=4, precision=precision,
                                 seed=[SEED])
    one_env = qt.createQuESTEnv(precision=precision, seed=[SEED],
                                device=devices[-1])
    with Phase(f"4 chips: {n}q U on the mesh against one chip", counter,
               devices[0]) as ph:
        u = build_circuit(qt, n, unitary_spec(n))
        idx = np.random.default_rng(SEED).integers(0, 1 << n, size=64)
        # each qubit's probability is a compile of its own: lane, row and
        # (on the mesh) device-bit qubits
        qubits = sorted({q for q in (0, 1, 6, 7, 13, n - 8, n - 2, n - 1)
                         if 0 <= q < n})
        amps, probs = {}, {}
        for name, env in (("mesh", mesh_env), ("one", one_env)):
            cc = ph.timed(f"{name}_compile_s",
                          lambda: u.compile(env).precompile())
            q = qt.createQureg(n, env)
            qt.initZeroState(q)

            def run():
                cc.run(q)
                q.state.block_until_ready()
            ph.timed(f"{name}_run_s", run)
            check(set(q.state.devices()) == set(env.devices),
                  f"the {name} state is not on its env's devices")
            amps[name] = np.array([qt.getAmp(q, int(i)) for i in idx])
            probs[name] = np.array([qt.calcProbOfOutcome(q, k, 0)
                                    for k in qubits])
            del q
        a_err = float(np.max(np.abs(amps["mesh"] - amps["one"])))
        p_err = float(np.max(np.abs(probs["mesh"] - probs["one"])))
        ph.doc.update(amp_error=a_err, prob_error=p_err)
        check(a_err <= 1e-5, f"mesh and one-chip amplitudes differ {a_err}")
        check(p_err <= 1e-5, f"mesh and one-chip probabilities differ "
                             f"{p_err}")

    m = s["mesh"]
    with Phase(f"4 chips: {m}q U then U-dagger on the mesh", counter,
               devices[0]) as ph:
        u = build_circuit(qt, m, unitary_spec(m))
        circ = u.extend(u.inverse())
        cc = ph.timed("compile_s", lambda: circ.compile(mesh_env)
                      .precompile())
        q = qt.createQureg(m, mesh_env)
        qt.initZeroState(q)

        def run():
            cc.run(q)
            q.state.block_until_ready()
        ph.timed("run_s", run)
        amp = qt.getAmp(q, 0)
        total = qt.calcTotalProb(q)
        ph.doc.update(amp0=[amp.real, amp.imag], total_prob=total,
                      bytes_per_device=2 * 4 * (1 << m) // 4)
        check(amp.real >= 1 - 1e-4, f"<0|U^dag U|0> = {amp}")
        check(abs(total - 1) <= 1e-5, f"total probability {total}")
        del q

    r = s["serve"]
    with Phase(f"4 chips: ServiceRouter over four one-chip replicas, "
               f"{r}q HEA", counter, devices[0]) as ph:
        envs = qt.serve.replica_envs(4, devices_per_replica=1,
                               precision=precision, seed=[SEED])
        placed = [e.device for e in envs]
        check(len({d.id for d in placed}) == 4,
              f"replicas share devices: {placed}")
        circ = hea_circuit(qt, r)
        ham = hamiltonian(r)
        rng = np.random.default_rng(SEED)
        pm = rng.uniform(0, 2 * np.pi, size=(8, len(circ.param_names)))
        for env in envs:
            cc = circ.compile(env).precompile()
            q = qt.createQureg(r, env)
            qt.initZeroState(q)
            cc.run(q, dict(zip(cc.param_names, pm[0])))
            out = set(q.state.devices())
            exe = set(cc._aot.output_shardings.device_set)
            check(out == {env.device} and exe == {env.device},
                  f"replica on {env.device} keeps state on {out}, "
                  f"executable on {exe}")
            del q
        want = np.asarray(circ.compile(envs[0]).expectation_sweep(pm, ham))
        router = qt.ServiceRouter(envs, warm_cache=False, perf_ledger=False)
        try:
            def serve():
                fs = [router.submit(circ, row, observables=ham)
                      for row in pm]
                return [f.result(timeout=600) for f in fs]
            got = ph.timed("serve_s", serve)
        finally:
            router.close()
        err = float(np.max(np.abs(np.asarray(got) - want)))
        ph.doc.update(replica_devices=[str(d) for d in placed],
                      energy_error=err, requests=len(pm))
        check(err <= 1e-5, f"router answers differ by {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX reports {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX has {len(devices)}", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", False)
    sys.path.insert(0, HERE)
    import quest_tpu as qt
    from quest_tpu import compile_cache

    import jaxlib
    emit({"jax": jax.__version__, "jaxlib": jaxlib.__version__,
          "libtpu": _libtpu_version(), "compile_cache":
          compile_cache.enable(), "device_kind": devices[0].device_kind,
          "devices": len(devices)})
    counter = CompileCounter()
    if args.chips == 4:
        run_four_chips(qt, qt.SINGLE, counter, devices[:4])
    else:
        env = qt.createQuESTEnv(num_devices=1, precision=qt.SINGLE,
                                seed=[SEED])
        run_one_chip(qt, env, counter, devices[0])
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


def _libtpu_version() -> str:
    from importlib import metadata
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "not installed"


if __name__ == "__main__":
    sys.exit(main())

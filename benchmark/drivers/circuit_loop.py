"""Closed loop of whole-circuit runs through ``CompiledCircuit.run``.

Set-up compiles the configuration's circuit (``precompile``), draws the
initial state from the seed on the device and runs the circuit once to
warm it, then draws the initial state again. The window runs the circuit
on the register, each run ending on ``block_until_ready``, until
``--seconds`` of run time have passed, and finishes the run in flight.
``circuit_s`` is that time over the runs completed.

The answer checked is the window's first run's. Its input is the seed's
initial state, drawn again once the window has closed. Its output is the
register's state where the window ends on that run; otherwise it is
moved to the host, off the clock, before the second run overwrites it.
So the check holds nothing on the device while the window runs, and the
memory peak is the program's. The number compared is
``||output - reference|| / ||reference||``.
"""

from __future__ import annotations

import time


class Driver:
    def __init__(self, run):
        self.run = run
        self.attempted = 0
        self.failed = 0
        self._notes = []

    def setup(self) -> None:
        run, qt = self.run, self.run.qt
        cfg = run.cfg
        env = qt.createQuESTEnv(num_devices=run.chips, precision=qt.SINGLE)
        self.env = env
        with run.span("compile"):
            self.cc = run.family.build_program(qt, cfg).compile(env) \
                .precompile()
        stats = self.cc.dispatch_stats()
        self.stats = {"gates_in": stats.gates_in,
                      "kernels_out": stats.kernels_out,
                      "relayouts": stats.relayouts,
                      "pallas_layers": sum(getattr(op, "kind", None) == "layer"
                                           for op in self.cc._ops)}
        self.sharding = env.sharding()
        self.reseed(run.seed)
        with run.span("warm_run"):
            t0 = time.perf_counter()
            self.cc.run(self.q)
            self.q.state.block_until_ready()
            self.warm_s = time.perf_counter() - t0
        self.reseed(run.seed)

    def reseed(self, seed: int) -> None:
        """A register holding the seed's initial state."""
        run = self.run
        self.seed = seed
        self.q = None
        self.q = run.qt.createQureg(run.cfg["qubits"], self.env)
        with run.span("initial_state"):
            self.q.state = self.initial()
            self.q.state.block_until_ready()

    def initial(self):
        """The seed's initial state, drawn on the device."""
        return self.run.family.initial_planes(self.run.cfg["qubits"],
                                              self.seed, self.sharding)

    def window(self, seconds: float) -> dict:
        run, q, cc = self.run, self.q, self.cc
        spent = 0.0
        runs = 0
        while spent < seconds:
            if runs == 1:
                with run.span("keep_checked_output"):
                    self.checked = run.jax.device_get(q.state)
            t0 = time.perf_counter()
            with run.span("circuit_run"):
                cc.run(q)
            with run.span("block_until_ready"):
                q.state.block_until_ready()
            spent += time.perf_counter() - t0
            runs += 1
        if runs == 1:
            self.checked = q.state
        self.attempted = runs
        self._notes.append({"window": {"runs": runs, "run_seconds": spent,
                                       "warm_run_s": self.warm_s}})
        return {"circuit_s": spent / runs}

    def free_state(self) -> None:
        """Free the register (the program's state) before the reference
        runs."""
        self.q = None

    release = free_state

    def check(self) -> list:
        return [{"name": "state_rel_err", "value": self.compare(),
                 "limit": self.run.cfg["limits"]["state_rel_err"]}]

    def compare(self) -> float:
        """The gap between the checked run's output and the reference's."""
        ref = self.run.reference
        with self.run.span("reference"):
            psi = ref.make_apply(self.run.cfg, "highest")(self.initial())
            return ref.relative_error(self.checked, psi)

    def control(self) -> float:
        """The same gap with the reference at three bfloat16 passes put in
        the program's place."""
        ref = self.run.reference
        self.checked = None   # the control's passes need the room
        out = ref.make_apply(self.run.cfg, "bf16_3x")(self.initial())
        self.checked = ref.to_planes(out)
        del out
        return self.compare()

    def readings(self) -> dict:
        return {"num_qubits": self.run.cfg["qubits"], "plan": self.stats}

    def notes(self) -> list:
        return [{"plan": self.stats}] + self._notes

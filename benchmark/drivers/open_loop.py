"""Open loop of energy requests to a ``SimulationService``.

Set-up compiles the configuration's circuit, builds the service as users
get it (its default ``max_batch`` and ``max_wait_s``; no warm cache, no
perf ledger) and warms every batch bucket the window can hit with
``warm(batch_sizes=...)``.

Arrivals are Poisson at the traffic file's ``rate_per_s``. The gaps are
one fixed set, drawn from the traffic file's ``arrival_seed`` to fill
``--seconds``; a run's seed only orders them and draws each request's
parameters, so every seed offers the same work. One host thread submits
each request when it is due (``submit(cc, params, observables=cost)``);
a request is timed from when it was due to when its future resolved.
After the last submission the run waits for every future, at most a
minute past the close.

``request_p95_ms`` is the 95th percentile over all requests, a failed
one counting as never answered; ``requests_per_s`` is the requests
answered over the time from the first due time to the later of the
close and the last answer. Every answer is checked against the
reference once the window has closed: the number compared is the
widest gap between a served energy and the reference's.
"""

from __future__ import annotations

import math
import time

import numpy as np

LATE_S = 60.0


def arrival_gaps(rate: float, seconds: float, seed: int) -> np.ndarray:
    """The fixed set of inter-arrival gaps whose due times fall inside
    ``seconds``."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=int(rate * seconds * 2) + 16)
    return gaps[np.cumsum(gaps) < seconds]


class Driver:
    def __init__(self, run):
        self.run = run
        self.attempted = 0
        self.failed = 0
        self._notes = []

    def setup(self) -> None:
        run, qt = self.run, self.run.qt
        cfg, traffic = run.cfg, run.traffic
        env = qt.createQuESTEnv(num_devices=run.chips, precision=qt.SINGLE)
        self.cc = run.family.build_program(qt, cfg).compile(env)
        if list(self.cc.param_names) != run.family.param_names(cfg):
            raise RuntimeError(f"parameter order {self.cc.param_names}")
        self.obs = run.family.observable(cfg)
        self.svc = qt.SimulationService(env, perf_ledger=False,
                                        warm_cache=False)
        top = self.svc.policy.max_batch
        self.buckets = [1 << k for k in range(top.bit_length())
                        if (1 << k) <= top]
        with run.span("warm"):
            self.svc.warm(self.cc, batch_sizes=self.buckets,
                          observables=self.obs)
            # the scheduler prices a batch by the program's digest, which
            # is computed (with a few eager probes) on first use
            _ = self.cc.program_digest
        self.schedule(traffic["rate_per_s"], run.seconds, run.seed)

    def schedule(self, rate: float, seconds: float, seed: int) -> None:
        """Due times and parameters of the window's requests."""
        from benchmark.seeds import host_rng
        gaps = arrival_gaps(rate, seconds, self.run.traffic["arrival_seed"])
        order = host_rng(seed, "arrivals").permutation(len(gaps))
        self.due = np.cumsum(gaps[order])
        self.params = self.run.family.draw_params(
            self.run.cfg, host_rng(seed, "params"), len(gaps))

    def window(self, seconds: float) -> dict:
        run, svc, cc, obs = self.run, self.svc, self.cc, self.obs
        count = len(self.due)
        self.failed = 0
        done = np.full(count, np.nan)
        futures = []
        late = np.zeros(count)

        def stamp(i):
            def on_done(_):
                done[i] = time.perf_counter()
            return on_done

        t0 = time.perf_counter()
        due = t0 + self.due
        for i in range(count):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                with run.span("wait_due"):
                    time.sleep(wait)
            late[i] = time.perf_counter() - due[i]
            with run.span("submit"):
                fut = svc.submit(cc, self.params[i], observables=obs)
            fut.add_done_callback(stamp(i))
            futures.append(fut)
        close = t0 + seconds
        self.energies = np.full(count, np.nan)
        with run.span("result_wait"):
            for i, fut in enumerate(futures):
                try:
                    self.energies[i] = float(fut.result(
                        timeout=max(0.0, close + LATE_S - time.perf_counter())))
                except Exception as exc:  # noqa: BLE001 - counted, reported
                    self.failed += 1
                    done[i] = np.nan
                    if len(self._notes) < 5:
                        self._notes.append({"failed_request": i,
                                            "error": repr(exc)[:300]})
        self.stats = svc.metrics.snapshot()
        self.attempted = count
        answered = ~np.isnan(done)
        latency = np.where(answered, done - due, np.inf)
        end = max(close, float(np.nanmax(done)) if answered.any() else close)
        p95 = float(np.percentile(latency, 95))
        self.latency = latency
        self._notes.append({"window": {
            "requests": count, "answered": int(answered.sum()),
            "generator_late_p95_ms": float(np.percentile(late, 95)) * 1e3,
            "generator_late_max_ms": float(late.max()) * 1e3,
            "request_p50_ms": float(np.percentile(latency, 50)) * 1e3,
            "request_max_ms": float(latency.max()) * 1e3,
            "batch_occupancy": self.stats.get("batch_occupancy")}})
        return {"request_p95_ms": p95 * 1e3 if math.isfinite(p95)
                else LATE_S * 1e3,
                "requests_per_s": float(answered.sum()) / (end - t0)}

    def free_state(self) -> None:
        """Nothing to free: a request's states live inside its dispatch."""

    def release(self) -> None:
        self.svc.close()
        self.svc = None

    def check(self) -> list:
        return [{"name": "energy_max_abs_err", "value": self.compare(),
                 "limit": self.run.cfg["limits"]["energy_max_abs_err"]}]

    def compare(self) -> float:
        """The widest gap between a served energy and the reference's,
        over every answered request."""
        ok = ~np.isnan(self.energies)
        if not ok.any():
            return math.inf
        with self.run.span("reference"):
            want = self.run.reference.make_energies(self.run.cfg, "highest")(
                self.params[ok])
        return float(np.max(np.abs(self.energies[ok] - want)))

    def control(self) -> float:
        """The same gap with the reference at three bfloat16 passes put in
        the program's place."""
        ok = ~np.isnan(self.energies)
        ref = self.run.reference
        low = ref.make_energies(self.run.cfg, "bf16_3x")(self.params[ok])
        high = ref.make_energies(self.run.cfg, "highest")(self.params[ok])
        return float(np.max(np.abs(low - high)))

    def reseed(self, seed: int) -> None:
        self.schedule(self.run.traffic["rate_per_s"], self.run.seconds, seed)

    def readings(self) -> dict:
        return {"service": self.stats}

    def notes(self) -> list:
        return self._notes

"""The service's dispatch loop on the device trace's clock: phase spans
(``quest_tpu.serve.{wait,coalesce,issue,ready,complete,fan_out}``) that
write host events into a ``jax.profiler`` trace and add their seconds to
exact sums in ``ServiceMetrics`` at the same boundary, joined to the
request-scoped traces by a per-service dispatch ``seq``."""

import time

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu.circuits import Circuit
from quest_tpu.serve import SimulationService
from quest_tpu.serve.metrics import PHASES, ServiceMetrics
from quest_tpu.telemetry.tracing import dispatch_annotation

NQ = 6
COUNTERS = ("dispatch_wait_s", "dispatch_host_s", "dispatcher_idle_s")


def _qaoa(n=NQ):
    c = Circuit(n)
    for q in range(n):
        c.h(q)
    g, b = c.parameter("gamma"), c.parameter("beta")
    for q in range(n):
        c.multi_rotate_z((q, (q + 1) % n), g)
    for q in range(n):
        c.rx(q, b)
    return c


def _cost(n=NQ):
    return [[(q, 3), ((q + 1) % n, 3)] for q in range(n)], [-0.5] * n


@pytest.fixture(scope="module")
def program():
    env = qt.createQuESTEnv(num_devices=1)
    cc = _qaoa().compile(env)
    # the scheduler prices a batch by the program's digest, computed
    # (with a few eager probes) on first use: pay it before serving
    _ = cc.program_digest
    return env, cc, _cost()


def _serve(svc, cc, obs, waves=4, per_wave=6, gap_s=0.02, seed=7):
    """Submit ``waves`` bursts of requests ``gap_s`` apart; returns the
    energies."""
    rng = np.random.default_rng(seed)
    futs = []
    for w in range(waves):
        if w:
            time.sleep(gap_s)
        futs += [svc.submit(cc, {"gamma": g, "beta": b}, observables=obs)
                 for g, b in rng.uniform(0, np.pi, size=(per_wave, 2))]
    return [f.result(timeout=120) for f in futs]


def test_timed_span_adds_its_seconds():
    got = []
    with dispatch_annotation("quest_tpu.test.span", got.append, seq=1):
        time.sleep(0.01)
    with pytest.raises(RuntimeError):
        with dispatch_annotation("quest_tpu.test.span", got.append):
            raise RuntimeError("the span still closes and counts")
    assert len(got) == 2 and got[0] >= 0.01 and got[1] >= 0.0


def test_service_metrics_phase_sums():
    m = ServiceMetrics()
    for phase, s in zip(PHASES, (1.0, 0.5, 2.0, 3.0, 3.25, 0.25)):
        m.add_phase_s(phase, s)
    snap = m.snapshot()
    assert snap["host_phase_s"] == dict(zip(
        PHASES, (1.0, 0.5, 2.0, 3.0, 3.25, 0.25)))
    assert snap["dispatch_wait_s"] == 3.0
    # coalesce + issue + (complete - ready) + fan_out
    assert snap["dispatch_host_s"] == pytest.approx(0.5 + 2.0 + 0.25 + 0.25)
    assert snap["dispatcher_idle_s"] == 1.0


def test_counters_zero_after_warm_and_positive_after_traffic(program):
    env, cc, obs = program
    svc = SimulationService(env, perf_ledger=False, warm_cache=False)
    try:
        svc.warm(cc, batch_sizes=[1, 2, 4, 8], observables=obs)
        time.sleep(0.05)      # the idle dispatcher waits meanwhile
        st = svc.dispatch_stats()["service"]
        assert all(st[k] == 0.0 for k in COUNTERS)
        assert all(v == 0.0 for v in st["host_phase_s"].values())
        _serve(svc, cc, obs)
        st = svc.dispatch_stats()["service"]
        assert all(st[k] > 0.0 for k in COUNTERS), \
            {k: st[k] for k in COUNTERS}
        assert all(st["host_phase_s"][p] > 0.0 for p in PHASES)
        from quest_tpu.telemetry import prometheus_text
        text = prometheus_text()
        for name in ("dispatch_wait_s", "dispatch_host_s",
                     "dispatcher_idle_s", "host_phase_s_ready"):
            assert f"quest_tpu_service_{name}{{" in text
    finally:
        svc.close()


def test_counters_account_for_the_dispatcher_lifetime(program):
    env, cc, obs = program
    svc = SimulationService(env, perf_ledger=False, warm_cache=False)
    svc.warm(cc, batch_sizes=[1, 2, 4, 8, 16, 32], observables=obs)
    t0 = time.perf_counter()
    _serve(svc, cc, obs, waves=6, gap_s=0.03)
    svc.close()
    lifetime = time.perf_counter() - t0
    st = svc.metrics.snapshot()
    accounted = sum(st[k] for k in COUNTERS)
    assert accounted == pytest.approx(lifetime, rel=0.10), \
        ({k: st[k] for k in COUNTERS}, lifetime)


def test_profiler_trace_joins_request_traces_by_seq(program, tmp_path):
    import jax
    env, cc, obs = program
    svc = SimulationService(env, perf_ledger=False, warm_cache=False,
                            trace_sample_rate=1.0)
    try:
        svc.warm(cc, batch_sizes=[1, 2, 4, 8], observables=obs)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(svc, cc, obs, waves=3, per_wave=4)
        finally:
            jax.profiler.stop_trace()
        traces = svc.tracer.finished()
    finally:
        svc.close()
    import glob
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    seqs = {}     # phase -> {seq: live}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("quest_tpu.serve."):
                    stats = dict(ev.stats)
                    if "seq" in stats:
                        seqs.setdefault(ev.name, {})[stats["seq"]] = \
                            stats.get("live")
    dispatch = [sp for t in traces for sp in t.spans()
                if sp.name == "dispatch"]
    assert dispatch
    for sp in dispatch:
        seq = sp.attrs["seq"]
        for phase in ("coalesce", "issue", "ready", "fan_out"):
            assert seq in seqs.get(f"quest_tpu.serve.{phase}", {}), \
                (phase, seq, seqs.keys())
        # the dispatch's phases carry its live requests
        assert seqs["quest_tpu.serve.issue"][seq] == sp.attrs["batch"]

"""ISSUE 13: model-vs-measured profiling, drift detection, and the
persistent perf ledger.

Covers the satellite test list: sampling-stride determinism, profile
key completeness (tier + dtype + form, the QL002 vocabulary), the drift
monitor firing on an injected modeled-vs-measured gap (a ``FaultSpec``
stall slowing a dispatch, and a deliberately 4x-miscalibrated
``CommCostModel``), the ledger round-trip across a simulated process
restart warm-starting the router EMA, and the overhead guard (the
``lockcheck.suspended()`` measurement pattern the telemetry bench rows
established).
"""

import json
import os
import time

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import profiling
from quest_tpu.telemetry import profile as prof_mod
from quest_tpu.telemetry import prometheus_text, validate_prometheus_text
from quest_tpu.telemetry.ledger import PERF_SCHEMA, PerfLedger
from quest_tpu.telemetry.profile import DriftMonitor


@pytest.fixture(autouse=True)
def _reset_profiler():
    """Every test starts and ends with the global profiler OFF and
    empty — profiling is opt-in and must never leak across tests."""
    prof_mod.configure(sample_rate=0.0, reset=True)
    prof_mod.profiler().drift.set_recalibrate(None)
    yield
    prof_mod.configure(sample_rate=0.0, reset=True)
    prof_mod.profiler().drift.set_recalibrate(None)


def _compiled(env, num_qubits=3, batch_width=1):
    c = qt.Circuit(num_qubits)
    c.ry(0, c.parameter("a"))
    for q in range(num_qubits - 1):
        c.cnot(q, q + 1)
    return c, c.compile(env, pallas="off")


def _sharded_circuit(num_qubits=6):
    """Gates on the TOP qubits so the 8-device plan carries relayouts
    (modeled comm seconds > 0 — the comm_plan drift feed)."""
    c = qt.Circuit(num_qubits)
    for q in range(num_qubits):
        c.h(q)
    for q in range(num_qubits - 1):
        c.cnot(q, q + 1)
    c.cnot(num_qubits - 1, 0)
    return c


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class TestSampling:
    def test_disabled_is_none_and_free(self):
        assert prof_mod.profile_dispatch("circuits.sweep") is None
        snap = prof_mod.profiler().snapshot()
        assert snap["dispatches_seen"] == 0

    def test_stride_is_deterministic(self):
        prof_mod.configure(sample_rate=0.25, reset=True)
        p = prof_mod.profiler()
        pattern = [p.start("s") is not None for _ in range(32)]
        assert sum(pattern) == 8            # exactly floor(N * rate)
        prof_mod.configure(sample_rate=0.25, reset=True)
        again = [p.start("s") is not None for _ in range(32)]
        assert again == pattern             # reproducible stride
        assert any(pattern) and not all(pattern)

    def test_rate_one_samples_everything(self):
        prof_mod.configure(sample_rate=1.0, reset=True)
        p = prof_mod.profiler()
        assert all(p.start("s") is not None for _ in range(8))

    def test_bad_rate_rejected(self):
        with pytest.raises(ValueError):
            prof_mod.configure(sample_rate=1.5)


# ---------------------------------------------------------------------------
# key completeness + roofline attribution
# ---------------------------------------------------------------------------

class TestKeys:
    def test_key_completeness_tier_dtype_form(self, env):
        """Every profile key carries the QL002 vocabulary — tier,
        dtype, and the form dimensions (kind/bucket/sharding) — plus
        the program digest, so a FAST-tier f32 sweep and an env-tier
        f64 energy dispatch can never share a measurement."""
        prof_mod.configure(sample_rate=1.0, reset=True)
        _, cc = _compiled(env)
        pm = np.zeros((4, 1))
        cc.sweep(pm)
        cc.expectation_sweep(pm, ([[(0, 3)]], [1.0]))
        keys = prof_mod.profiler().snapshot()["keys"]
        kinds = {v["kind"] for v in keys.values()}
        assert {"sweep", "energy"} <= kinds
        expected_dtype = str(np.dtype(env.precision.real_dtype))
        for v in keys.values():
            assert v["tier"]                       # tier token ("env")
            assert v["dtype"] == expected_dtype    # dtype component
            assert v["kind"] and v["bucket"] >= 1  # form components
            assert v["sharding"]
            assert v["program"]                    # content digest

    def test_roofline_attribution(self, env):
        prof_mod.configure(sample_rate=1.0, reset=True)
        _, cc = _compiled(env)
        cc.sweep(np.zeros((4, 1)))
        snap = prof_mod.profiler().snapshot()
        key = next(v for v in snap["keys"].values()
                   if v["site"] == "circuits.sweep")
        assert key["count"] == 1
        assert key["bytes_per_pass"] > 0.0
        assert key["achieved_bytes_per_s"] > 0.0
        # planner bytes over host time is no roofline share: the
        # profiler reports no peak and no fraction of one
        assert "roofline_frac" not in key
        assert "peak_bytes_per_s" not in snap

    def test_dispatch_stats_profile_section(self, env):
        from quest_tpu.serve import SimulationService
        prof_mod.configure(sample_rate=1.0, reset=True)
        _, cc = _compiled(env)
        svc = SimulationService(env, perf_ledger=False)
        try:
            svc.submit(cc, {"a": 0.1}).result(timeout=60)
            prof = svc.dispatch_stats()["profile"]
            assert any(v["site"] == "serve.execute"
                       for v in prof["keys"].values())
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

class TestDriftMonitor:
    def test_baseline_absorbs_systematic_offset(self):
        mon = DriftMonitor(threshold_log2=1.0, baseline_n=3)
        # modeled prices only comm; measured includes compute: a STABLE
        # 8x offset is calibration, not drift
        for _ in range(6):
            mon.record("comm_plan", 1.0, 8.0)
        st = mon.snapshot()["models"]["comm_plan"]
        assert st["baseline_locked"]
        assert st["drift_events"] == 0
        assert abs(st["drift_ratio"] - 1.0) < 1e-9

    def test_fires_on_4x_gap(self):
        mon = DriftMonitor(threshold_log2=1.0, baseline_n=3)
        for _ in range(3):
            mon.record("comm_plan", 1.0, 8.0)     # baseline ratio 8
        mon.record("comm_plan", 1.0, 32.0)        # 4x departure
        snap = mon.snapshot()["models"]["comm_plan"]
        assert snap["drift_events"] == 1
        assert abs(snap["drift_log2"] - 2.0) < 1e-9
        evs = [e for e in mon.events if e["event"] == "model_drift"]
        assert len(evs) == 1
        assert evs[0]["model"] == "comm_plan"
        assert abs(evs[0]["drift_ratio"] - 4.0) < 1e-6
        assert "wall" in evs[0] and "t" in evs[0]   # unified schema

    def test_nonpositive_samples_ignored(self):
        mon = DriftMonitor(baseline_n=1)
        mon.record("m", 0.0, 1.0)
        mon.record("m", 1.0, 0.0)
        assert mon.snapshot()["models"] == {}

    def test_recalibration_hook_invalidates_comm_model(self):
        sentinel = ("sentinel-key",)
        profiling._COMM_MODEL_CACHE[sentinel] = "stale-fit"
        prof_mod.configure(sample_rate=1.0, reset=True)
        prof_mod.enable_recalibration()
        mon = prof_mod.profiler().drift
        mon.baseline_n = 2
        for _ in range(2):
            mon.record("comm_plan", 1.0, 2.0)
        mon.record("comm_plan", 1.0, 64.0)        # fires
        assert sentinel not in profiling._COMM_MODEL_CACHE
        # the fired model's baseline reset so the recalibrated fit is
        # judged fresh
        assert "comm_plan" not in mon.snapshot()["models"]


class TestDriftIntegration:
    def test_stall_fault_fires_drift(self, mesh_env):
        """The ISSUE-13 acceptance shape: a FaultSpec stall slows a
        sharded dispatch, measured departs the baselined modeled ratio,
        a model_drift event lands."""
        from quest_tpu.resilience import FaultInjector, FaultSpec, inject
        cc = _sharded_circuit().compile(mesh_env, pallas="off")
        assert cc._plan_comm_seconds() > 0.0
        q = qt.createQureg(6, mesh_env)
        cc.run(q)                                  # compile warm-up
        np.asarray(q.state)
        prof_mod.configure(sample_rate=1.0, reset=True)
        prof_mod.profiler().drift.baseline_n = 3
        for _ in range(3):
            q2 = qt.createQureg(6, mesh_env)
            cc.run(q2)                             # baseline samples
        base = prof_mod.profiler().snapshot()
        st = base["drift"]["models"]["comm_plan"]
        assert st["baseline_locked"] and st["drift_events"] == 0
        # stall the NEXT circuits.run dispatch long past 2x baseline
        mean_s = max(next(v["mean_s"] for v in base["keys"].values()
                          if v["site"] == "circuits.run"), 1e-3)
        spec = FaultSpec(kind="stall", site="circuits.run",
                         at_calls=(0,))
        with inject(FaultInjector([spec], seed=3,
                                  stall_s=max(0.25, 8.0 * mean_s))):
            q3 = qt.createQureg(6, mesh_env)
            cc.run(q3)
        snap = prof_mod.profiler().drift.snapshot()
        assert snap["models"]["comm_plan"]["drift_events"] >= 1
        assert any(e["event"] == "model_drift"
                   and e["model"] == "comm_plan"
                   for e in prof_mod.profiler().drift.events)

    def test_miscalibrated_comm_model_drifts_within_one_trace(
            self, mesh_env):
        """The acceptance criterion: on the 8-dev CPU mesh a 4x
        alpha/beta miscalibration produces a model_drift event and a
        drift-ratio gauge visible in prometheus_text() within one trace
        of dispatches."""
        from quest_tpu.profiling import CommCostModel
        cc = _sharded_circuit().compile(mesh_env, pallas="off")
        q = qt.createQureg(6, mesh_env)
        cc.run(q)                                  # compile warm-up
        prof_mod.configure(sample_rate=1.0, reset=True)
        prof_mod.profiler().drift.baseline_n = 3
        for _ in range(3):
            q2 = qt.createQureg(6, mesh_env)
            cc.run(q2)                             # calibrated baseline
        # miscalibrate: scale the fitted model's alpha AND beta by 4x
        # (the planner would now price every collective 4x too dear)
        old = cc._cost_model or profiling.DEFAULT_COMM_MODEL
        cc._cost_model = CommCostModel(
            alpha_s=old.alpha_s * 4.0,
            beta_s_per_byte=old.beta_s_per_byte * 4.0,
            inter_alpha_s=(old.inter_alpha_s * 4.0
                           if old.inter_alpha_s is not None else None),
            inter_beta_s_per_byte=(
                old.inter_beta_s_per_byte * 4.0
                if old.inter_beta_s_per_byte is not None else None))
        cc._plan_comm_s = None                     # re-model the plan
        q3 = qt.createQureg(6, mesh_env)
        cc.run(q3)                                 # ONE trace suffices
        drift = prof_mod.profiler().drift.snapshot()
        st = drift["models"]["comm_plan"]
        assert st["drift_events"] >= 1
        # 4x-too-expensive model => measured/modeled fell 4x below
        # baseline => ratio ~0.25
        assert st["drift_ratio"] < 0.5
        txt = prometheus_text()
        assert not validate_prometheus_text(txt)
        gauge = [ln for ln in txt.splitlines()
                 if "drift_ratio" in ln and "comm_plan" in ln
                 and 'source="dispatch_profiler"' in ln]
        assert gauge, "drift-ratio gauge missing from prometheus_text"

    def test_tier_drift_recorded_from_fidelity_monitor(self, env):
        """The tier error model's drift feed: a tiered serving dispatch
        whose fidelity monitor observes nonzero norm drift records a
        tier_error modeled-vs-measured sample."""
        mon = prof_mod.profiler().drift
        prof_mod.configure(sample_rate=1.0, reset=True)
        mon.record("tier_error", 1e-6, 1e-7)
        assert "tier_error" in mon.snapshot()["models"]


# ---------------------------------------------------------------------------
# perf ledger
# ---------------------------------------------------------------------------

class TestPerfLedger:
    def test_program_record_roundtrip_and_merge(self, tmp_path):
        led = PerfLedger(str(tmp_path))
        led.record_program("abc", requests=4, total_request_s=2.0,
                           buckets={8: 2}, tiers={"env": 2})
        led.record_program("abc", requests=4, total_request_s=6.0,
                           buckets={8: 1, 16: 3})
        doc = led.program("abc")
        assert doc["schema"] == PERF_SCHEMA
        assert doc["requests"] == 8
        assert doc["mean_request_s"] == pytest.approx(1.0)
        assert doc["buckets"] == {"8": 3, "16": 3}
        assert led.mean_request_s("abc") == pytest.approx(1.0)
        assert led.mean_request_s() == pytest.approx(1.0)
        assert led.warm_buckets("abc") in ((8, 16), (16, 8))
        assert led.mean_request_s("never-seen") == 0.0
        assert led.warm_buckets("never-seen") == ()

    def test_torn_record_reads_as_fresh(self, tmp_path):
        led = PerfLedger(str(tmp_path))
        led.record_program("abc", requests=1, total_request_s=1.0)
        path = led._program_path("abc")
        with open(path, "w") as fh:
            fh.write('{"torn":')
        led.record_program("abc", requests=2, total_request_s=1.0)
        assert led.program("abc")["requests"] == 2

    def test_service_flush_and_restart_warm_starts_router_ema(
            self, tmp_path, env):
        """The acceptance round-trip: run traffic through a service
        wired to a ledger, close it (the 'process exit'), then build a
        FRESH router over the same ledger dir — its replicas place the
        first request with a NONZERO ema_request_s."""
        from quest_tpu.serve import SimulationService
        from quest_tpu.serve.router import ServiceRouter
        circ, cc = _compiled(env)
        led = PerfLedger(str(tmp_path))
        svc = SimulationService(env, perf_ledger=led)
        try:
            futs = [svc.submit(cc, {"a": 0.1 * i}) for i in range(6)]
            for f in futs:
                f.result(timeout=60)
        finally:
            svc.close()
        digest = cc.program_digest
        assert led.program(digest)["requests"] == 6
        assert led.mean_request_s() > 0.0
        # "restart": a brand-new ledger object over the same directory
        led2 = PerfLedger(str(tmp_path))
        router = ServiceRouter(envs=[env], perf_ledger=led2,
                               max_wait_s=1e-3)
        try:
            seeded = [h.ema_request_s for h in router._replicas]
            assert all(s > 0.0 for s in seeded)     # warm-started
            assert seeded[0] == pytest.approx(led2.mean_request_s())
            # and the seeded router still serves correctly
            got = router.submit(circ, {"a": 0.0}).result(timeout=60)
            assert np.all(np.isfinite(np.asarray(got)))
        finally:
            router.close()

    def test_warm_defaults_to_recorded_buckets(self, tmp_path, env):
        from quest_tpu.serve import SimulationService
        circ, cc = _compiled(env)
        led = PerfLedger(str(tmp_path))
        led.record_program(cc.program_digest, requests=3,
                           total_request_s=0.3, buckets={4: 3})
        svc = SimulationService(env, perf_ledger=led)
        try:
            svc.warm(cc)        # no batch_sizes: the ledger decides
            assert svc.dispatch_stats()["batch_size"] == 4
        finally:
            svc.close()

    def test_double_close_never_double_counts(self, tmp_path, env):
        from quest_tpu.serve import SimulationService
        _, cc = _compiled(env)
        led = PerfLedger(str(tmp_path))
        svc = SimulationService(env, perf_ledger=led)
        try:
            svc.submit(cc, {"a": 0.2}).result(timeout=60)
        finally:
            svc.close()
            svc.close()
        assert led.program(cc.program_digest)["requests"] == 1

    def test_profile_flush_drains(self, tmp_path, env):
        prof_mod.configure(sample_rate=1.0, reset=True)
        _, cc = _compiled(env)
        cc.sweep(np.zeros((2, 1)))
        led = PerfLedger(str(tmp_path))
        p = prof_mod.profiler()
        assert p.flush_to_ledger(led) >= 1
        assert p.flush_to_ledger(led) == 0      # drained: no re-count
        profs = led.profiles()
        assert profs and all(d["schema"] == PERF_SCHEMA for d in profs)

    def test_ema_decay_is_a_supervisor_knob(self):
        from quest_tpu.resilience import SupervisorPolicy
        assert SupervisorPolicy().ema_decay == pytest.approx(0.8)
        assert SupervisorPolicy(ema_decay=0.5).ema_decay == 0.5
        with pytest.raises(ValueError):
            SupervisorPolicy(ema_decay=1.0)


# ---------------------------------------------------------------------------
# overhead guard
# ---------------------------------------------------------------------------

class TestOverhead:
    def test_modeled_overhead_under_budget(self, env):
        """The <1%-at-default-stride contract, measured the
        bench_serving_telemetry way: raw locks via
        ``lockcheck.suspended()``, the deterministic per-sample cost
        amortized over the default stride, divided by a real measured
        dispatch time."""
        from quest_tpu.testing import lockcheck
        _, cc = _compiled(env, num_qubits=8)
        pm = np.zeros((8, 1))
        cc.sweep(pm)                               # compile warm-up
        t0 = time.perf_counter()
        for _ in range(3):
            np.asarray(cc.sweep(pm))
        dispatch_s = (time.perf_counter() - t0) / 3.0
        with lockcheck.suspended():
            prof_mod.configure(sample_rate=1.0, reset=True)
            p = prof_mod.profiler()
            n = 2000
            t0 = time.perf_counter()
            for _ in range(n):
                s = p.start("circuits.sweep")
                s.done(None, program="overhead", kind="sweep", bucket=8,
                       tier="env", dtype="float64", sharding="none",
                       bytes_per_pass=1e6)
            sample_cost_s = (time.perf_counter() - t0) / n
        stride = prof_mod.DEFAULT_PROFILE_RATE
        modeled_pct = sample_cost_s * stride / dispatch_s * 100.0
        assert sample_cost_s < 1e-3               # sane absolute bound
        assert modeled_pct < 1.0, (
            f"modeled profiler overhead {modeled_pct:.3f}% at stride "
            f"{stride} exceeds the 1% budget "
            f"(sample {sample_cost_s * 1e6:.1f}us vs dispatch "
            f"{dispatch_s * 1e3:.2f}ms)")

    def test_unsampled_path_is_cheap(self):
        n = 50000
        t0 = time.perf_counter()
        for _ in range(n):
            prof_mod.profile_dispatch("circuits.sweep")
        per = (time.perf_counter() - t0) / n
        assert per < 5e-6                          # one compare + call


# ---------------------------------------------------------------------------
# tools: perf_compare + bench --ledger + console panel
# ---------------------------------------------------------------------------

class TestTools:
    def _rows(self, tmp_path, name, value):
        p = tmp_path / name
        rows = [
            {"metric": "serving requests/sec, t", "value": value,
             "unit": "requests/sec"},
            {"metric": "aot compile, t", "value": 2.0, "unit": "s"},
            {"metric": "skipped thing", "value": 0.0, "unit": "s"},
        ]
        p.write_text("\n".join(json.dumps(r) for r in rows))
        return str(p)

    def test_perf_compare_gates_regressions(self, tmp_path):
        from tools import perf_compare
        old = self._rows(tmp_path, "old.jsonl", 100.0)
        same = self._rows(tmp_path, "same.jsonl", 99.0)
        bad = self._rows(tmp_path, "bad.jsonl", 50.0)
        assert perf_compare.main([old, same]) == 0
        assert perf_compare.main([old, bad]) == 1
        assert perf_compare.main([old, bad, "--threshold", "60"]) == 0
        assert perf_compare.main([old, bad, "--metric", "aot"]) == 0

    def test_perf_compare_reads_ledger_dirs(self, tmp_path):
        from tools import perf_compare
        for sub, v in (("a", 100.0), ("b", 40.0)):
            led = PerfLedger(str(tmp_path / sub))
            led.append_bench({"metric": "m", "value": v,
                              "unit": "requests/sec"})
        assert perf_compare.main(
            [str(tmp_path / "a"), str(tmp_path / "a")]) == 0
        assert perf_compare.main(
            [str(tmp_path / "a"), str(tmp_path / "b")]) == 1

    def test_perf_compare_lower_is_better_for_seconds(self, tmp_path):
        from tools import perf_compare
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        a.write_text(json.dumps({"metric": "compile", "value": 2.0,
                                 "unit": "s"}))
        b.write_text(json.dumps({"metric": "compile", "value": 4.0,
                                 "unit": "s"}))
        assert perf_compare.main([str(a), str(b)]) == 1   # 2s -> 4s
        assert perf_compare.main([str(b), str(a)]) == 0

    def test_bench_emit_appends_to_ledger(self, tmp_path, monkeypatch,
                                          capsys):
        import bench
        monkeypatch.setenv("QUEST_BENCH_LEDGER_DIR", str(tmp_path))
        bench.emit({"metric": "ledger smoke", "value": 1.0,
                    "unit": "gates/sec", "vs_baseline": 0.0})
        capsys.readouterr()
        rows = PerfLedger(str(tmp_path)).bench_rows()
        assert len(rows) == 1
        assert rows[0]["schema"] == PERF_SCHEMA
        assert rows[0]["metric"] == "ledger smoke"

    def test_obs_console_profiler_panel(self, env):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "obs_console_under_test",
            os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                         "obs_console.py"))
        console = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(console)
        prof_mod.configure(sample_rate=1.0, reset=True)
        _, cc = _compiled(env)
        cc.sweep(np.zeros((2, 1)))
        prof_mod.profiler().drift.record("comm_plan", 1.0, 2.0)
        stats = {"service": {}, "profile":
                 prof_mod.profiler().snapshot()}
        frame = console.render(stats)
        assert "PROFILER" in frame
        assert "circuits.sweep" in frame
        assert "p99" in frame
        assert "drift:" in frame

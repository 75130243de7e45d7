"""Precision-tiered execution (ISSUE 8): budget->tier selection, FAST
oracle parity, tier-keyed cache isolation, and the serving runtime's
violation->escalation path. Kept lean per the tier-1 timing budget:
small registers, shared compiles, no multi-process work."""

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import (DOUBLE_TIER, FAST_TIER, QUAD_TIER, SINGLE_TIER,
                       TIER_LADDER, choose_tier, modeled_tier_error,
                       tier_by_name, tier_runtime_tol)
from quest_tpu.circuits import Circuit


class TestTierSelection:
    def test_ladder_is_rank_ordered_with_nonincreasing_drift(self):
        ranks = [t.rank for t in TIER_LADDER]
        assert ranks == sorted(ranks)
        drifts = [t.drift_per_gate for t in TIER_LADDER]
        assert drifts == sorted(drifts, reverse=True)

    def test_tier_by_name_roundtrip_and_unknown(self):
        assert tier_by_name("fast") is FAST_TIER
        assert tier_by_name(SINGLE_TIER) is SINGLE_TIER
        with pytest.raises(ValueError):
            tier_by_name("quintuple")

    def test_budget_to_tier_is_monotone(self, env):
        """Tighter budget NEVER picks a faster (lower-rank) tier."""
        budgets = np.logspace(-1, -14, 40)   # loose -> tight
        prev_rank = -1
        rejected = False
        for b in budgets:
            try:
                t = choose_tier(float(b), 200, env)
            except ValueError:
                rejected = True    # every tighter budget rejects too
                continue
            assert not rejected
            assert t.rank >= prev_rank
            prev_rank = t.rank
        # spot anchors: a loose budget buys FAST, a strict one climbs
        assert choose_tier(1e-1, 200, env).name == "fast"
        assert choose_tier(1e-12, 200, env).name == "double"

    def test_unmeetable_budget_raises_typed(self, env):
        with pytest.raises(ValueError, match="unmeetable"):
            choose_tier(1e-30, 1000, env)
        with pytest.raises(ValueError):
            choose_tier(0.0, 10, env)

    def test_modeled_error_scales_with_depth_and_floors(self):
        assert modeled_tier_error(FAST_TIER, 200) == \
            pytest.approx(200 * FAST_TIER.drift_per_gate)
        assert modeled_tier_error(DOUBLE_TIER, 1) >= 1e-15
        # runtime tolerance: headroom over the model, floored and capped
        assert tier_runtime_tol(DOUBLE_TIER, 1) == pytest.approx(1e-6)
        assert tier_runtime_tol(FAST_TIER, 10_000) == pytest.approx(2e-2)

    def test_quad_tier_gates(self, env):
        """QUAD is a per-DISPATCH rung: a compile-time quad tier is
        rejected (run()/apply() have no dd form — the message names the
        constraint and the compile_dd alternative), an f32 env rejects
        the dispatch form too (dd planes would round back to f32 on
        exit), and on an x64 f64 env the dispatch form executes through
        the batched dd runner."""
        c = Circuit(3).h(0)
        with pytest.raises(ValueError, match="compile_dd"):
            c.compile(env, tier=QUAD_TIER)
        env32 = qt.createQuESTEnv(num_devices=1, precision=qt.SINGLE,
                                  seed=[2])
        cc32 = c.compile(env32, pallas=False)
        with pytest.raises(ValueError, match="f64-storage"):
            cc32.sweep(np.zeros((1, 0)), tier=QUAD_TIER)
        cc = c.compile(env, pallas=False)
        out = np.asarray(cc.sweep(np.zeros((1, 0)), tier=QUAD_TIER))
        assert out.shape == (1, 2, 8)
        assert ("quad" in {k[-1] for k in cc._batched_cache})

    def test_compile_error_budget_selects_and_reports(self, env):
        c = Circuit(4)
        for q in range(4):
            c.h(q)
        cc = c.compile(env, error_budget=1e-2)
        assert cc.tier is FAST_TIER
        st = cc.dispatch_stats()
        assert st.precision_tier == "fast"
        assert st.modeled_tier_error == pytest.approx(
            modeled_tier_error(FAST_TIER, 4))
        assert st.as_dict()["precision_tier"] == "fast"
        # no budget -> legacy env precision
        assert c.compile(env).tier is None


class TestDefaultCompensated:
    def test_single_source_of_truth(self):
        from quest_tpu.env import default_compensated
        assert default_compensated(qt.SINGLE) is True
        assert default_compensated(qt.DOUBLE) is False
        assert default_compensated(qt.QUAD) is False
        env_s = qt.createQuESTEnv(num_devices=1, precision=qt.SINGLE,
                                  seed=[1])
        assert env_s.compensated is True
        from quest_tpu.serve.router import replica_envs
        for e in replica_envs(2, devices_per_replica=1,
                              precision=qt.SINGLE, seed=[1]):
            assert e.compensated is default_compensated(qt.SINGLE)


class TestFastTierParity:
    """FAST-tier results stay within the MODELED bound of the suite's
    f64 oracle on the three workload shapes the budget API serves."""

    @pytest.mark.parametrize("name", ["qft", "grover", "hea"])
    def test_fast_sweep_within_modeled_bound(self, env, name, rng):
        from quest_tpu import algorithms as alg
        if name == "qft":
            circ = alg.qft(6)
        elif name == "grover":
            circ = alg.grover(6, marked=50, num_iterations=2)
        else:
            circ = Circuit(6)
            for q in range(6):
                circ.ry(q, circ.parameter(f"y{q}"))
            for q in range(5):
                circ.cnot(q, q + 1)
        cc = circ.compile(env, pallas=False)
        pm = rng.uniform(0, 2 * np.pi,
                         size=(2, len(circ.param_names)))
        ref = np.asarray(cc.sweep(pm))            # env f64 oracle
        n_gates = max(len(circ.ops), 1)
        for tier in (FAST_TIER, SINGLE_TIER):
            got = np.asarray(cc.sweep(pm, tier=tier))
            assert got.dtype == ref.dtype          # callers keep env dtype
            dev = float(np.max(np.abs(got - ref)))
            assert dev <= modeled_tier_error(tier, n_gates), \
                f"{name}@{tier.name}: {dev}"
            assert dev > 0.0 or tier is SINGLE_TIER  # f32 ran, not f64

    def test_fast_energy_parity_and_compensated_single(self, env, rng):
        circ = Circuit(5)
        for q in range(5):
            circ.ry(q, circ.parameter(f"y{q}"))
        for q in range(4):
            circ.cnot(q, q + 1)
        cc = circ.compile(env, pallas=False)
        pm = rng.uniform(0, 2 * np.pi, size=(2, 5))
        terms = [[(q, 3)] for q in range(5)] + [[(0, 1), (1, 1)]]
        coeffs = list(rng.normal(size=len(terms)))
        ref = np.asarray(cc.expectation_sweep(pm, (terms, coeffs)))
        bound = modeled_tier_error(FAST_TIER, len(circ.ops)) \
            * (np.abs(coeffs).sum() * 64)
        for tier in (FAST_TIER, SINGLE_TIER):
            got = np.asarray(cc.expectation_sweep(pm, (terms, coeffs),
                                                  tier=tier))
            assert float(np.max(np.abs(got - ref))) <= bound

    def test_fast_pallas_layer_kernel_interpret(self, rng):
        """The FAST lane stage (bf16-split compensated matmuls) agrees
        with the HIGHEST stage within the modeled per-gate drift."""
        import jax.numpy as jnp
        from quest_tpu.ops import pallas_kernels as pk
        u = np.linalg.qr(rng.normal(size=(128, 128))
                         + 1j * rng.normal(size=(128, 128)))[0]
        layer = pk.LayerOp(9, 1, [("lane", u)])
        z = rng.normal(size=512) + 1j * rng.normal(size=512)
        z = (z / np.linalg.norm(z)).astype(np.complex64)
        ref = np.asarray(pk.apply_layer(jnp.asarray(z), 9, layer,
                                        interpret=True))
        fast = np.asarray(pk.apply_layer(jnp.asarray(z), 9, layer,
                                         interpret=True, fast=True))
        dev = float(np.max(np.abs(fast - ref)))
        assert dev <= FAST_TIER.drift_per_gate


class TestTierKeyedCaches:
    def test_batched_cache_isolated_per_tier(self, env, rng):
        c = Circuit(4)
        for q in range(4):
            c.ry(q, c.parameter(f"y{q}"))
        cc = c.compile(env, pallas=False)
        pm = rng.uniform(0, 2 * np.pi, size=(2, 4))
        cc.sweep(pm)
        cc.sweep(pm, tier=FAST_TIER)
        cc.sweep(pm, tier=SINGLE_TIER)
        toks = {k[-1] for k in cc._batched_cache}
        assert {"env", "fast", "single"} <= toks
        assert len(cc._batched_cache) == 3     # one executable per tier

    def test_warm_form_and_warmcache_keys_differ_per_tier(self, env,
                                                          tmp_path):
        from quest_tpu.serve.warmcache import WarmCache
        c = Circuit(4)
        for q in range(4):
            c.h(q)
        cc = c.compile(env)
        f_env = cc._warm_form_key("sweep", "none")
        f_fast = cc._warm_form_key("sweep", "none", FAST_TIER)
        f_single = cc._warm_form_key("sweep", "none", SINGLE_TIER)
        assert len({f_env, f_fast, f_single}) == 3
        wc = WarmCache(str(tmp_path))
        shapes = ((2, 16), (4, 0))
        keys = {wc._key(cc, f, shapes)
                for f in (f_env, f_fast, f_single)}
        assert len(keys) == 3    # a tier mismatch is a MISS, never a hit
        # the in-memory AOT slots are form-keyed the same way
        cc.install_batched_aot(f_fast, shapes, object())
        assert cc._aot_lookup(f_single, (np.zeros((2, 16)),
                                         np.zeros((4, 0)))) is None


class TestEscalation:
    def test_precision_fault_classifies_for_escalation(self):
        from quest_tpu.resilience.health import NumericalFault
        from quest_tpu.resilience.recovery import (PRECISION, POISON,
                                                   classify)
        assert classify(NumericalFault("x", kind="precision")) \
            == PRECISION
        assert classify(NumericalFault("x", kind="nan")) == POISON

    def test_drift_screens(self):
        from quest_tpu.resilience import health
        planes = np.zeros((3, 2, 8))
        planes[:, 0, 0] = [1.0, 1.04, 1.0]
        norms = health.plane_norms(planes)
        assert norms == pytest.approx([1.0, 1.04, 1.0])
        assert list(health.drifted_rows(norms, 1e-2)) == [1]
        assert list(health.drifted_rows([1.0, np.nan], 1e-2)) == []

    def test_injected_violation_escalates_one_tier_up(self, env, rng):
        """The forced-violation path: a drifted FAST-tier result row is
        re-executed one tier up and the caller receives the CORRECT
        planes — escalation, not a wrong answer."""
        from quest_tpu.resilience import FaultInjector, FaultSpec, inject
        from quest_tpu.serve import SimulationService
        c = Circuit(4)
        for q in range(4):
            c.ry(q, c.parameter(f"y{q}"))
        cc = c.compile(env, pallas=False)
        pm = rng.uniform(0, 2 * np.pi, size=(4, 4))
        ref = np.asarray(cc.sweep(pm))
        tol = tier_runtime_tol(FAST_TIER, len(c.ops))
        inj = FaultInjector([FaultSpec(kind="precision",
                                       site="serve.execute",
                                       at_calls=(0,))], seed=3)
        with inject(inj):
            with SimulationService(env, max_batch=4,
                                   max_wait_s=1e-3) as svc:
                futs = [svc.submit(cc, dict(
                    zip(c.param_names, pm[b])), tier=FAST_TIER)
                    for b in range(4)]
                res = [np.asarray(f.result(timeout=120))
                       for f in futs]
                stats = svc.dispatch_stats()
        assert inj.counts("precision") == 1
        snap = stats["service"]
        assert snap["fast_tier_dispatches"] >= 1
        assert snap["tier_violations"] >= 1
        assert snap["tier_escalations"] >= 1
        assert "fast" in stats["resilience"]["tier_observed_drift"]
        for b in range(4):      # zero violations survive to callers
            assert float(np.max(np.abs(res[b] - ref[b]))) <= tol

    def test_double_escalates_to_quad(self, env, rng):
        """The dd rung is re-admitted to the serving ladder (ISSUE 14):
        a violating DOUBLE dispatch escalates to QUAD — which used to be
        silently excluded — and the caller gets correct planes."""
        from quest_tpu.resilience import FaultInjector, FaultSpec, inject
        from quest_tpu.serve import SimulationService
        c = Circuit(3)
        for q in range(3):
            c.ry(q, c.parameter(f"y{q}"))
        cc = c.compile(env, pallas=False)
        pm = rng.uniform(0, 2 * np.pi, size=(1, 3))
        ref = np.asarray(cc.sweep(pm))
        inj = FaultInjector([FaultSpec(kind="precision",
                                       site="serve.execute",
                                       at_calls=(0,))], seed=3)
        with inject(inj):
            with SimulationService(env, max_batch=2,
                                   max_wait_s=1e-3) as svc:
                fut = svc.submit(cc, dict(zip(c.param_names, pm[0])),
                                 tier=DOUBLE_TIER)
                res = np.asarray(fut.result(timeout=120))
                stats = svc.dispatch_stats()["service"]
        assert stats["tier_violations"] >= 1
        assert stats["tier_escalations"] >= 1
        assert float(np.max(np.abs(res - ref[0]))) <= 1e-6

    def test_escalation_bounded_at_ladder_top(self, env, rng):
        """At the top engine rung — now QUAD — a violation fails TYPED
        (kind 'precision'), it does not loop."""
        from quest_tpu.resilience import FaultInjector, FaultSpec, inject
        from quest_tpu.resilience.health import NumericalFault
        from quest_tpu.serve import SimulationService
        c = Circuit(3)
        for q in range(3):
            c.ry(q, c.parameter(f"y{q}"))
        cc = c.compile(env, pallas=False)
        pm = rng.uniform(0, 2 * np.pi, size=(1, 3))
        inj = FaultInjector([FaultSpec(kind="precision",
                                       site="serve.execute",
                                       at_calls=(0,))], seed=3)
        with inject(inj):
            with SimulationService(env, max_batch=2,
                                   max_wait_s=1e-3) as svc:
                fut = svc.submit(cc, dict(zip(c.param_names, pm[0])),
                                 tier=QUAD_TIER)
                with pytest.raises(NumericalFault) as ei:
                    fut.result(timeout=120)
                stats = svc.dispatch_stats()["service"]
        assert ei.value.kind == "precision"
        assert stats["tier_violations"] >= 1
        assert stats["tier_escalations"] == 0

    def test_submit_error_budget_rejects_unmeetable(self, env):
        from quest_tpu.serve import SimulationService
        c = Circuit(3).h(0)
        with SimulationService(env) as svc:
            with pytest.raises(ValueError, match="unmeetable"):
                svc.submit(c, error_budget=1e-30)


class TestPrecisionTraceTool:
    def test_trace_tiers_smoke_fast(self, env, capsys):
        import importlib
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "tools"))
        try:
            ptrace = importlib.import_module("precision_trace")
        finally:
            sys.path.pop(0)
        rc = ptrace.main(["--qubits", "6", "--circuit", "hea",
                          "--budget", "1e-1", "--layers", "1"])
        assert rc == 0
        import json
        out = json.loads(capsys.readouterr().out)
        assert out["chosen_tier"] == "fast"
        assert out["num_qubits"] == 6
        names = [r["tier"] for r in out["ladder"]]
        assert names == ["fast", "single", "double", "quad"]
        assert out["escalation_path"][0] in ("single", "double")
        assert out["modeled_error"] <= 1e-1
        # pinned tier and rejected budget shapes
        env_ = qt.createQuESTEnv(num_devices=1, seed=[0])
        from quest_tpu import algorithms as alg
        doc = ptrace.trace_tiers(alg.qft(5), env_, budget=1e-30)
        assert doc["chosen_tier"] is None
        assert "budget_rejected" in doc
        doc2 = ptrace.trace_tiers(alg.qft(5), env_, tier="single")
        assert doc2["chosen_tier"] == "single"

"""Replicated serving (ISSUE 6): health-aware routing over N service
replicas, replica failover with supervised restart, and the persistent
warm-start compile cache.

The router promises: N replicas behind one ``submit()`` give EXACTLY
the answers one service would (oracle parity <= 1e-12), a killed or
wedged replica never loses a request (failover preserves the original
absolute deadline; the supervisor restarts and readmits only after an
oracle-grade probe), a rolling restart of every replica drops zero
requests, and a restarted replica with a populated warm cache LOADS
its executables (~0 fresh compiles) instead of recompiling.
"""

import os
import threading
import time

import jax
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu.circuits import Circuit
from quest_tpu.resilience import (FaultInjector, FaultSpec,
                                  ResiliencePolicy, SupervisorPolicy)
from quest_tpu.resilience import faults as rz_faults
from quest_tpu.serve import (DeadlineExceeded, ServiceClosed,
                             ServiceRouter, SimulationService, WarmCache,
                             replica_envs)
from quest_tpu.serve.warmcache import circuit_digest


def _hea(num_qubits, layers=1, ring=True):
    c = Circuit(num_qubits)
    for layer in range(layers):
        for q in range(num_qubits):
            c.ry(q, c.parameter(f"y{layer}_{q}"))
            c.rz(q, c.parameter(f"z{layer}_{q}"))
        for q in range(num_qubits if ring else num_qubits - 1):
            c.cnot(q, (q + 1) % num_qubits)
    return c


def _z_ham(n):
    return ([[(q, 3)] for q in range(n)], [1.0] * n)


def _oracle_energies(c, pm, ham):
    env = qt.createQuESTEnv(num_devices=1, seed=[99])
    cc = c.compile(env)
    return np.asarray(cc.expectation_sweep(np.asarray(pm), ham))


def _fast_supervisor(**kw):
    # stall_timeout 2s: above a cold CPU compile (~0.3-0.8s for these
    # tiny programs) so only an injected wedge reads as a stall; tests
    # that tighten it further warm every bucket their trace hits
    base = dict(poll_s=0.01, stall_timeout_s=2.0, restart_backoff_s=0.02,
                probe_timeout_s=60.0, probe_batch=2)
    base.update(kw)
    return SupervisorPolicy(**base)


def _wait_readmitted(router, count=1, timeout=90.0):
    """Wait until ``count`` readmissions have happened (checking the
    replica's ``state`` alone races the supervisor — it is still
    "ready" in the instant between a crash and its detection)."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if router.metrics.snapshot()["readmissions"] >= count and \
                all(h.state == "ready" for h in router._replicas
                    if h.state != "failed"):
            return True
        time.sleep(0.01)
    return False


class TestReplicaEnvs:
    def test_disjoint_device_subsets(self):
        envs = replica_envs(2, devices_per_replica=4, seed=[3])
        assert [e.num_devices for e in envs] == [4, 4]
        d0 = set(d.id for d in envs[0].mesh.devices.ravel())
        d1 = set(d.id for d in envs[1].mesh.devices.ravel())
        assert d0.isdisjoint(d1)

    def test_auto_split_and_single_device(self):
        envs = replica_envs(2, seed=[3])       # 8 devices -> 4 + 4
        assert [e.num_devices for e in envs] == [4, 4]
        envs = replica_envs(3, devices_per_replica=1, seed=[3])
        assert [e.num_devices for e in envs] == [1, 1, 1]
        assert all(e.mesh is None for e in envs)

    def test_overlap_fallback_when_pool_too_small(self):
        # 3 replicas x 4 devices > 8: full-mesh replicas share devices
        envs = replica_envs(3, devices_per_replica=4, seed=[3])
        assert [e.num_devices for e in envs] == [4, 4, 4]

    def test_validation(self):
        with pytest.raises(ValueError):
            replica_envs(0)
        with pytest.raises(ValueError):
            replica_envs(2, devices_per_replica=3)


class TestReplicaPlacement:
    """One-device replica envs name their own device: state, compiled
    executables and served batches stay on it."""

    def test_replicas_keep_state_and_executables_on_their_device(self):
        envs = replica_envs(4, devices_per_replica=1, seed=[3])
        devices = [e.device for e in envs]
        assert len({d.id for d in devices}) == 4
        c = _hea(4)
        for env in envs:
            q = qt.createQureg(4, env)
            qt.initZeroState(q)
            assert q.state.devices() == {env.device}
            cc = c.compile(env).precompile()
            cc.run(q, {nm: 0.1 for nm in cc.param_names})
            assert q.state.devices() == {env.device}
            assert cc._aot.output_shardings.device_set == {env.device}

    def test_service_batches_run_on_the_env_device(self, rng):
        env = replica_envs(4, devices_per_replica=1, seed=[3])[3]
        c = _hea(4)
        ham = _z_ham(4)
        pm = rng.uniform(-np.pi, np.pi, size=(3, len(c.param_names)))
        with SimulationService(env, warm_cache=False,
                               perf_ledger=False) as svc:
            cc = svc.warm(c, batch_sizes=(4,), observables=ham)
            got = [svc.submit(cc, row, observables=ham).result(timeout=60)
                   for row in pm]
            with env.default_device():
                out = cc.sweep(np.asarray(pm))
        assert out.devices() == {env.device}
        np.testing.assert_allclose(got, _oracle_energies(c, pm, ham),
                                   atol=1e-12)

    def test_warm_restart_loads_onto_its_own_device(self, tmp_path, rng):
        c = _hea(4, ring=False)
        ham = _z_ham(4)
        pm = rng.uniform(-np.pi, np.pi, size=(4, len(c.param_names)))
        cache = WarmCache(str(tmp_path / "warm"))
        for boot in range(2):
            # a fresh env each boot, on the same (non-default) device —
            # what a supervised restart builds
            env = replica_envs(4, devices_per_replica=1, seed=[7])[2]
            with SimulationService(env, warm_cache=cache,
                                   perf_ledger=False) as svc:
                cc = svc.warm(c, batch_sizes=(4,), observables=ham)
                got = [svc.submit(cc, row, observables=ham)
                       .result(timeout=60) for row in pm]
            loaded = list(cc._batched_aot.values())
            assert loaded
            for exe in loaded:
                for sh in jax.tree.leaves(exe.output_shardings):
                    assert sh.device_set == {env.device}
            np.testing.assert_allclose(got, _oracle_energies(c, pm, ham),
                                       atol=1e-12)
        st = cache.stats()
        assert st["misses"] == 1 and st["hits"] == 1, st


class TestRouterOracle:
    def test_concurrent_parity_and_load_spread(self, rng):
        """4 threads x 8 requests over 2 subset-mesh replicas (4 devices
        each): oracle parity <= 1e-12 and BOTH replicas serve traffic."""
        n = 5
        c = _hea(n)
        ham = _z_ham(n)
        pm = rng.uniform(0, 2 * np.pi, size=(32, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        envs = replica_envs(2, devices_per_replica=4, seed=[7])
        results = [None] * len(pm)
        errors = []
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_batch=8, max_wait_s=5e-3,
                           request_timeout_s=120.0) as router:
            router.warm(c, batch_sizes=(8,), observables=ham)

            def worker(tid):
                try:
                    futs = []
                    for j in range(8):
                        i = tid * 8 + j
                        futs.append((i, router.submit(
                            c, dict(zip(c.param_names, pm[i])),
                            observables=ham)))
                    for i, f in futs:
                        results[i] = f.result(timeout=120)
                except Exception as e:
                    errors.append(e)

            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors
            st = router.dispatch_stats()
        np.testing.assert_allclose(np.asarray(results, dtype=np.float64),
                                   want, atol=1e-12)
        assert st["router"]["routed"] == len(pm)
        served = [p["service"]["completed"] for p in st["replicas"]]
        assert all(s > 0 for s in served), served

    def test_mixed_kinds_roundtrip(self, env):
        n = 4
        c = Circuit(n)
        a = c.parameter("a")
        c.rx(0, a)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        ham = ([[(0, 3)]], [1.0])
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_batch=4, max_wait_s=5e-3) as router:
            f_state = router.submit(c, {"a": 0.0})
            f_e = router.submit(c, {"a": np.pi}, observables=ham)
            f_shot = router.submit(c, {"a": 0.0}, shots=9)
            planes = f_state.result(timeout=60)
            q = qt.createQureg(n, env)
            qt.initZeroState(q)
            c.compile(env).run(q, {"a": 0.0})
            np.testing.assert_allclose(planes, np.asarray(q.state),
                                       atol=1e-12)
            assert abs(f_e.result(timeout=60) + 1.0) < 1e-12
            idx, total = f_shot.result(timeout=60)
        assert idx.shape == (9,) and np.all(idx == 0)
        assert abs(total - 1.0) < 1e-12

    def test_compiled_circuit_routes_by_recorded_program(self):
        """A CompiledCircuit submission routes by its recorded Circuit
        so ANY replica can serve (and fail over) the request."""
        c = _hea(3, ring=False)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        cc = c.compile(envs[0])
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_wait_s=1e-3) as router:
            fut = router.submit(cc, {nm: 0.0 for nm in cc.param_names})
            assert fut.result(timeout=60).shape == (2, 8)

    def test_submit_validates(self):
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        c = _hea(3, ring=False)
        with ServiceRouter(envs, supervisor=_fast_supervisor()) as router:
            with pytest.raises(TypeError, match="Circuit"):
                router.submit("nope")
            with pytest.raises(DeadlineExceeded):
                router.submit(c, {nm: 0.0 for nm in c.param_names},
                              deadline=-1.0)
        with pytest.raises(ServiceClosed):
            router.submit(c, {nm: 0.0 for nm in c.param_names})

    def test_breaker_aware_routing(self):
        """An open breaker for the submitted program on replica 0 routes
        new requests to replica 1 instead of burning them on the
        fast-fail path."""
        c = _hea(3, ring=False)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        params = {nm: 0.0 for nm in c.param_names}
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_wait_s=1e-3) as router:
            # compile the program on replica 0 (and serve one request)
            router.submit(c, params).result(timeout=60)
            svc0 = router._replicas[0].service
            entry = svc0._compiled.peek(id(c))
            if entry is not None:        # replica 0 took the first one
                cc0 = entry[1]
                key = f"sv-{cc0.num_qubits}q-{id(cc0):x}"
                svc0._breaker._open_until[key] = time.monotonic() + 30.0
                assert svc0.program_state(c)["breaker"] == "open"
                before = router._replicas[1].service.metrics.get(
                    "completed")
                for _ in range(4):
                    router.submit(c, params).result(timeout=60)
                after = router._replicas[1].service.metrics.get(
                    "completed")
                assert after - before == 4


class TestFailoverAndRestart:
    def test_crash_mid_trace_fails_over_and_restarts(self, rng):
        """Kill one of two replicas mid-trace: every request completes
        with oracle parity, failover/restart counters match, and the
        dead replica is restarted, probed, and readmitted."""
        n = 4
        c = _hea(n)
        ham = _z_ham(n)
        pm = rng.uniform(0, 2 * np.pi, size=(24, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_batch=8, max_wait_s=2e-3,
                           request_timeout_s=120.0) as router:
            router.warm(c, batch_sizes=(8,), observables=ham)
            futs = []
            for i, row in enumerate(pm):
                if i == 8:
                    router._replicas[0].service._debug_crash()
                futs.append(router.submit(
                    c, dict(zip(c.param_names, row)), observables=ham))
            got = np.array([f.result(timeout=120) for f in futs])
            np.testing.assert_allclose(got, want, atol=1e-12)
            assert _wait_readmitted(router)
            st = router.dispatch_stats()
        r = st["router"]
        assert r["failovers"] >= 1
        assert r["replica_quarantines"] >= 1
        assert r["replica_restarts"] >= 1
        assert r["readmissions"] >= 1
        assert r["probe_batches"] >= 1

    def test_stall_quarantines_and_work_completes(self, rng):
        """A wedged dispatcher (no heartbeat) is quarantined by the
        supervisor; its stranded requests fail over and complete."""
        n = 4
        c = _hea(n, ring=False)
        ham = _z_ham(n)
        pm = rng.uniform(0, 2 * np.pi, size=(8, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        with ServiceRouter(envs,
                           supervisor=_fast_supervisor(
                               stall_timeout_s=0.3),
                           max_batch=4, max_wait_s=2e-3,
                           request_timeout_s=120.0) as router:
            # every bucket the trace can hit is warmed: with no cold
            # compiles left, only the injected wedge reads as a stall
            router.warm(c, batch_sizes=(1, 2, 4), observables=ham)
            futs = []
            for i, row in enumerate(pm):
                if i == 2:
                    router._replicas[0].service._debug_wedge(1.5)
                futs.append(router.submit(
                    c, dict(zip(c.param_names, row)), observables=ham))
            got = np.array([f.result(timeout=120) for f in futs])
            np.testing.assert_allclose(got, want, atol=1e-12)
            st = router.dispatch_stats()
        assert st["router"]["replica_quarantines"] >= 1
        events = [e["event"] for e in router.events]
        assert "replica_quarantined" in events

    def test_failover_preserves_absolute_deadline(self):
        """A failed-over request keeps its ORIGINAL absolute deadline —
        the surviving replica's queue holds it with (strictly) less
        than the full budget, not a fresh request_timeout_s."""
        c = _hea(3, ring=False)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        params = {nm: 0.0 for nm in c.param_names}
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_wait_s=60.0, request_timeout_s=60.0
                           ) as router:
            router.warm(c, batch_sizes=(1,))
            # replica 1 paused: the failed-over request will sit in its
            # queue where the deadline is inspectable
            router._replicas[1].service.pause()
            t_submit = time.monotonic()
            fut = router.submit(c, params, deadline=5.0)
            time.sleep(0.2)              # let it land somewhere
            # kill whichever replica holds it; the other is paused
            holder = 0 if router._replicas[0].service._backlog else 1
            other = 1 - holder
            if holder == 1:
                router._replicas[1].service.resume()
                router._replicas[0].service.pause()
            router._replicas[holder].service._debug_crash()
            t0 = time.monotonic()
            while not router._replicas[other].service._backlog \
                    and time.monotonic() - t0 < 30:
                time.sleep(0.01)
            svc = router._replicas[other].service
            with svc._cond:
                reqs = list(svc._queue)
            assert reqs, "failed-over request never reached the " \
                         "surviving replica"
            # original absolute deadline: t_submit + 5s, NOT re-derived
            # from the 60s request_timeout_s at failover time
            assert reqs[0].deadline == pytest.approx(t_submit + 5.0,
                                                     abs=0.5)
            # drop the inspection-friendly 60s max-wait so the request
            # dispatches inside its (preserved) 5s deadline
            from quest_tpu.serve import CoalescePolicy
            svc.policy = CoalescePolicy(max_batch=64, max_wait_s=1e-3)
            svc.resume()
            assert fut.result(timeout=60).shape == (2, 8)

    def test_backoff_past_deadline_fails_fast(self, env):
        """Satellite: a retry whose backoff hold would outlive the
        request deadline fails fast with DeadlineExceeded instead of
        burning the retry on a stale dispatch."""
        cc = _hea(3, ring=False).compile(env)
        policy = ResiliencePolicy(backoff_base_s=30.0, backoff_cap_s=30.0,
                                  backoff_jitter=0.0)
        inj = FaultInjector(
            [FaultSpec("transient", site="serve.execute", at_calls=(0,))],
            seed=3)
        with SimulationService(env, max_wait_s=1e-3, max_retries=3,
                               resilience=policy) as svc:
            with rz_faults.inject(inj):
                t0 = time.monotonic()
                fut = svc.submit(cc, {nm: 0.0 for nm in cc.param_names},
                                 deadline=1.0)
                with pytest.raises(DeadlineExceeded, match="backoff"):
                    fut.result(timeout=60)
                elapsed = time.monotonic() - t0
            snap = svc.dispatch_stats()["service"]
        assert elapsed < 10.0            # did NOT sleep the 30s backoff
        assert snap["retries"] == 0      # the retry was never burned
        assert snap["timeouts"] == 1

    def test_probe_rejects_wrong_replica(self, rng):
        """Readmission is oracle-gated: a restarted replica whose probe
        results are wrong stays quarantined."""
        n = 3
        c = _hea(n, ring=False)
        ham = _z_ham(n)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        sp = _fast_supervisor(max_restart_attempts=2,
                              restart_backoff_s=10.0)
        with ServiceRouter(envs, supervisor=sp, max_wait_s=2e-3
                           ) as router:
            router.warm(c, batch_sizes=(2,), observables=ham)
            # poison the recorded reference: every honest probe now fails
            with router._lock:
                router._warm_specs[0].reference += 1.0
            router._replicas[0].service._debug_crash()
            t0 = time.monotonic()
            while router.metrics.snapshot()["probe_failures"] < 1 \
                    and time.monotonic() - t0 < 60:
                time.sleep(0.02)
            st = router.dispatch_stats()
            assert st["router"]["probe_failures"] >= 1
            assert st["router"]["readmissions"] == 0
            assert router._replicas[0].state in ("quarantined",
                                                 "restarting", "failed")

    def test_hedge_resolves_stuck_request(self, rng):
        """Opt-in hedging: a request wedged on one replica is duplicated
        onto the other after hedge_after_s; the hedge result wins."""
        n = 3
        c = _hea(n, ring=False)
        ham = _z_ham(n)
        pm = rng.uniform(0, 2 * np.pi, size=(1, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        sp = _fast_supervisor(stall_quarantine=False)  # hedge, not restart
        with ServiceRouter(envs, supervisor=sp, max_wait_s=1e-3,
                           hedge_after_s=0.1, request_timeout_s=60.0
                           ) as router:
            router.warm(c, batch_sizes=(1,), observables=ham)
            # wedge BOTH, submit, then unwedge only replica 1: the
            # request lands on a wedged replica and only the hedge to
            # the other one can resolve it
            router._replicas[0].service._debug_wedge(3.0)
            fut = router.submit(c, dict(zip(c.param_names, pm[0])),
                                observables=ham)
            got = fut.result(timeout=60)
            st = router.dispatch_stats()
        assert abs(got - want[0]) < 1e-12
        assert st["router"]["hedged_dispatches"] >= 1


class TestRollingRestart:
    def test_rolling_restart_drops_zero_requests(self, rng):
        """The acceptance bar: a rolling restart of ALL replicas under
        continuous traffic completes with every request answered
        correctly — zero drops, every replica restarted and readmitted."""
        n = 4
        c = _hea(n, ring=False)
        ham = _z_ham(n)
        pm = rng.uniform(0, 2 * np.pi, size=(48, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        results = [None] * len(pm)
        errors = []
        stop = threading.Event()
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_batch=8, max_wait_s=2e-3,
                           request_timeout_s=120.0) as router:
            router.warm(c, batch_sizes=(8,), observables=ham)

            def traffic():
                try:
                    for i, row in enumerate(pm):
                        fut = router.submit(
                            c, dict(zip(c.param_names, row)),
                            observables=ham)
                        results[i] = fut.result(timeout=120)
                        time.sleep(0.005)
                except Exception as e:
                    errors.append(e)
                finally:
                    stop.set()

            t = threading.Thread(target=traffic)
            t.start()
            time.sleep(0.05)             # traffic in flight
            acct = router.rolling_restart(timeout_per_replica=120.0)
            t.join(timeout=180)
            st = router.dispatch_stats()
        assert not errors, errors
        assert stop.is_set()
        np.testing.assert_allclose(np.asarray(results, dtype=np.float64),
                                   want, atol=1e-12)
        assert all(r["ok"] for r in acct["replicas"]), acct
        assert st["router"]["replica_restarts"] >= 2
        assert st["router"]["readmissions"] >= 2
        assert st["router"]["failed_unroutable"] == 0

    def test_rolling_restart_needs_two_replicas(self):
        envs = replica_envs(1, devices_per_replica=1, seed=[7])
        with ServiceRouter(envs, supervisor=_fast_supervisor()) as router:
            with pytest.raises(ValueError, match=">= 2"):
                router.rolling_restart()


class TestWarmCache:
    def test_digest_is_stable_and_discriminating(self):
        def build():
            c = Circuit(4)
            for q in range(4):
                c.ry(q, c.parameter(f"y{q}"))
            c.cnot(0, 1)
            return c
        d1, d2 = circuit_digest(build()), circuit_digest(build())
        assert d1 == d2 and d1 is not None
        changed = build()
        changed.rz(0, 0.25)
        assert circuit_digest(changed) != d1
        dens = circuit_digest(build(), is_density=True)
        assert dens != d1

    def test_cold_miss_then_warm_restart_hits(self, env, tmp_path, rng):
        """Acceptance: a service warmed against a populated cache dir
        reports ~0 fresh compiles (all hits) where the cold pass was
        all misses — and the loaded executables give oracle answers."""
        c = _hea(4, ring=False)
        ham = _z_ham(4)
        pm = rng.uniform(0, 2 * np.pi, size=(8, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        cache = WarmCache(str(tmp_path / "warm"))
        with SimulationService(env, max_batch=8, max_wait_s=2e-3,
                               warm_cache=cache) as svc:
            svc.warm(c, batch_sizes=(8,), observables=ham)
            svc.warm(c, batch_sizes=(8,))
            cold = svc.dispatch_stats()["service"]
        assert cold["warm_cache_misses"] == 2
        assert cold["warm_cache_hits"] == 0

        # "process restart": fresh service, fresh cache object, same dir
        cache2 = WarmCache(str(tmp_path / "warm"))
        env2 = qt.createQuESTEnv(num_devices=1, seed=[12345])
        with SimulationService(env2, max_batch=8, max_wait_s=2e-3,
                               warm_cache=cache2) as svc:
            svc.warm(c, batch_sizes=(8,), observables=ham)
            svc.warm(c, batch_sizes=(8,))
            futs = [svc.submit(c, dict(zip(c.param_names, row)),
                               observables=ham) for row in pm]
            got = np.array([f.result(timeout=60) for f in futs])
            warm = svc.dispatch_stats()["service"]
            wc = svc.dispatch_stats()["warm_cache"]
        assert warm["warm_cache_hits"] == 2      # ~0 fresh compiles
        assert warm["warm_cache_misses"] == 0
        assert wc["hits"] == 2 and wc["errors"] == 0
        np.testing.assert_allclose(got, want, atol=1e-12)

    def test_torn_artifact_falls_back_to_compile(self, env, tmp_path,
                                                 rng):
        """A truncated artifact never crashes or mis-answers: the load
        counts an error, the form recompiles, the slot is rewritten."""
        c = _hea(3, ring=False)
        ham = _z_ham(3)
        cache = WarmCache(str(tmp_path / "warm"))
        with SimulationService(env, max_batch=4, warm_cache=cache) as svc:
            svc.warm(c, batch_sizes=(4,), observables=ham)
        # truncate every stored artifact to half its bytes
        paths = []
        for dirpath, _, names in os.walk(str(tmp_path / "warm")):
            for nm in names:
                if nm.endswith(".exe.pkl"):
                    paths.append(os.path.join(dirpath, nm))
        assert paths
        for p in paths:
            blob = open(p, "rb").read()
            with open(p, "wb") as f:
                f.write(blob[:len(blob) // 2])
        cache2 = WarmCache(str(tmp_path / "warm"))
        env2 = qt.createQuESTEnv(num_devices=1, seed=[12345])
        pm = rng.uniform(0, 2 * np.pi, size=(4, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        with SimulationService(env2, max_batch=4,
                               warm_cache=cache2) as svc:
            svc.warm(c, batch_sizes=(4,), observables=ham)
            futs = [svc.submit(c, dict(zip(c.param_names, row)),
                               observables=ham) for row in pm]
            got = np.array([f.result(timeout=60) for f in futs])
        st = cache2.stats()
        assert st["errors"] >= 1          # the torn load was counted
        assert st["misses"] >= 1          # and recompiled
        np.testing.assert_allclose(got, want, atol=1e-12)

    def test_router_restart_rides_shared_cache(self, tmp_path, rng):
        """The router's replicas share one cache: a supervised restart
        re-warms from artifacts the first boot stored (hits, no fresh
        compiles on the replacement service)."""
        c = _hea(4, ring=False)
        ham = _z_ham(4)
        envs = replica_envs(2, devices_per_replica=1, seed=[7])
        cache = WarmCache(str(tmp_path / "warm"))
        with ServiceRouter(envs, supervisor=_fast_supervisor(),
                           max_batch=8, max_wait_s=2e-3,
                           warm_cache=cache) as router:
            router.warm(c, batch_sizes=(8,), observables=ham)
            base = cache.stats()
            assert base["misses"] >= 1    # first boot compiled + stored
            router._replicas[0].service._debug_crash()
            assert _wait_readmitted(router)
            st = cache.stats()
            restarted = router._replicas[0].service
            warm_metrics = restarted.metrics.snapshot()
        assert st["hits"] >= base["hits"] + 1
        assert st["misses"] == base["misses"]     # restart compiled NOTHING
        assert warm_metrics["warm_cache_hits"] >= 1
        assert warm_metrics["warm_cache_misses"] == 0


@pytest.mark.chaos
class TestReplicaChaosStorm:
    """ISSUE 6 acceptance: replica-level chaos on the 8-device CPU
    pool — replicas killed and stalled mid-trace plus engine-level
    transient faults; every request completes with oracle parity
    <= 1e-12 or fails typed, and the failover/restart counters are
    consistent with the injected faults."""

    def test_replica_kill_and_stall_storm(self, rng):
        n = 4
        c = _hea(n)
        ham = _z_ham(n)
        REQS = 96
        pm = rng.uniform(0, 2 * np.pi, size=(REQS, len(c.param_names)))
        want = _oracle_energies(c, pm, ham)
        envs = replica_envs(2, devices_per_replica=4, seed=[11])
        specs = [
            FaultSpec("replica_crash", site="router.route",
                      at_calls=(13,)),
            FaultSpec("replica_stall", site="router.route",
                      at_calls=(47,)),
            FaultSpec("transient", site="serve.execute",
                      probability=0.08),
        ]
        inj = FaultInjector(specs, seed=20260803, stall_s=0.05)
        policy = ResiliencePolicy(
            seed=1, backoff_base_s=1e-3, backoff_cap_s=0.02,
            breaker_threshold=25, breaker_cooldown_s=0.05,
            degrade_after=6, degrade_cooldown_s=0.2,
            watchdog_timeout_s=10.0)
        typed = (qt.ServeError, qt.NumericalFault, RuntimeError)
        completed, typed_failures, wrong = 0, 0, []
        router = ServiceRouter(
            envs, supervisor=_fast_supervisor(stall_timeout_s=0.4),
            max_batch=8, max_wait_s=2e-3, max_retries=3,
            request_timeout_s=120.0, resilience=policy)
        try:
            router.warm(c, batch_sizes=(1, 2, 4, 8), observables=ham)
            with rz_faults.inject(inj):
                futs = [router.submit(c, dict(zip(c.param_names, pm[i])),
                                      observables=ham)
                        for i in range(REQS)]
                got = [None] * REQS
                for i, f in enumerate(futs):
                    try:
                        got[i] = f.result(timeout=120)
                        completed += 1
                        if abs(got[i] - want[i]) > 1e-12:
                            wrong.append((i, got[i], want[i]))
                    except typed:
                        typed_failures += 1
                stats = router.dispatch_stats()
        finally:
            router.close()

        # injected replica faults actually fired
        snap = stats["fault_injection"]
        assert snap["injected_by_kind"].get("replica_crash", 0) == 1
        assert snap["injected_by_kind"].get("replica_stall", 0) == 1
        assert snap["injected_by_kind"].get("transient", 0) >= 1

        # every request accounted for; NO silent wrong answers
        assert not wrong, wrong[:5]
        assert completed + typed_failures == REQS
        assert completed > 0

        # counters consistent with the injected faults: the crash and
        # the stall each forced a quarantine, the crash forced at least
        # one restart cycle, and stranded requests failed over
        r = stats["router"]
        assert r["replica_quarantines"] >= 2
        assert r["replica_restarts"] >= 1
        assert r["failovers"] >= 1
        assert r["failed_unroutable"] == 0
        events = [e["event"] for e in router.events]
        assert "injected_replica_crash" in events
        assert "injected_replica_stall" in events


class TestRouterStatsCoherence:
    def test_router_dispatch_stats_coherent_under_live_trace(self, rng):
        """Satellite (ISSUE 9): concurrent ``router.dispatch_stats()``
        snapshot coherence at the ROUTER level — per-replica aggregation
        read continuously while a live trace runs, mirroring the
        engine-level torn-read test. Readers must never see a torn or
        impossible snapshot: fixed replica set, derived ratios in
        range, and per-replica counters monotone non-decreasing."""
        n = 4
        c = _hea(n, ring=False)
        ham = _z_ham(n)
        pm = rng.uniform(0, 2 * np.pi, size=(48, len(c.param_names)))
        envs = replica_envs(2, devices_per_replica=1, seed=[31])
        # stall timeout ABOVE any first-dispatch compile: a supervisor
        # restart mid-trace legitimately zeroes a replica's counters,
        # which is not the torn-read this test hunts (warm() below
        # removes the compiles from the traced window too)
        router = ServiceRouter(envs, warm_cache=False, max_batch=8,
                               max_wait_s=1e-3,
                               supervisor=_fast_supervisor(
                                   stall_timeout_s=30.0),
                               trace_sample_rate=1.0)
        router.warm(c, batch_sizes=[8], observables=ham)
        bad = []
        stop = threading.Event()

        def reader():
            last = {}            # replica index -> (restarts, counters)
            while not stop.is_set():
                try:
                    stats = router.dispatch_stats()
                except Exception as e:   # a torn read raising IS the bug
                    bad.append(("raised", type(e).__name__, str(e)))
                    return
                reps = stats["replicas"]
                if len(reps) != 2:
                    bad.append(("replica_count", len(reps)))
                    continue
                for rep in reps:
                    svc = rep["service"]
                    for ratio in ("coalesce_ratio", "padded_fraction"):
                        if not 0.0 <= svc[ratio] <= 1.0:
                            bad.append((ratio, svc[ratio]))
                    if svc["max_batch_occupancy"] > 8:
                        bad.append(("occupancy", svc[
                            "max_batch_occupancy"]))
                    if svc["shared_batch_requests"] > svc[
                            "coalesced_requests"]:
                        bad.append(("shared>coalesced", svc))
                    prev_restarts, prev = last.get(
                        rep["replica"], (rep["restarts"], {}))
                    if rep["restarts"] == prev_restarts:
                        for key in ("batches", "completed",
                                    "coalesced_requests"):
                            if svc[key] < prev.get(key, 0):
                                bad.append(("regressed",
                                            rep["replica"], key,
                                            prev.get(key), svc[key]))
                    last[rep["replica"]] = (rep["restarts"], svc)
                tel = stats["telemetry"]
                if tel["traces_sampled"] > tel["requests_seen"]:
                    bad.append(("tracer", tel))

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            futs = [router.submit(c, dict(zip(c.param_names, row)),
                                  observables=ham) for row in pm]
            got = np.asarray([f.result(timeout=120) for f in futs])
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
            final = router.dispatch_stats()
            router.close()
        assert not bad, bad[:5]
        # the aggregation adds up after the trace drains (no replica
        # restarted, so no counters were lost): every request was
        # routed once and completed on exactly one replica
        assert final["router"]["replica_restarts"] == 0
        assert final["router"]["routed"] == len(pm)
        assert sum(rep["service"]["completed"]
                   for rep in final["replicas"]) == len(pm)
        want = _oracle_energies(c, pm, ham)
        assert np.max(np.abs(got - want)) <= 1e-12

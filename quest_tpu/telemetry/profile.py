"""Model-vs-measured dispatch profiling and cost-model drift detection.

Every scheduling decision in this engine runs off a MODEL — the layout
planner and batch-sharding policy price data movement in
:class:`~quest_tpu.profiling.CommCostModel` seconds, the precision
ladder selects tiers off the :class:`~quest_tpu.profiling.
TierErrorModel`, and the router places requests on a bare service-time
EMA — but nothing closed the loop against what the hardware actually
did. This module is that loop:

- :class:`DispatchProfiler` — a process-global, deterministic-stride
  sampler (the ``trace_sample_rate`` pattern: default OFF, one float
  compare per dispatch; a sampled dispatch costs one ``block_until_
  ready`` + a histogram observe). Sampled dispatches are timed
  **wall-to-ready** at the same boundaries QL004's fault hooks and
  trace annotations cover, keyed by ``(site, program digest, kind,
  batch bucket, tier, dtype, sharding mode, replica)`` into fixed-
  bucket histograms. Every site passes the planner's known
  bytes-per-pass, so each key also reports planner bytes over host
  wall-to-ready seconds (``achieved_bytes_per_s``). That is host time,
  not device time, and it is no share of a roofline: after fusion the
  planner's bytes can exceed what the device moved. A device roofline
  comes from a profiler trace (``benchmark/trace_reduce.py``).
- :class:`DriftMonitor` — compares modeled vs measured wherever a model
  exists (``comm_plan``: the plan's modeled collective seconds vs the
  measured collective-bearing dispatch time; ``batch_amp_comm``: the
  ``choose_batch_sharding`` amp-mode crossover price vs observed;
  ``tier_error``: the tier error model's bound vs the fidelity
  monitor's observed drift). The modeled quantity and the measured one
  are different units of the same decision, so the monitor tracks the
  LOG-RATIO against a per-model baseline locked from the first
  ``baseline_n`` samples: a stable model-to-hardware offset is
  calibration, a RATIO that moves is drift. When ``|log2(measured /
  modeled) - baseline|`` exceeds ``threshold_log2``
  (``QUEST_TPU_DRIFT_LOG2``, default 1.0 = a 2x departure), a
  unified-schema ``model_drift`` event is recorded, the per-model
  ``drift_ratio`` gauge moves off 1.0 (visible in
  :func:`~quest_tpu.telemetry.export.prometheus_text` through the
  registered ``dispatch_profiler`` provider), and — with
  :func:`enable_recalibration` opted in — the cached
  :func:`~quest_tpu.profiling.measure_comm_model` fit is invalidated so
  the next plan recalibrates.

The profiler is enabled with :func:`configure` (or
``QUEST_TPU_PROFILE=1`` / ``QUEST_TPU_PROFILE_RATE=<rate>`` in the
environment); :data:`DEFAULT_PROFILE_RATE` is the default stride when
enabled without an explicit rate — measured overhead at that stride is
the ``bench.py`` profiler rows' <1% contract. Snapshots surface as
``dispatch_stats()["profile"]`` on services and routers, in
``tools/obs_console.py``'s profiler panel, and persist across process
restarts through :class:`~quest_tpu.telemetry.ledger.PerfLedger`.
"""

from __future__ import annotations

import collections
import math
import os
import threading
import time
from typing import Optional

from .events import make_event
from .metrics import LATENCY_BUCKETS_S, Histogram, metrics_registry

__all__ = ["DEFAULT_PROFILE_RATE", "DispatchProfiler", "DriftMonitor",
           "profiler", "configure", "profile_dispatch", "record_model",
           "enable_recalibration", "platform_peak_bytes_per_s",
           "PEAK_BYTES_PER_S"]

# the default sampling stride when profiling is enabled without an
# explicit rate: every 8th dispatch. A sampled dispatch pays one
# block_until_ready (which serving dispatches pay anyway, converting
# results to numpy) plus ~microseconds of bookkeeping, so 1/8 keeps the
# modeled overhead well under the 1% bench budget on every backend.
DEFAULT_PROFILE_RATE = 0.125

# Peak memory bandwidth (B/s) per ``device_kind`` as JAX reports it: the
# one table bench.py's rows and the layout planner's memory model use. A
# device that is missing here is an error, never a default.
PEAK_BYTES_PER_S = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s of HBM per chip
    "TPU v5 lite": 8.19e11,
    # Google Cloud documentation, "TPU v4": 1,228 GB/s of HBM per chip
    "TPU v4": 1.228e12,
    # the CPU backend of the test rigs: a nominal two-channel DDR4 host
    # (42 GB/s), a model and not a measurement
    "cpu": 4.2e10,
}


def platform_peak_bytes_per_s() -> tuple:
    """``(device_kind, peak B/s)`` for the current backend's device.
    Raises ``KeyError`` for a device kind the table does not hold."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BYTES_PER_S:
        raise KeyError(f"no peak bandwidth for device kind {kind!r}: add "
                       "it, with its source, to PEAK_BYTES_PER_S")
    return (kind, PEAK_BYTES_PER_S[kind])


class DriftMonitor:
    """Per-model modeled-vs-measured drift tracking.

    :meth:`record` takes one ``(modeled, measured)`` pair of POSITIVE
    quantities in the same decision (seconds vs seconds, error vs
    error). The first ``baseline_n`` samples of a model lock its
    baseline log-ratio — the systematic model-to-hardware offset, which
    is expected (modeled comm seconds price only the wire; measured
    dispatch time includes compute) and is NOT drift. After the lock,
    ``drift_log2 = log2(measured/modeled) - baseline``; when its
    absolute value exceeds ``threshold_log2`` a ``model_drift`` event
    (unified schema, :mod:`quest_tpu.telemetry.events`) is recorded and
    the optional recalibration hook fires. ``drift_ratio`` (the gauge)
    is ``2**drift_log2`` — 1.0 means the model still predicts what it
    predicted at baseline.
    """

    def __init__(self, threshold_log2: Optional[float] = None,
                 baseline_n: int = 4, max_events: int = 256):
        if threshold_log2 is None:
            try:
                threshold_log2 = float(os.environ.get(
                    "QUEST_TPU_DRIFT_LOG2", "1.0"))
            except ValueError:
                threshold_log2 = 1.0
        self.threshold_log2 = float(threshold_log2)
        self.baseline_n = max(1, int(baseline_n))
        self._lock = threading.Lock()
        self._models: dict = {}
        self._t0 = time.monotonic()
        self._recalibrate = None
        self.events: collections.deque = collections.deque(
            maxlen=max(1, int(max_events)))

    def set_recalibrate(self, fn) -> None:
        """Opt-in hook ``fn(model_name)`` invoked (outside the monitor
        lock) whenever a drift event fires for ``model_name``."""
        self._recalibrate = fn

    def reset(self, model: Optional[str] = None) -> None:
        """Drop a model's baseline (all models when ``model`` is None)
        so the next samples re-establish it — the post-recalibration
        step."""
        with self._lock:
            if model is None:
                self._models.clear()
            else:
                self._models.pop(model, None)

    def record(self, model: str, modeled: float, measured: float) -> None:
        """One modeled-vs-measured observation (non-positive values are
        ignored: a zero model prices nothing to compare)."""
        if not (modeled > 0.0 and measured > 0.0):
            return
        log2r = math.log2(measured / modeled)
        fired = None
        with self._lock:
            st = self._models.get(model)
            if st is None:
                st = {"samples": 0, "baseline": None, "_bsum": 0.0,
                      "_bn": 0, "drift_log2": 0.0, "drift_ratio": 1.0,
                      "drift_events": 0, "last_log2_ratio": 0.0}
                self._models[model] = st
            st["samples"] += 1
            st["last_log2_ratio"] = log2r
            if st["baseline"] is None:
                st["_bsum"] += log2r
                st["_bn"] += 1
                if st["_bn"] >= self.baseline_n:
                    st["baseline"] = st["_bsum"] / st["_bn"]
                dev = 0.0
            else:
                dev = log2r - st["baseline"]
            st["drift_log2"] = dev
            st["drift_ratio"] = 2.0 ** dev
            if abs(dev) > self.threshold_log2:
                st["drift_events"] += 1
                ev = make_event(
                    "model_drift", self._t0, model=model,
                    drift_ratio=round(2.0 ** dev, 6),
                    drift_log2=round(dev, 4),
                    modeled=float(modeled), measured=float(measured),
                    threshold_log2=self.threshold_log2)
                self.events.append(ev)
                fired = model
            recal = self._recalibrate
        if fired is not None and recal is not None:
            try:
                recal(fired)
            except (RuntimeError, ValueError, OSError, TypeError):
                pass    # recalibration is best-effort; drift is recorded

    def snapshot(self) -> dict:
        with self._lock:
            models = {name: {k: v for k, v in st.items()
                             if not k.startswith("_")}
                      for name, st in self._models.items()}
            for st in models.values():
                if st["baseline"] is None:
                    st["baseline"] = 0.0
                    st["baseline_locked"] = False
                else:
                    st["baseline_locked"] = True
            return {"threshold_log2": self.threshold_log2,
                    "baseline_n": self.baseline_n,
                    "models": models,
                    "events": list(self.events)}


class _KeyStats:
    """One profile key's accumulated device-time distribution."""

    __slots__ = ("fields", "hist", "bytes_per_pass")

    def __init__(self, fields: dict):
        self.fields = fields
        self.hist = Histogram("dispatch_s", buckets=LATENCY_BUCKETS_S)
        self.bytes_per_pass = 0.0


class _Sample:
    """One sampled dispatch: created at dispatch entry (so injected
    stalls and the whole executable call land inside the span), closed
    by :meth:`done` with the full key once the dispatch's mode/bucket
    are known."""

    __slots__ = ("_profiler", "site", "t0")

    def __init__(self, profiler_: "DispatchProfiler", site: str,
                 t0: float):
        self._profiler = profiler_
        self.site = site
        self.t0 = t0

    def done(self, out=None, *, program: str = "", kind: str = "",
             bucket: int = 0, tier: str = "env", dtype: str = "",
             sharding: str = "none", replica: str = "",
             bytes_per_pass: float = 0.0, models: Optional[dict] = None
             ) -> float:
        """Close the span wall-to-READY: blocks on ``out`` (the
        dispatch's result arrays) so the measured time is device
        completion, not async enqueue. ``models`` maps drift-model
        names to their modeled quantity for this dispatch. Returns the
        measured seconds."""
        if out is not None:
            try:
                import jax
                jax.block_until_ready(out)
            except (ImportError, TypeError, ValueError, RuntimeError):
                pass    # host-resident results are already ready
        dt = time.monotonic() - self.t0
        self._profiler._record(
            self.site, dt, program=program, kind=kind, bucket=bucket,
            tier=tier, dtype=dtype, sharding=sharding, replica=replica,
            bytes_per_pass=bytes_per_pass, models=models)
        return dt


class DispatchProfiler:
    """Deterministic-stride dispatch profiler + drift monitor.

    ``sample_rate`` in [0, 1] gates :meth:`start` exactly like
    :class:`~quest_tpu.telemetry.tracing.Tracer`: rate 0 (the default)
    costs one float compare per dispatch; a positive rate samples
    ``floor(N * rate)`` of every ``N`` dispatches on a reproducible
    stride (never a random draw — replayed incidents must profile the
    same dispatches). ``max_keys`` bounds the per-key histogram map; a
    workload cycling more distinct keys keeps its existing keys and
    counts the drops.
    """

    def __init__(self, sample_rate: float = 0.0, max_keys: int = 256,
                 name: str = "dispatch_profiler",
                 drift_threshold_log2: Optional[float] = None,
                 drift_baseline_n: int = 4):
        if not (0.0 <= sample_rate <= 1.0):
            raise ValueError(
                f"profile sample rate must be in [0, 1], got "
                f"{sample_rate!r}")
        self.name = name
        self.sample_rate = float(sample_rate)
        self.max_keys = max(1, int(max_keys))
        self._lock = threading.Lock()
        self._seen = 0
        self._sampled = 0
        self._keys_dropped = 0
        self._keys: dict = {}
        self.drift = DriftMonitor(threshold_log2=drift_threshold_log2,
                                  baseline_n=drift_baseline_n)
        metrics_registry().register(name, self.snapshot,
                                    kind="profiler", owner=self)

    # -- sampling ----------------------------------------------------------

    def start(self, site: str) -> Optional[_Sample]:
        """A new sampled dispatch span, or None (unsampled / disabled).
        Rate 0 returns before touching the lock."""
        rate = self.sample_rate
        if rate <= 0.0:
            return None
        with self._lock:
            self._seen += 1
            take = int(self._seen * rate) > int((self._seen - 1) * rate)
            if not take:
                return None
            self._sampled += 1
        return _Sample(self, site, time.monotonic())

    def _record(self, site: str, dt: float, *, program: str, kind: str,
                bucket: int, tier: str, dtype: str, sharding: str,
                replica: str, bytes_per_pass: float,
                models: Optional[dict]) -> None:
        fields = {"site": site, "program": str(program)[:16],
                  "kind": kind, "bucket": int(bucket), "tier": tier,
                  "dtype": dtype, "sharding": sharding,
                  "replica": replica}
        keystr = "|".join((site, fields["program"], kind,
                           f"b{int(bucket)}", tier, dtype, sharding,
                           replica))
        with self._lock:
            ks = self._keys.get(keystr)
            if ks is None:
                if len(self._keys) >= self.max_keys:
                    self._keys_dropped += 1
                    ks = None
                else:
                    ks = _KeyStats(fields)
                    self._keys[keystr] = ks
        if ks is not None:
            # the histogram carries its own lock; observing outside the
            # profiler lock keeps the acquisition graph a simple chain
            ks.hist.observe(dt)
            if bytes_per_pass > 0.0:
                ks.bytes_per_pass = float(bytes_per_pass)
        for model, modeled in (models or {}).items():
            self.drift.record(model, float(modeled), dt)

    # -- reading -----------------------------------------------------------

    @staticmethod
    def _render_keys(items) -> dict:
        """Per-key percentile documents from ``(keystr,
        _KeyStats)`` pairs — shared by :meth:`snapshot` (live view) and
        :meth:`flush_to_ledger` (drained view)."""
        keys = {}
        for keystr, ks in items:
            count = ks.hist.count
            total = ks.hist.sum
            mean = total / count if count else 0.0
            achieved = ks.bytes_per_pass / mean \
                if (mean > 0.0 and ks.bytes_per_pass > 0.0) else 0.0
            keys[keystr] = {
                **ks.fields,
                "count": count,
                "mean_s": mean,
                "p50_s": ks.hist.percentile(50.0),
                "p99_s": ks.hist.percentile(99.0),
                "bytes_per_pass": ks.bytes_per_pass,
                "achieved_bytes_per_s": achieved,
            }
        return keys

    def snapshot(self) -> dict:
        """The profiler's full state as a plain dict: counters, per-key
        host wall-to-ready percentiles + achieved bytes/s, and
        the drift monitor's per-model gauges/events."""
        with self._lock:
            items = list(self._keys.items())
            out = {"sample_rate": self.sample_rate,
                   "dispatches_seen": self._seen,
                   "dispatches_sampled": self._sampled,
                   "keys_dropped": self._keys_dropped}
        out["keys"] = self._render_keys(items)
        out["drift"] = self.drift.snapshot()
        return out

    stats = snapshot

    def reset(self) -> None:
        with self._lock:
            self._seen = 0
            self._sampled = 0
            self._keys_dropped = 0
            self._keys.clear()
        self.drift.reset()
        self.drift.events.clear()

    def flush_to_ledger(self, ledger) -> int:
        """DRAIN the accumulated per-key aggregates into a
        :class:`~quest_tpu.telemetry.ledger.PerfLedger`. The key map is
        SWAPPED OUT under the lock before anything is rendered, so two
        flushing owners (every closing service flushes) each persist a
        disjoint set of measurements — never the same one twice — and a
        dispatch recorded mid-flush lands in the fresh map rather than
        being erased. Returns the number of ledger keys written."""
        with self._lock:
            drained = self._keys
            self._keys = {}
        if not drained:
            return 0
        return ledger.record_profile(
            {"keys": self._render_keys(list(drained.items()))})


# ---------------------------------------------------------------------------
# the process-global profiler (the instance every dispatch site records
# into; the exporters scrape it through the metrics registry)
# ---------------------------------------------------------------------------

def _env_rate() -> float:
    raw = os.environ.get("QUEST_TPU_PROFILE_RATE", "").strip()
    if raw:
        try:
            return min(max(float(raw), 0.0), 1.0)
        except ValueError:
            return 0.0
    if os.environ.get("QUEST_TPU_PROFILE", "") not in ("", "0", "off"):
        return DEFAULT_PROFILE_RATE
    return 0.0


_PROFILER = DispatchProfiler(sample_rate=_env_rate())


def profiler() -> DispatchProfiler:
    """The process-global :class:`DispatchProfiler` (default off —
    enable with :func:`configure` or ``QUEST_TPU_PROFILE[_RATE]``)."""
    return _PROFILER


def configure(sample_rate: Optional[float] = None,
              drift_threshold_log2: Optional[float] = None,
              reset: bool = False) -> DispatchProfiler:
    """(Re)configure the global profiler. ``reset=True`` clears the
    accumulated keys, counters, drift baselines, and events first."""
    if reset:
        _PROFILER.reset()
    if sample_rate is not None:
        if not (0.0 <= float(sample_rate) <= 1.0):
            raise ValueError(
                f"profile sample rate must be in [0, 1], got "
                f"{sample_rate!r}")
        _PROFILER.sample_rate = float(sample_rate)
    if drift_threshold_log2 is not None:
        _PROFILER.drift.threshold_log2 = float(drift_threshold_log2)
    return _PROFILER


def profile_dispatch(site: str) -> Optional[_Sample]:
    """The dispatch-site hook: a :class:`_Sample` for this dispatch, or
    None (disabled / unsampled — ONE float compare). Create it BEFORE
    the fault hook fires so injected stalls land inside the measured
    span; close it with ``sample.done(out, **key)`` once the dispatch's
    bucket/tier/sharding are known. Travels with the QL004 trio: every
    fault-hooked dispatch boundary carries a trace annotation AND this
    hook (enforced by quest-lint QL004)."""
    p = _PROFILER
    if p.sample_rate <= 0.0:
        return None
    return p.start(site)


def record_model(model: str, modeled: float, measured: float) -> None:
    """Feed one modeled-vs-measured pair to the global drift monitor
    (no-op while profiling is disabled — the monitor's baselines should
    only accumulate when the operator asked for the loop)."""
    p = _PROFILER
    if p.sample_rate <= 0.0:
        return
    p.drift.record(model, modeled, measured)


def enable_recalibration() -> None:
    """Opt in to model recalibration on drift: a ``model_drift`` event
    on a comm model invalidates the cached
    :func:`~quest_tpu.profiling.measure_comm_model` fit (the next plan
    re-runs the microbench) and resets that model's drift baseline so
    the recalibrated fit is judged fresh. Also enabled by
    ``QUEST_TPU_DRIFT_RECALIBRATE=1``."""

    def _recal(model: str) -> None:
        if "comm" in model:
            from .. import profiling
            profiling.invalidate_comm_model()
        _PROFILER.drift.reset(model)

    _PROFILER.drift.set_recalibrate(_recal)


if os.environ.get("QUEST_TPU_DRIFT_RECALIBRATE", "") not in ("", "0",
                                                             "off"):
    enable_recalibration()

"""Persistent warm-start compile cache: ``warm()`` means "load", not
"recompile the world".

Every (re)started serving process pays the same compiles for the same
programs — on a TPU that is seconds to minutes per batch bucket, which
makes supervised replica restart under live traffic (serve/router.py)
slow. This module makes the compile a disk artifact:

- **keyed executable artifacts**: for each warm form — a
  ``(circuit digest, env fingerprint, form key, exact arg shapes)``
  slot — the compiled executable is serialized
  (``jax.experimental.serialize_executable``) to
  ``$QUEST_TPU_WARM_CACHE_DIR`` and a later ``warm()`` DESERIALIZES it
  into :attr:`CompiledCircuit._batched_aot` instead of tracing and
  compiling, onto the devices of the program's env. Covers the
  unsharded batch mode (single-device replicas — the router's common
  CPU/test shape and any per-device replica);
- the forms these artifacts cannot carry (mesh-sharded modes, samplers)
  compile warm from JAX's own persistent cache, which this module leaves
  alone: :func:`quest_tpu.compile_cache.enable` places it.

Keying is content-addressed and refuses to guess: the circuit digest
hashes the recorded op stream (static matrices by value; parameterized
builders by code object AND by sample evaluations at fixed probe
bindings, so a changed formula changes the key), and the env
fingerprint pins jax version, backend, device kind, count and ids,
precision, and x64 — any mismatch is a miss, never a wrong executable. Loads of
corrupt/incompatible artifacts count ``errors`` and fall back to a
fresh compile that overwrites the slot.

``WarmCache.stats()`` reports hits / misses / stores / errors / skips;
the serving runtime mirrors hits and misses into its metrics registry
(the acceptance signal: a restarted replica with a populated cache dir
reports ~0 fresh compiles).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
from typing import Optional

import jax
import numpy as np

__all__ = ["WarmCache", "circuit_digest", "env_fingerprint",
           "WARM_CACHE_ENV"]

WARM_CACHE_ENV = "QUEST_TPU_WARM_CACHE_DIR"

# fixed probe bindings for parameterized-op sampling: two distinct
# per-name values pin WHICH parameter drives WHICH op (a code-object
# hash alone cannot see closure contents)
_PROBES = ((0.137, 0.0173), (1.113, 0.0311))


def _probe_params(names, base: float, step: float) -> dict:
    return {nm: base + step * i for i, nm in enumerate(names)}


def _hash_array(h, arr) -> None:
    a = np.ascontiguousarray(np.asarray(arr))
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())


def _hash_consts(h, consts) -> None:
    """Digest a code object's constants. Nested code objects (inner
    lambdas, comprehensions) must be hashed structurally — their repr
    embeds a per-process memory address and an absolute source path, so
    ``repr(co_consts)`` would change the digest on every restart and the
    persistent cache would silently never hit."""
    for c in consts:
        if hasattr(c, "co_code"):
            h.update(c.co_name.encode())
            h.update(c.co_code)
            _hash_consts(h, c.co_consts)
        else:
            h.update(repr(c).encode())


def _hash_callable(h, fn, names) -> bool:
    """Digest a parameterized matrix/diag builder: code identity plus
    sample evaluations at the probe bindings. Returns False when the
    builder cannot be probed (the op then has no stable content key and
    the whole circuit is uncacheable)."""
    code = getattr(fn, "__code__", None)
    h.update(getattr(fn, "__qualname__", type(fn).__name__).encode())
    if code is not None:
        h.update(code.co_code)
        _hash_consts(h, code.co_consts)
    try:
        for base, step in _PROBES:
            out = fn(_probe_params(names, base, step))
            if isinstance(out, (list, tuple)):
                for m in out:
                    _hash_array(h, m)
            else:
                _hash_array(h, out)
    # quest: allow-broad-except(digest boundary: an unhashable exotic
    # gate payload means "uncacheable", never a caller-visible error)
    except Exception:
        return False
    return True


def circuit_digest(circuit, is_density: bool = False) -> Optional[str]:
    """Stable content digest of a recorded :class:`~quest_tpu.circuits.
    Circuit` — the across-process-restart analogue of the ``id()``-keyed
    in-memory caches. None when any op resists content addressing
    (never guess: an aliased key would load a WRONG executable)."""
    h = hashlib.sha256()
    h.update(f"v1|{circuit.num_qubits}|{int(bool(is_density))}|".encode())
    names = tuple(circuit.param_names)
    h.update("|".join(names).encode())
    for op in circuit.ops:
        h.update(f"|{op.kind}|{op.targets}|{op.ctrl_mask}|"
                 f"{op.flip_mask}|".encode())
        if op.mat is not None:
            _hash_array(h, op.mat)
        if op.diag is not None:
            _hash_array(h, op.diag)
        for fn in (op.mat_fn, op.diag_fn):
            if fn is not None and not _hash_callable(h, fn, names):
                return None
        if op.kraus is not None:
            if callable(op.kraus):
                if not _hash_callable(h, op.kraus, names):
                    return None
            else:
                for m in op.kraus:
                    if callable(m):
                        if not _hash_callable(h, m, names):
                            return None
                    else:
                        _hash_array(h, m)
    return h.hexdigest()


def env_fingerprint(env) -> str:
    """Everything a serialized executable implicitly depends on: a
    mismatch in any field must be a cache MISS."""
    try:
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", dev.platform)
    except (AttributeError, IndexError, RuntimeError):
        kind = "unknown"
    # a serialized executable names the devices its arguments live on,
    # so it loads only onto those: the device ids are part of the key
    return "|".join([
        jax.__version__, jax.default_backend(), str(kind),
        str(env.num_devices), ",".join(str(d.id) for d in env.devices),
        env.precision.name,
        str(np.dtype(env.precision.real_dtype)),
        str(bool(jax.config.jax_enable_x64)),
        str(jax.process_count() if hasattr(jax, "process_count") else 1),
    ])


class WarmCache:
    """One on-disk executable cache rooted at ``root``.

    Thread-safe (the router's supervisor restarts replicas from a
    background thread while callers warm). All I/O failures degrade to
    misses — the cache can make a restart fast, never make it wrong or
    make it crash.
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self._c = {"hits": 0, "misses": 0, "stores": 0, "errors": 0,
                   "skipped": 0}

    @classmethod
    def from_env(cls) -> Optional["WarmCache"]:
        """The ambient cache: rooted at ``$QUEST_TPU_WARM_CACHE_DIR``,
        None (disabled) when the variable is unset/empty."""
        root = os.environ.get(WARM_CACHE_ENV, "").strip()
        return cls(root) if root else None

    # -- accounting --------------------------------------------------------

    def _incr(self, name: str) -> None:
        with self._lock:
            self._c[name] += 1

    def stats(self) -> dict:
        with self._lock:
            return {**self._c, "root": self.root}

    # -- keyed artifacts ---------------------------------------------------

    def _key(self, cc, form: tuple, shapes: tuple) -> Optional[str]:
        digest = circuit_digest(cc.circuit, cc.is_density)
        if digest is None:
            return None
        doc = f"{digest}|{env_fingerprint(cc.env)}|{form!r}|{shapes!r}"
        return hashlib.sha256(doc.encode()).hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key[:2], key + ".exe.pkl")

    def _load(self, key: str, devices: list):
        """The stored executable, loaded onto ``devices`` (without them
        JAX loads it onto every local device)."""
        path = self._path(key)
        if not os.path.exists(path):
            return None
        try:
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            with open(path, "rb") as f:
                payload = pickle.load(f)
            return deserialize_and_load(*payload,
                                        execution_devices=devices)
        # quest: allow-broad-except(torn-artifact boundary: a corrupt
        # file or incompatible runtime must read as a MISS, never an
        # error -- the recompile overwrites the slot)
        except Exception:
            # torn file, incompatible runtime, missing support: treat
            # as absent (the recompile will overwrite the slot)
            self._incr("errors")
            return None

    def _store(self, key: str, compiled) -> bool:
        try:
            from jax.experimental.serialize_executable import serialize
            payload = serialize(compiled)
            blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        # quest: allow-broad-except(backend boundary: executable
        # serialization support varies by backend/jax version; any
        # failure means "don't persist", never a serving error)
        except Exception:
            self._incr("errors")
            return False
        path = self._path(key)
        d = os.path.dirname(path)
        try:
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)        # atomic: no torn artifacts
        except OSError:
            self._incr("errors")
            return False
        self._incr("stores")
        return True

    # -- the warm entry point ----------------------------------------------

    def warm_form(self, cc, kind: str, batch: int,
                  hamiltonian=None, tier=None) -> str:
        """Make one warm form's executable resident in ``cc``:
        ``"hit"`` — deserialized from disk and installed (no compile);
        ``"miss"`` — compiled fresh, stored, installed; ``"skip"`` —
        this form cannot be cached here (mesh batch mode, unprobeable
        circuit, serialization unsupported) and the caller should warm
        it by dispatch (the XLA layer still helps). ``tier`` selects a
        precision tier's form: the tier token rides the form key (and
        therefore this cache's content address), so a FAST-tier
        artifact can never be served to another tier — a tier mismatch
        is a miss, never a wrong program."""
        try:
            form, shapes, _ = cc.lower_batched(kind, batch, hamiltonian,
                                               lower=False, tier=tier)
        except ValueError:
            self._incr("skipped")
            return "skip"
        key = self._key(cc, form, shapes)
        if key is None:
            self._incr("skipped")
            return "skip"
        compiled = self._load(key, cc.env.devices)
        if compiled is not None:
            cc.install_batched_aot(form, shapes, compiled)
            self._incr("hits")
            return "hit"
        try:
            _, _, lowered = cc.lower_batched(kind, batch, hamiltonian,
                                             tier=tier)
            compiled = lowered.compile()
        # quest: allow-broad-except(warm boundary: a form that cannot
        # lower/compile AOT just skips persistent warming -- the live
        # jit path still serves it)
        except Exception:
            self._incr("skipped")
            return "skip"
        if not self._store(key, compiled):
            # unsupported backend serialization: the compile already
            # happened, so still install it for this process's dispatch
            cc.install_batched_aot(form, shapes, compiled)
            self._incr("skipped")
            return "skip"
        cc.install_batched_aot(form, shapes, compiled)
        self._incr("misses")
        return "miss"

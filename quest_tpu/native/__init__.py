"""ctypes binding to the native scheduler (``native/src/scheduler.cc``).

The shared library is built on demand with g++ (the repo ships no binary
artifacts); set ``QUEST_TPU_NO_NATIVE=1`` to force the pure-Python planner
(`quest_tpu.parallel.layout`). Both produce identical schedules — the test
suite asserts it.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

__all__ = ["available", "load", "build_and_load", "NativeScheduler"]

from .hosttag import BASE_FLAGS, HOST_TAG, build_tag


def _src_path(src_name: str) -> str:
    return os.path.abspath(os.path.join(
        os.path.dirname(__file__), os.pardir, os.pardir,
        "native", "src", src_name))


def tagged_lib_path(base_name: str, src_name: Optional[str] = None,
                    flags: tuple[str, ...] = ()) -> str:
    """Cache path for a native library, keyed by host/ISA fingerprint
    and — for a library built on demand from ``src_name`` with
    ``flags`` — by a digest of that source text and build command."""
    tag = HOST_TAG
    if src_name is not None and os.path.exists(_src_path(src_name)):
        tag = build_tag(_src_path(src_name), flags)
    return os.path.join(os.path.dirname(__file__), f"{base_name}.{tag}.so")


_LIB_PATH = tagged_lib_path("libquest_sched", "scheduler.cc")
_lib: Optional[ctypes.CDLL] = None
_load_failed = False

KIND_U, KIND_DIAG, KIND_U_PARAM, KIND_DIAG_PARAM = 0, 1, 2, 3


def build_and_load(src_name: str, lib_path: str,
                   extra_flags: tuple[str, ...] = ()) -> Optional[ctypes.CDLL]:
    """Build (if absent) and dlopen one native library, or return None.
    ``lib_path`` comes from :func:`tagged_lib_path` with the same source
    and ``extra_flags``.

    Shared on-demand g++ pattern for every native component: the repo ships
    no binary artifacts, ``QUEST_TPU_NO_NATIVE=1`` disables all of them, and
    a failed build/load is reported as None so callers fall back to their
    pure-Python/XLA path. Callers gate on QUEST_TPU_NO_NATIVE per call
    (so clearing the variable re-enables native in-process) — this
    function only builds and loads.
    """
    src = _src_path(src_name)
    if not os.path.exists(lib_path):
        # the path carries the source digest (tagged_lib_path): a library
        # that exists was built from exactly this source and command
        if not os.path.exists(src):
            return None
        cmd = [os.environ.get("CXX", "g++"), *BASE_FLAGS, *extra_flags,
               "-shared", "-o", lib_path, src]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        except (subprocess.SubprocessError, OSError):
            return None
    try:
        return ctypes.CDLL(lib_path)
    except OSError:
        return None


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the scheduler library, or None."""
    global _lib, _load_failed
    if os.environ.get("QUEST_TPU_NO_NATIVE"):
        return None               # checked per call: unsetting re-enables
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    lib = build_and_load("scheduler.cc", _LIB_PATH)
    if lib is None:
        _load_failed = True
        return None

    lib.qsched_create.restype = ctypes.c_void_p
    lib.qsched_destroy.argtypes = [ctypes.c_void_p]
    lib.qsched_add_op.restype = ctypes.c_int
    lib.qsched_add_op.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int]
    lib.qsched_compile.restype = ctypes.c_int
    lib.qsched_compile.argtypes = [ctypes.c_void_p] + [ctypes.c_int] * 5
    lib.qsched_error.restype = ctypes.c_char_p
    lib.qsched_error.argtypes = [ctypes.c_void_p]
    lib.qsched_num_fused.restype = ctypes.c_int
    lib.qsched_num_fused.argtypes = [ctypes.c_void_p]
    lib.qsched_fused_info.restype = ctypes.c_int
    lib.qsched_fused_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int)]
    lib.qsched_fused_targets.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.qsched_fused_data.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_double)]
    lib.qsched_num_items.restype = ctypes.c_int
    lib.qsched_num_items.argtypes = [ctypes.c_void_p]
    lib.qsched_num_relayouts.restype = ctypes.c_int
    lib.qsched_num_relayouts.argtypes = [ctypes.c_void_p]
    # communication-aware planner ABI (absent from pre-cost-model builds;
    # the mtime check rebuilds a stale .so, so absence only means the
    # source itself predates the feature)
    if hasattr(lib, "qsched_set_cost_model"):
        lib.qsched_set_cost_model.restype = None
        lib.qsched_set_cost_model.argtypes = [
            ctypes.c_void_p, ctypes.c_double, ctypes.c_double,
            ctypes.c_double]
        for name in ("qsched_num_xshard", "qsched_num_swaps_absorbed",
                     "qsched_num_fused_collectives"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "qsched_set_cost_model2"):
        # two-tier multi-host ABI (absent from pre-pod-scale builds)
        lib.qsched_set_cost_model2.restype = None
        lib.qsched_set_cost_model2.argtypes = [
            ctypes.c_void_p, ctypes.c_double, ctypes.c_double,
            ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.c_int, ctypes.c_int]
    lib.qsched_item_info.restype = ctypes.c_int
    lib.qsched_item_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64)]
    lib.qsched_item_targets.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.qsched_item_perms.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def supports_cost_model() -> bool:
    """True when the loaded scheduler library exposes the
    communication-aware planner ABI (``qsched_set_cost_model``)."""
    lib = load()
    return lib is not None and hasattr(lib, "qsched_set_cost_model")


def supports_two_tier() -> bool:
    """True when the loaded scheduler library exposes the two-tier
    multi-host planner ABI (``qsched_set_cost_model2``)."""
    lib = load()
    return lib is not None and hasattr(lib, "qsched_set_cost_model2")


class NativeScheduler:
    """One scheduling session: feed ops, compile, read the schedule back.

    Speaks the compact descriptor protocol of the C ABI; the caller
    (quest_tpu.circuits) converts between `_Op` objects and descriptors.
    """

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("native scheduler unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.qsched_create())

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.qsched_destroy(h)
            self._h = None

    def add_op(self, kind: int, targets, ctrl_mask: int, flip_mask: int,
               data: Optional[np.ndarray], source_index: int) -> int:
        t = (ctypes.c_int * len(targets))(*targets)
        if data is not None:
            flat = np.ascontiguousarray(
                data, dtype=np.complex128).reshape(-1).view(np.float64)
            d = flat.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
        else:
            d = None
        return self._lib.qsched_add_op(
            self._h, kind, len(targets), t, ctrl_mask, flip_mask, d,
            source_index)

    def set_cost_model(self, alpha_s: float, beta_s_per_byte: float,
                       chunk_bytes: float,
                       inter_alpha_s=None, inter_beta_s_per_byte=None,
                       host_bits: int = 0, reorder: bool = True) -> None:
        """Enable the communication-aware planner (call before
        :meth:`compile`); parameters mirror
        :class:`quest_tpu.profiling.CommCostModel`. ``host_bits > 0``
        switches on the two-tier multi-host mode; ``reorder`` gates the
        hot-qubit eviction re-pairing there. At ``host_bits == 0`` the
        inter values are never consulted, so the single-tier ABI is used
        and pre-pod-scale libraries stay compatible."""
        two_tier = host_bits > 0
        if two_tier:
            if not hasattr(self._lib, "qsched_set_cost_model2"):
                raise RuntimeError(
                    "scheduler library predates the two-tier multi-host "
                    "ABI; rebuild native/src/scheduler.cc")
            self._lib.qsched_set_cost_model2(
                self._h, float(alpha_s), float(beta_s_per_byte),
                float(-1.0 if inter_alpha_s is None else inter_alpha_s),
                float(-1.0 if inter_beta_s_per_byte is None
                      else inter_beta_s_per_byte),
                float(chunk_bytes), int(host_bits), int(bool(reorder)))
            return
        if not hasattr(self._lib, "qsched_set_cost_model"):
            raise RuntimeError("scheduler library predates the cost-model "
                               "ABI; rebuild native/src/scheduler.cc")
        self._lib.qsched_set_cost_model(self._h, float(alpha_s),
                                        float(beta_s_per_byte),
                                        float(chunk_bytes))

    def compile(self, num_qubits: int, shard_bits: int, lookahead: int,
                fusion: bool, diag_row_cap: int = -1) -> None:
        rc = self._lib.qsched_compile(self._h, num_qubits, shard_bits,
                                      lookahead, int(fusion),
                                      int(diag_row_cap))
        if rc != 0:
            raise ValueError(self._lib.qsched_error(self._h).decode())

    # -- schedule readback -------------------------------------------------

    def fused_ops(self):
        """Yield (kind, targets, ctrl_mask, flip_mask, data, source_index)."""
        out = []
        for idx in range(self._lib.qsched_num_fused(self._h)):
            nt = ctypes.c_int()
            cm = ctypes.c_int64()
            fm = ctypes.c_int64()
            si = ctypes.c_int()
            kind = self._lib.qsched_fused_info(
                self._h, idx, ctypes.byref(nt), ctypes.byref(cm),
                ctypes.byref(fm), ctypes.byref(si))
            targets = (ctypes.c_int * nt.value)()
            self._lib.qsched_fused_targets(self._h, idx, targets)
            data = None
            if kind in (KIND_U, KIND_DIAG):
                count = (1 << nt.value) ** 2 if kind == KIND_U else 1 << nt.value
                buf = np.empty(2 * count, dtype=np.float64)
                self._lib.qsched_fused_data(
                    self._h, idx,
                    buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
                data = buf.view(np.complex128)
                if kind == KIND_U:
                    data = data.reshape(1 << nt.value, 1 << nt.value)
                else:
                    data = data.reshape((2,) * nt.value)
            out.append((kind, tuple(targets), cm.value, fm.value, data,
                        si.value))
        return out

    def items(self, num_qubits: int):
        """Yield plan items in quest_tpu.parallel.layout format."""
        out = []
        for i in range(self._lib.qsched_num_items(self._h)):
            oi = ctypes.c_int()
            nt = ctypes.c_int()
            cm = ctypes.c_int64()
            fm = ctypes.c_int64()
            kind = self._lib.qsched_item_info(
                self._h, i, ctypes.byref(oi), ctypes.byref(nt),
                ctypes.byref(cm), ctypes.byref(fm))
            if kind == 1:
                before = (ctypes.c_int * num_qubits)()
                after = (ctypes.c_int * num_qubits)()
                self._lib.qsched_item_perms(self._h, i, before, after)
                out.append(("relayout", np.array(before, dtype=np.int64),
                            np.array(after, dtype=np.int64)))
            elif kind == 2:
                targets = (ctypes.c_int * nt.value)()
                axis_order = (ctypes.c_int * nt.value)()
                self._lib.qsched_item_targets(self._h, i, targets, axis_order)
                out.append(("xshard", oi.value, tuple(targets), cm.value,
                            fm.value, None))
            else:
                targets = (ctypes.c_int * nt.value)()
                axis_order = (ctypes.c_int * nt.value)()
                self._lib.qsched_item_targets(self._h, i, targets, axis_order)
                out.append(("op", oi.value, tuple(targets), cm.value,
                            fm.value, tuple(axis_order)))
        return out

    def num_relayouts(self) -> int:
        return self._lib.qsched_num_relayouts(self._h)

    def num_xshard(self) -> int:
        if not hasattr(self._lib, "qsched_num_xshard"):
            return 0
        return self._lib.qsched_num_xshard(self._h)

    def num_swaps_absorbed(self) -> int:
        if not hasattr(self._lib, "qsched_num_swaps_absorbed"):
            return 0
        return self._lib.qsched_num_swaps_absorbed(self._h)

    def num_fused_collectives(self) -> int:
        if not hasattr(self._lib, "qsched_num_fused_collectives"):
            return 0
        return self._lib.qsched_num_fused_collectives(self._h)

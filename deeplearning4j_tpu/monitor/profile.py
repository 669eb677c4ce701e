"""Compiled-program performance observatory: per-program cost/memory profiles.

Every TFLOP/s and MFU figure the bench has ever printed came from a
hand-written analytic formula, and the per-shard HBM budget model
(``perf/epoch_cache.py``) has never been checked against what the compiler
actually allocates. This module closes both gaps at the source of truth —
the compiled XLA artifact:

- :class:`ProgramProfile` — one cached fused program's identity (model
  name + the ``(shuffle, K, guard, stride)`` cache key + the arg-shape
  signature) and its compiled-artifact numbers: ``cost_analysis()`` FLOPs
  and bytes-accessed, ``memory_analysis()`` argument/output/temp/alias/
  generated-code HBM (and the derived peak), and the lowering + compile
  wall times.
- :class:`ProfiledProgram` — the wrapper the ``_epoch_steps`` caches on
  both network classes and ``ParallelWrapper`` store. With
  ``DL4J_PROFILE`` off (the default) every call passes straight through
  to the wrapped ``jax.jit`` function: the executed program is the
  unwrapped program, bit for bit. With it on, the first call per
  arg-shape signature AOT-lowers and compiles the SAME function, harvests
  the profile, and runs the compiled executable from then on — exactly
  one compile per signature either way, so profiling changes WHEN the
  numbers are read, never WHAT runs.
- :func:`capture_program_profile` — the one-shot harvest for programs
  outside the epoch caches (``bench.py`` profiles the single-step and
  transformer programs with it).
- :func:`classify_boundedness` — the cost model's step-time
  decomposition: optimal compute time (FLOPs / peak FLOP/s) vs optimal
  memory time (bytes accessed / peak HBM bandwidth) vs the measured step
  time; the gap above the optimum is dispatch/overhead, and the larger
  optimum names the section compute- or memory-bound.

Profiles land in a process-global :class:`ProfileStore` (``profiles()``)
and are mirrored into the :class:`MetricsRegistry` (``program_flops``,
``program_bytes_accessed``, ``program_peak_hbm_bytes`` gauges +
``program_compile_seconds`` histogram, labeled by program/key) so every
exporter — and every bench artifact, including error-path partial flushes
— carries them beside the spans.

Profile collection is a HOST-side readback (compile introspection,
device ``memory_stats``). It is only permitted at chunk boundaries —
dl4j-lint's host-sync rule flags any profile-collection call reachable
from a hot path (see ``analysis/rules.py`` ``PROFILE_READBACK_CALLS``).

This module is stdlib-only at import (jax loads lazily inside the
capture paths) so ``deeplearning4j_tpu.monitor`` stays importable before
— or without — a backend.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

__all__ = [
    "ProgramProfile",
    "ProfileStore",
    "ProfiledProgram",
    "capture_program_profile",
    "classify_boundedness",
    "flops_divergence_pct",
    "profile_enabled",
    "profiles",
]

_ON = ("1", "on", "true", "yes")


def profile_enabled() -> bool:
    """``DL4J_PROFILE``: ``on`` captures a :class:`ProgramProfile` for
    every cached fused program (AOT lower + compile on first call per
    signature) and samples HBM watermarks at chunk boundaries. Default
    OFF — the fused program and its call path are the unwrapped
    ``jax.jit`` program, bit for bit."""
    return os.environ.get("DL4J_PROFILE", "").strip().lower() in _ON


class ProgramProfile:
    """One compiled program's cost/memory analysis + compile timing."""

    __slots__ = ("name", "key", "signature", "flops", "bytes_accessed",
                 "optimal_seconds", "argument_bytes", "output_bytes",
                 "temp_bytes", "alias_bytes", "generated_code_bytes",
                 "peak_bytes", "lower_s", "compile_s", "n_devices",
                 "error")

    def __init__(self, name: str, key: Any, signature: Any):
        self.name = name
        self.key = key
        self.signature = signature
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.optimal_seconds: Optional[float] = None
        self.argument_bytes: Optional[int] = None
        self.output_bytes: Optional[int] = None
        self.temp_bytes: Optional[int] = None
        self.alias_bytes: Optional[int] = None
        self.generated_code_bytes: Optional[int] = None
        self.peak_bytes: Optional[int] = None
        self.lower_s: Optional[float] = None
        self.compile_s: Optional[float] = None
        self.n_devices: Optional[int] = None
        self.error: Optional[str] = None

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "key": list(self.key) if isinstance(self.key, tuple)
            else self.key,
            "signature": str(self.signature),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "optimal_seconds": self.optimal_seconds,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "peak_bytes": self.peak_bytes,
            "lower_s": self.lower_s,
            "compile_s": self.compile_s,
            "n_devices": self.n_devices,
            "error": self.error,
        }

    def __repr__(self) -> str:
        return (f"ProgramProfile({self.name!r}, key={self.key}, "
                f"flops={self.flops}, peak_bytes={self.peak_bytes}, "
                f"compile_s={self.compile_s})")


class ProfileStore:
    """Thread-safe collection of captured profiles (process-global via
    ``profiles()``; tests construct private stores)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._profiles: List[ProgramProfile] = []

    def add(self, profile: ProgramProfile) -> None:
        with self._lock:
            self._profiles.append(profile)

    def all(self) -> List[ProgramProfile]:
        with self._lock:
            return list(self._profiles)

    def find(self, name: Optional[str] = None,
             key: Optional[Any] = None) -> List[ProgramProfile]:
        return [p for p in self.all()
                if (name is None or p.name == name)
                and (key is None or p.key == key)]

    def snapshot(self) -> List[dict]:
        """JSON-ready list — the ``extras["profile"]["programs"]`` block
        bench artifacts (and their error-path partial flushes) embed."""
        return [p.to_dict() for p in self.all()]

    def reset(self) -> None:
        with self._lock:
            self._profiles.clear()


_STORE: Optional[ProfileStore] = None
_STORE_LOCK = threading.Lock()


def profiles() -> ProfileStore:
    """The process-global profile store every capture lands in."""
    global _STORE
    if _STORE is None:
        with _STORE_LOCK:
            if _STORE is None:
                _STORE = ProfileStore()
    return _STORE


# ---------------------------------------------------------------------------
# harvest helpers
# ---------------------------------------------------------------------------


def _signature_of(args) -> Tuple:
    """Hashable (shape, dtype) tuple over the arg pytree's leaves — the
    per-compilation identity a jitted function re-specializes on."""
    import jax

    return tuple(
        (tuple(getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)))
        for leaf in jax.tree_util.tree_leaves(args))


def _harvest_cost(compiled, profile: ProgramProfile) -> None:
    """``compiled.cost_analysis()`` → FLOPs / bytes-accessed / optimal
    seconds (missing keys stay None)."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # backend without cost analysis
        profile.error = f"cost_analysis: {type(e).__name__}: {e}"[:200]
        return
    if not ca:
        return
    profile.flops = _maybe_float(ca.get("flops"))
    profile.bytes_accessed = _maybe_float(ca.get("bytes accessed"))
    profile.optimal_seconds = _maybe_float(ca.get("optimal_seconds"))


def _harvest_memory(compiled, profile: ProgramProfile) -> None:
    """``compiled.memory_analysis()`` → argument/output/temp/alias/code
    bytes and the derived peak: arguments + outputs + temporaries +
    generated code, minus aliased (donated) buffers, which XLA reuses
    in place — a conservative model of the program's HBM high-water."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        profile.error = f"memory_analysis: {type(e).__name__}: {e}"[:200]
        return
    if ma is None:
        return
    profile.argument_bytes = _maybe_int(
        getattr(ma, "argument_size_in_bytes", None))
    profile.output_bytes = _maybe_int(
        getattr(ma, "output_size_in_bytes", None))
    profile.temp_bytes = _maybe_int(
        getattr(ma, "temp_size_in_bytes", None))
    profile.alias_bytes = _maybe_int(
        getattr(ma, "alias_size_in_bytes", None))
    profile.generated_code_bytes = _maybe_int(
        getattr(ma, "generated_code_size_in_bytes", None))
    parts = [profile.argument_bytes, profile.output_bytes,
             profile.temp_bytes, profile.generated_code_bytes]
    if any(p is not None for p in parts):
        peak = sum(p or 0 for p in parts) - (profile.alias_bytes or 0)
        profile.peak_bytes = max(0, peak)


def _maybe_float(v) -> Optional[float]:
    try:
        return None if v is None else float(v)
    except (TypeError, ValueError):
        return None


def _maybe_int(v) -> Optional[int]:
    try:
        return None if v is None else int(v)
    except (TypeError, ValueError):
        return None


def _register(profile: ProgramProfile) -> None:
    """Mirror the profile into the global MetricsRegistry so exporters
    (JSONL, Prometheus, the bench telemetry block) see it beside spans."""
    from deeplearning4j_tpu.monitor import record_counter
    from deeplearning4j_tpu.monitor.registry import metrics

    reg = metrics()
    labels = {"program": profile.name, "key": str(profile.key)}
    if profile.flops is not None:
        reg.gauge("program_flops",
                  "cost-analysis FLOPs per program execution").set(
            profile.flops, **labels)
    if profile.bytes_accessed is not None:
        reg.gauge("program_bytes_accessed",
                  "cost-analysis bytes accessed per execution").set(
            profile.bytes_accessed, **labels)
    if profile.peak_bytes is not None:
        reg.gauge("program_peak_hbm_bytes",
                  "memory-analysis peak (arg+out+temp+code-alias)").set(
            profile.peak_bytes, **labels)
    if profile.compile_s is not None:
        reg.histogram("program_compile_seconds",
                      "XLA compile wall time per profiled program"
                      ).observe(profile.compile_s, program=profile.name)
    record_counter("program_profiles_total", program=profile.name,
                   outcome="error" if profile.error else "ok")


def capture_program_profile(fn, args, *, name: str, key: Any = (),
                            store: Optional[ProfileStore] = None):
    """AOT-lower and compile jitted ``fn`` on ``args``, harvest its
    cost/memory analysis and compile timing, register the profile, and
    return ``(profile, compiled)``. ``lower`` only reads the args'
    avals — donated buffers are NOT consumed; only executing the
    returned ``compiled`` does that. Runs inside a ``profile.capture``
    span (compile-cache visibility: the wall times land on the
    timeline)."""
    from deeplearning4j_tpu.monitor import tracer

    profile = ProgramProfile(name, key, _signature_of(args))
    with tracer().span("profile.capture", program=name,
                       key=str(key)) as sp:
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        profile.lower_s = round(time.perf_counter() - t0, 6)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        profile.compile_s = round(time.perf_counter() - t1, 6)
        _harvest_cost(compiled, profile)
        _harvest_memory(compiled, profile)
        try:
            import jax

            profile.n_devices = len(jax.devices())
        except Exception:
            pass
        sp.attrs.update(flops=profile.flops,
                        peak_bytes=profile.peak_bytes,
                        compile_s=profile.compile_s)
    (store if store is not None else profiles()).add(profile)
    _register(profile)
    return profile, compiled


class ProfiledProgram:
    """The ``_epoch_steps`` cache entry: a jitted fused program plus its
    observatory.

    Transparent by construction: attribute access (``lower``, ``trace``
    — the program-contract checker's surface) delegates to the wrapped
    jit function, tracer-valued calls (``jax.eval_shape`` /
    ``make_jaxpr`` re-tracing) pass straight through, and with
    ``DL4J_PROFILE`` off so does every execution. With it on, the first
    call per arg-shape signature compiles via the AOT path (one compile,
    same program) and captures the :class:`ProgramProfile`; later calls
    run the cached executable. A capture failure logs once and falls
    back to the plain jit path — profiling must never kill training."""

    def __init__(self, fn, *, name: str, key: Any):
        self._fn = fn
        self.name = name
        self.key = key
        self._compiled: Dict[Tuple, Any] = {}
        self.profiles: List[ProgramProfile] = []

    def __call__(self, *args):
        if not profile_enabled():
            return self._fn(*args)
        import jax

        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(args)):
            return self._fn(*args)  # being re-traced, not executed
        sig = _signature_of(args)
        compiled = self._compiled.get(sig)
        if compiled is None:
            try:
                prof, compiled = capture_program_profile(
                    self._fn, args, name=self.name, key=self.key)
                self.profiles.append(prof)
            except Exception as e:
                logger.warning(
                    "profile capture for %s%s failed (%s); falling back "
                    "to the plain jit path", self.name, self.key, e)
                compiled = False
            self._compiled[sig] = compiled
        if compiled is False:
            return self._fn(*args)
        return compiled(*args)

    def __getattr__(self, attr):
        return getattr(self._fn, attr)

    def __repr__(self) -> str:
        return (f"ProfiledProgram({self.name!r}, key={self.key}, "
                f"profiles={len(self.profiles)})")


# ---------------------------------------------------------------------------
# the cost model's step-time decomposition
# ---------------------------------------------------------------------------


def classify_boundedness(flops: Optional[float],
                         bytes_accessed: Optional[float],
                         measured_s: Optional[float],
                         peak_flops_per_s: float,
                         peak_bytes_per_s: float) -> dict:
    """Decompose a measured step time against the compiled cost model.

    ``optimal_compute_s`` = FLOPs / peak FLOP/s and ``optimal_memory_s``
    = bytes accessed / peak HBM bandwidth are the two roofline floors;
    the larger one is the program's optimal device time and names it
    compute- or memory-bound. Whatever the measured step time spends
    ABOVE that optimum is dispatch/overhead wait (host launch, link,
    queueing) — the decomposition that tells a perf PR whether to chase
    kernels or dispatch."""
    out = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "measured_s": measured_s,
        "optimal_compute_s": None,
        "optimal_memory_s": None,
        "optimal_s": None,
        "dispatch_wait_s": None,
        "dispatch_wait_pct": None,
        "arithmetic_intensity": None,
        "bound": None,
    }
    if flops is not None and peak_flops_per_s > 0:
        out["optimal_compute_s"] = flops / peak_flops_per_s
    if bytes_accessed is not None and peak_bytes_per_s > 0:
        out["optimal_memory_s"] = bytes_accessed / peak_bytes_per_s
    if flops is not None and bytes_accessed:
        out["arithmetic_intensity"] = flops / bytes_accessed
    floors = [s for s in (out["optimal_compute_s"],
                          out["optimal_memory_s"]) if s is not None]
    if floors:
        out["optimal_s"] = max(floors)
        if (out["optimal_compute_s"] is not None
                and out["optimal_memory_s"] is not None):
            out["bound"] = ("compute"
                            if out["optimal_compute_s"]
                            >= out["optimal_memory_s"] else "memory")
        elif out["optimal_compute_s"] is not None:
            out["bound"] = "compute"
        else:
            out["bound"] = "memory"
    if measured_s is not None and out["optimal_s"] is not None:
        out["dispatch_wait_s"] = max(0.0, measured_s - out["optimal_s"])
        if measured_s > 0:
            out["dispatch_wait_pct"] = round(
                100.0 * out["dispatch_wait_s"] / measured_s, 2)
    return out


def flops_divergence_pct(analytic: Optional[float],
                         cost_analysis: Optional[float]
                         ) -> Optional[float]:
    """Signed divergence of the compiled cost-analysis FLOPs from the
    analytic formula, as a percentage of the analytic value (positive:
    the compiler counts MORE work than the formula). None when either
    side is missing or the analytic value is zero."""
    if not analytic or cost_analysis is None:
        return None
    return round(100.0 * (cost_analysis - analytic) / analytic, 2)

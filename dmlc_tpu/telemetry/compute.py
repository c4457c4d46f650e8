"""Compute observability: compile ledger, XLA cost/roofline, HBM.

The step ledger (PR 5) and request ledger (PR 12) decompose a step into
feed / collective / "device-compute residual" and a request into
queue / prefill / decode — but the residual itself was a black box.
This module opens it along three axes:

  * **compile ledger** — :func:`profiled_jit` wraps every ``jax.jit``
    entry the repo owns and takes over its compile cache through the
    AOT path (``trace().lower().compile()``): exact cache-hit vs. trace
    counting, each recompile attributed to the (shape, dtype) signature
    that triggered it, and a compile timed phase by phase with
    ``telemetry.span``: ``compute.compile`` over ``.trace`` (Python to
    a jaxpr), ``.lower`` (jaxpr to StableHLO) and ``.backend`` (XLA's
    compile, or the persistent cache's read, deserialise and load),
    then ``compute.first_call`` around a fresh executable's first
    launch.  Whether the persistent cache answered is JAX's own word
    (its ``/jax/compilation_cache/*`` monitoring events), never a
    guess from a duration.  Signature churn beyond a threshold inside
    a sliding window is a *recompile storm* — shipped to the tracker
    watchdog as the ``recompile_storm`` anomaly kind.
  * **cost/roofline ledger** — the first compile of a signature pulls
    the executable's XLA cost analysis (FLOPs, bytes accessed) for
    free; combined with the per-dtype peak-FLOPs / HBM-bandwidth
    table (:func:`telemetry.steps.detect_peaks`) this yields an
    analytic roofline per step: ``mfu``, ``membw_util`` and a
    ``bound=compute|memory`` verdict.
  * **device memory accounting** — per-device HBM live/peak/limit from
    ``Device.memory_stats()`` with a host-RSS fallback for backends
    (CPU) that report none, plus a headroom gauge future autoscaling /
    KV-quantization work gates on.

Everything here is dark-cheap: ``DMLC_COMPUTE_PROFILE=1`` (default)
costs counters and one dict lookup per jitted call; ``=0`` makes
:func:`profiled_jit` return the plain ``jax.jit`` object — zero
per-call overhead, no registry entries.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from ..base import DMLCError, get_env
from ..concurrency import make_lock
from . import core

__all__ = [
    "profiled_jit", "enabled", "sites",
    "roofline", "sample_hbm",
    "recompiles_total", "status", "report", "prometheus_text",
    "reset_compute",
]

logger = logging.getLogger("dmlc_tpu.telemetry")


def enabled() -> bool:
    """Compile/cost/HBM ledgers on (the dark-cheap default)."""
    return bool(get_env("DMLC_COMPUTE_PROFILE", True))


# ---------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------

_lock = make_lock("compute._lock")
_sites: Dict[str, "_ProfiledJit"] = {}


# str(dtype) dominated the per-call signature cost on large pytrees
# (hundreds of leaves × numpy dtype __str__ every dispatch); dtypes are
# a tiny closed set, so memoize the conversion.  The canonicalizing
# variant mirrors what jit traces on (x64 demotion: int64 and float32
# numpy inputs land on the same executable, so they must land on the
# same signature)
_dtype_strs: Dict = {}
_canon_dtype_strs: Dict = {}


def _dtype_str(dt) -> str:
    s = _dtype_strs.get(dt)
    if s is None:
        s = _dtype_strs[dt] = str(dt)
    return s


def _canon_dtype_str(dt) -> str:
    s = _canon_dtype_strs.get(dt)
    if s is None:
        from jax import dtypes as _jdt

        s = _canon_dtype_strs[dt] = str(_jdt.canonicalize_dtype(dt))
    return s


def _leaf_sig(av) -> Tuple:
    return (tuple(av.shape), _dtype_str(av.dtype),
            bool(getattr(av, "weak_type", False)))


def _sig_text(key) -> str:
    """Compact human-readable signature: what a recompile is
    attributed to in spans, logs and /compute."""
    parts = []
    for item in key:
        if isinstance(item, tuple) and len(item) == 2 \
                and isinstance(item[0], str) and item[0] == "static":
            parts.append(f"static:{item[1]!r:.40}")
        elif isinstance(item, tuple) and len(item) == 2:
            leaves = item[1]
            parts.append(",".join(
                f"{'x'.join(map(str, shp))}:{dt}" + ("w" if wk else "")
                for shp, dt, wk in leaves) or "()")
        else:  # pragma: no cover - defensive
            parts.append(repr(item)[:40])
    return ";".join(parts)


# JAX's own word on its persistent compilation cache: the events it
# records as a compile finds (or writes) an entry.  ``cache_misses``
# fires where an entry is WRITTEN, so a program the cache would not
# keep (under its size or compile-time floors, or no cache at all)
# fires neither: ``off``
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_cache_tls = threading.local()
_listening = False  # guarded by _lock


def _on_cache_event(event: str, **_kw) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is None:
        return
    core.inc("compute", "cache_hits" if kind == "hit" else "cache_misses")
    seen = getattr(_cache_tls, "seen", None)
    if seen is not None:  # a site's backend compile, on this thread
        seen.append(kind)


def _on_cache_duration(event: str, secs: float, **_kw) -> None:
    if event == _CACHE_RETRIEVAL:
        core.inc("compute", "cache_retrieval_secs", secs)


def _listen() -> None:
    """Register the cache listener once a process (JAX calls a listener
    on the thread that compiles): the process counters
    ``compute.cache_hits`` / ``cache_misses`` / ``cache_retrieval_secs``
    also count plain ``jax.jit`` sites."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring

    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_cache_duration)


@contextlib.contextmanager
def _timed(name: str, site: str, times: Dict[str, float]):
    """A compile-phase span, its length (the span's own) kept in
    ``times`` under the name's last part."""
    with core.span(name, stage="compute", args={"site": site}) as args:
        rec = core.current_span()
        yield args
    times[name.rsplit(".", 1)[-1]] = rec["dur"] * 1e-6


class _ProfiledJit:
    """A ``jax.jit`` wrapper that owns its compile cache.

    The wrapper keys on the canonicalized abstract values of the array
    arguments (shape, dtype, weak_type — exactly what jit traces on)
    plus the values of the static arguments, compiles each fresh
    signature once through the AOT path, and dispatches cache hits
    straight to the compiled executable.  A lowering or compile error
    is the program's own and is raised (compiling a second time through
    plain jit would only spend the same minutes on the same error); a
    CALL-time surprise (an unhashable static argument, a committed-
    sharding mismatch against the AOT executable) falls back to the
    plain jit call and is counted in ``aot_fallbacks``.
    """

    def __init__(self, fn, *, site: str, static_argnums=(),
                 max_signatures: Optional[int] = None, **jit_kwargs):
        import jax

        self._fn = fn
        self.site = str(site)
        self._static = tuple(int(i) for i in static_argnums)
        self._max_sigs = max_signatures
        if self._static:
            jit_kwargs = dict(jit_kwargs,
                              static_argnums=self._static)
        self._jit = jax.jit(fn, **jit_kwargs)
        self._lock = make_lock("_ProfiledJit._lock")
        self._cache: Dict[Any, Tuple] = {}
        self.traces = 0
        self.hits = 0
        self.recompiles = 0
        self.aot_fallbacks = 0
        # seconds by phase, each the sum of its spans' own lengths
        self._phase_secs = dict.fromkeys(
            ("trace", "lower", "backend", "first_call"), 0.0)
        # the persistent cache's answers to this site's backend compiles
        self.cache_hits = 0
        self.cache_misses = 0
        self.last_cost: Optional[Dict] = None
        self.last_signature: Optional[str] = None
        self._trace_times: deque = deque(maxlen=256)
        # identity memo for a REPEATED pytree argument: serving passes
        # the same params dict every call, and hashing its ~30 leaves
        # per step is pure dispatch tax.  One entry per argument
        # position, holding the object itself (so its id cannot be
        # reused) — and ONLY the latest one: a training loop hands in a
        # fresh params tree every step, and remembering more than the
        # last would pin every superseded copy of the weights in HBM.
        # Dict args only (an ndarray can be mutated in place, a params
        # pytree's leaf STRUCTURE cannot change shape without being a
        # new tree in practice)
        # dmlc-check: unguarded(benign race: GIL-atomic dict ops; strong ref defeats id reuse)
        self._arg_sig_memo: Dict[int, Tuple[Any, Any]] = {}
        with _lock:
            _sites[self.site] = self
        _listen()

    # -- signature ------------------------------------------------------
    def _signature(self, args) -> Tuple:
        import jax
        from jax.api_util import shaped_abstractify

        parts = []
        for i, a in enumerate(args):
            if i in self._static:
                parts.append(("static", a))
            elif isinstance(a, dict):
                memo = self._arg_sig_memo.get(i)
                if memo is not None and memo[0] is a:
                    parts.append(memo[1])
                    continue
                part = self._tree_sig(a)
                self._arg_sig_memo[i] = (a, part)
                parts.append(part)
            else:
                parts.append(self._tree_sig(a))
        return tuple(parts)

    def _tree_sig(self, a):
        import jax
        from jax.api_util import shaped_abstractify

        leaves, treedef = jax.tree_util.tree_flatten(a)
        sigs = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                # array-like fast path: shape/dtype/weak_type read
                # straight off the leaf — the hot-loop dispatch cost,
                # paid per leaf per call
                sigs.append((tuple(shape), _canon_dtype_str(dtype),
                             bool(getattr(leaf, "weak_type", False))))
            else:  # scalars etc: canonicalize like jit does
                sigs.append(_leaf_sig(shaped_abstractify(leaf)))
        return (treedef, tuple(sigs))

    # -- compile (cache miss) -------------------------------------------
    def _compile(self, key, args):
        """``(entry, fresh)``: the executable of a signature seen for
        the first time, compiled under ``compute.compile`` and its
        three children (a child of whatever span the caller has open: a
        request's prefill, where nothing warmed the program up)."""
        with self._lock:
            entry = self._cache.get(key)
            if entry is not None:  # raced another thread's compile
                self.hits += 1
                self.last_cost = entry[1]
                return entry, False
            if (self._max_sigs is not None
                    and len(self._cache) >= self._max_sigs):
                raise DMLCError(
                    f"jit site {self.site!r} hit its signature cap: "
                    f"{len(self._cache)} distinct compile signatures "
                    f"(new: {_sig_text(key)}) — every novel signature "
                    f"is a full XLA recompile; bucket the inputs or "
                    f"raise the cap")
            sig = _sig_text(key)
            times: Dict[str, float] = {}
            seen: list = []
            with core.span("compute.compile", stage="compute",
                           args={"site": self.site, "signature": sig,
                                 "trace": self.traces + 1}) as span_args:
                with _timed("compute.compile.trace", self.site, times):
                    traced = self._jit.trace(*args)
                with _timed("compute.compile.lower", self.site, times):
                    lowered = traced.lower()
                with _timed("compute.compile.backend", self.site,
                            times) as backend_args:
                    _cache_tls.seen = seen
                    try:
                        compiled = lowered.compile()
                    finally:
                        _cache_tls.seen = None
                    # the last word counts: a miss is reported where
                    # the entry is written, after the compile
                    cache = seen[-1] if seen else "off"
                    backend_args["cache"] = span_args["cache"] = cache
            for phase, secs in times.items():
                self._phase_secs[phase] += secs
            self.traces += 1
            n_recompiles = self.recompiles = self.traces - 1
            self._trace_times.append((time.time(), sig))
            self.cache_hits += seen.count("hit")
            self.cache_misses += seen.count("miss")
            self.last_signature = sig
            cost = _extract_cost(compiled)
            self.last_cost = cost
            entry = (compiled, cost)
            self._cache[key] = entry
        if n_recompiles:
            logger.info("compute: recompile #%d at site %s for "
                        "signature %s (%.3fs, cache %s)", n_recompiles,
                        self.site, sig, sum(times.values()), cache)
        return entry, True

    def _first_call(self, compiled, dyn):
        """The first launch of a fresh executable, on the host's clock
        (what a program pays once beyond its compile: the runtime's
        load, the first transfer of its constants)."""
        times: Dict[str, float] = {}
        with _timed("compute.first_call", self.site, times):
            out = compiled(*dyn)
        with self._lock:
            self._phase_secs["first_call"] += times["first_call"]
        return out

    # -- dispatch --------------------------------------------------------
    def __call__(self, *args):
        try:
            key = self._signature(args)
            hash(key)  # unhashable static args surface HERE, not below
        except Exception:  # noqa: BLE001 - unhashable static etc.
            with self._lock:
                self.aot_fallbacks += 1
            core.inc("compute", "aot_fallbacks")
            return self._jit(*args)
        fresh = False
        with self._lock:
            entry = self._cache.get(key)
            if entry is not None:
                self.hits += 1
                self.last_cost = entry[1]
        if entry is None:
            entry, fresh = self._compile(key, args)
        compiled, _cost = entry
        dyn = tuple(a for i, a in enumerate(args)
                    if i not in self._static)
        try:
            if fresh:
                return self._first_call(compiled, dyn)
            return compiled(*dyn)
        except Exception:  # noqa: BLE001 - e.g. committed-device mismatch
            with self._lock:
                self.aot_fallbacks += 1
            core.inc("compute", "aot_fallbacks")
            return self._jit(*args)

    # -- views -----------------------------------------------------------
    def stats(self) -> Dict:
        with self._lock:
            secs = self._phase_secs
            return {
                "traces": self.traces,
                "hits": self.hits,
                "recompiles": self.recompiles,
                "aot_fallbacks": self.aot_fallbacks,
                "compile_secs_total": round(
                    secs["trace"] + secs["lower"] + secs["backend"], 6),
                **{f"{phase}_secs_total": round(v, 6)
                   for phase, v in secs.items()},
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "signatures": len(self._cache),
                "last_signature": self.last_signature,
                "last_cost": dict(self.last_cost)
                if self.last_cost else None,
            }

    def recent_traces(self, window_s: float) -> int:
        now = time.time()
        with self._lock:
            return sum(1 for t, _ in self._trace_times
                       if now - t <= window_s)

    def reregister(self) -> None:
        """Take the site in the registry: after a test-time
        :func:`reset_compute` orphaned a long-lived wrapper (the
        serving engine caches its jitted programs process-wide), or
        from another wrapper of the same site (the engine's MHA and
        latent prefill programs share ``serving.prefill``; the engine
        built last runs one of them, and that one is the site)."""
        with _lock:
            _sites[self.site] = self


def _extract_cost(compiled) -> Optional[Dict]:
    """FLOPs / bytes-accessed from an executable's XLA cost analysis
    (a dict; keys a backend does not report are left out)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - optional backend feature
        return None
    if not isinstance(ca, dict):
        return None
    out = {}
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if isinstance(flops, (int, float)) and flops >= 0:
        out["flops"] = float(flops)
    if isinstance(nbytes, (int, float)) and nbytes >= 0:
        out["bytes_accessed"] = float(nbytes)
    return out or None


def profiled_jit(fn, *, site: str, static_argnums=(),
                 max_signatures: Optional[int] = None, **jit_kwargs):
    """``jax.jit`` with a compile ledger attached.

    With ``DMLC_COMPUTE_PROFILE=0`` this *is* ``jax.jit(fn, ...)`` —
    the returned object carries no wrapper, no registry entry and no
    per-call cost, which is what the zero-overhead acceptance test
    pins."""
    if not enabled():
        import jax

        if static_argnums:
            jit_kwargs = dict(jit_kwargs, static_argnums=static_argnums)
        return jax.jit(fn, **jit_kwargs)
    return _ProfiledJit(fn, site=site, static_argnums=static_argnums,
                        max_signatures=max_signatures, **jit_kwargs)


def sites() -> Dict[str, _ProfiledJit]:
    with _lock:
        return dict(_sites)


def recompiles_total() -> int:
    return sum(pj.stats()["recompiles"] for pj in sites().values())


# ---------------------------------------------------------------------------
# recompile storms
# ---------------------------------------------------------------------------

def _storm_params() -> Tuple[float, int]:
    return (get_env("DMLC_COMPUTE_STORM_WINDOW_S", 60.0),
            get_env("DMLC_COMPUTE_STORM_TRACES", 4))


def _storm_doc() -> Dict:
    """Sites whose compile rate inside the sliding window crossed the
    storm threshold.  Counted on *traces* (not recompiles) so a cold
    site churning through fresh signatures trips just as loudly as a
    warm one re-tracing."""
    window_s, threshold = _storm_params()
    hot = []
    for site, pj in sorted(sites().items()):
        n = pj.recent_traces(window_s)
        if n >= threshold:
            hot.append({"site": site, "traces_in_window": n})
    return {"active": bool(hot), "window_s": window_s,
            "threshold": threshold, "sites": hot}


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def roofline(flops: Optional[float], bytes_accessed: Optional[float],
             wall_s: float, peak_flops: Optional[float],
             peak_bw: Optional[float]) -> Dict:
    """Analytic roofline verdict for one measured interval.

    ``bound`` compares the kernel's arithmetic intensity (FLOPs per
    byte moved) against the machine balance (peak FLOP/s per peak
    byte/s): below the balance point the kernel cannot saturate the
    ALUs no matter how well it is scheduled — it is memory-bound."""
    out: Dict[str, Optional[float]] = {
        "flops": flops, "bytes_accessed": bytes_accessed,
        "intensity": None, "mfu": None, "membw_util": None,
        "bound": None,
    }
    if wall_s <= 0:
        return out
    if flops and bytes_accessed:
        out["intensity"] = flops / bytes_accessed
    if flops and peak_flops:
        out["mfu"] = flops / wall_s / peak_flops
    if bytes_accessed and peak_bw:
        out["membw_util"] = bytes_accessed / wall_s / peak_bw
    if out["intensity"] is not None and peak_flops and peak_bw:
        balance = peak_flops / peak_bw
        out["bound"] = "memory" if out["intensity"] < balance \
            else "compute"
    return out


# ---------------------------------------------------------------------------
# device memory (HBM) accounting
# ---------------------------------------------------------------------------

_hbm_lock = make_lock("compute._hbm_lock")
_last_hbm: Optional[Dict] = None


def _host_rss() -> Dict:
    """Host fallback when the backend reports no memory_stats (CPU):
    the process's live/peak RSS against total system memory — a proxy,
    flagged as such (``source=host_rss``), but enough that the gauges
    and the /compute schema never go dark on a dev box."""
    live = peak = limit = None
    try:
        import resource

        # ru_maxrss is KiB on linux
        peak = float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                     ) * 1024.0
    except Exception:  # noqa: BLE001 - non-posix
        pass
    try:
        with open("/proc/self/statm") as f:
            import os as _os

            live = float(f.read().split()[1]) * _os.sysconf("SC_PAGE_SIZE")
    except Exception:  # noqa: BLE001 - non-linux
        live = peak
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    limit = float(line.split()[1]) * 1024.0
                    break
    except Exception:  # noqa: BLE001 - non-linux
        pass
    return {"available": False, "source": "host_rss", "devices": [],
            "live_bytes": live, "peak_bytes": peak,
            "limit_bytes": limit,
            "headroom_bytes": (limit - live)
            if (limit is not None and live is not None) else None}


def sample_hbm(publish: bool = True) -> Dict:
    """One HBM sample across local devices (live/peak/limit/headroom).

    Returns the device view when ``memory_stats()`` works, the
    host-RSS proxy otherwise; optionally publishes the aggregate
    gauges (sum live, max per-device peak, min per-device headroom —
    the conservative reading for an admission decision)."""
    global _last_hbm
    doc: Optional[Dict] = None
    try:
        import jax

        devices = jax.local_devices()
        per_dev = []
        for d in devices:
            ms = d.memory_stats()
            if not isinstance(ms, dict):
                per_dev = []
                break
            live = ms.get("bytes_in_use")
            peak = ms.get("peak_bytes_in_use", live)
            limit = ms.get("bytes_limit")
            per_dev.append({
                "id": d.id, "kind": d.device_kind,
                "live_bytes": live, "peak_bytes": peak,
                "limit_bytes": limit,
                "headroom_bytes": (limit - live)
                if (limit is not None and live is not None) else None})
        if per_dev:
            lives = [d["live_bytes"] for d in per_dev
                     if d["live_bytes"] is not None]
            peaks = [d["peak_bytes"] for d in per_dev
                     if d["peak_bytes"] is not None]
            limits = [d["limit_bytes"] for d in per_dev
                      if d["limit_bytes"] is not None]
            heads = [d["headroom_bytes"] for d in per_dev
                     if d["headroom_bytes"] is not None]
            doc = {"available": True, "source": "device",
                   "devices": per_dev,
                   "live_bytes": sum(lives) if lives else None,
                   "peak_bytes": max(peaks) if peaks else None,
                   "limit_bytes": sum(limits) if limits else None,
                   "headroom_bytes": min(heads) if heads else None}
    except Exception:  # noqa: BLE001 - no jax / backend quirk
        doc = None
    if doc is None:
        doc = _host_rss()
    if publish:
        if doc.get("live_bytes") is not None:
            core.set_gauge("compute", "hbm_live_bytes",
                           float(doc["live_bytes"]))
        if doc.get("peak_bytes") is not None:
            core.set_gauge("compute", "hbm_peak_bytes",
                           float(doc["peak_bytes"]))
        if doc.get("headroom_bytes") is not None:
            core.set_gauge("compute", "hbm_headroom_bytes",
                           float(doc["headroom_bytes"]))
    with _hbm_lock:
        _last_hbm = doc
    return doc


# ---------------------------------------------------------------------------
# views: heartbeat status, /compute document, prometheus text
# ---------------------------------------------------------------------------

def status() -> Dict:
    """Small-scalar compute doc shipped with heartbeats (the watchdog's
    ``recompile_storm`` signal plus the headline gauges); empty when
    the profile is off or nothing was ever jitted through it."""
    if not enabled():
        return {}
    site_map = {s: pj.stats() for s, pj in sites().items()}
    if not site_map:
        return {}
    storm = _storm_doc()
    with _hbm_lock:
        hbm = _last_hbm
    out = {
        "traces": sum(st["traces"] for st in site_map.values()),
        "hits": sum(st["hits"] for st in site_map.values()),
        "recompiles": sum(st["recompiles"] for st in site_map.values()),
        "storm": storm,
    }
    if hbm:
        out["hbm_peak_bytes"] = hbm.get("peak_bytes")
        out["hbm_headroom_bytes"] = hbm.get("headroom_bytes")
    return out


def _step_roofline() -> Dict:
    """The step ledger's roofline view (peaks + latest verdict)."""
    from . import steps

    return steps.ledger().roofline_summary()


def report() -> Dict:
    """The ``GET /compute`` document."""
    site_map = {s: pj.stats() for s, pj in sorted(sites().items())}
    with _hbm_lock:
        hbm = _last_hbm
    return {
        "enabled": enabled(),
        "sites": site_map,
        "traces_total": sum(s["traces"] for s in site_map.values()),
        "cache_hits_total": sum(s["hits"] for s in site_map.values()),
        "recompiles_total": sum(s["recompiles"]
                                for s in site_map.values()),
        "aot_fallbacks_total": sum(s["aot_fallbacks"]
                                   for s in site_map.values()),
        "storm": _storm_doc(),
        "hbm": hbm if hbm is not None else sample_hbm(),
        "roofline": _step_roofline(),
    }


def prometheus_text() -> str:
    """Per-site compile-ledger families as labeled exposition text
    (the core registry cannot label, so these are hand-rendered the
    same way slo/anomaly surfaces are)."""
    site_map = {s: pj.stats() for s, pj in sorted(sites().items())}
    if not site_map:
        return ""
    fams = (
        ("dmlc_compute_recompiles_total", "counter",
         "XLA recompiles beyond the first trace, per jit site",
         "recompiles"),
        ("dmlc_compute_traces_total", "counter",
         "jit traces (compiles) per jit site", "traces"),
        ("dmlc_compute_cache_hits_total", "counter",
         "jit compile-cache hits per jit site", "hits"),
        ("dmlc_compute_site_compile_secs_total", "counter",
         "seconds compiling (trace + lower + backend) per jit site",
         "compile_secs_total"),
        ("dmlc_compute_site_trace_secs_total", "counter",
         "seconds tracing Python to a jaxpr per jit site",
         "trace_secs_total"),
        ("dmlc_compute_site_lower_secs_total", "counter",
         "seconds lowering a jaxpr to StableHLO per jit site",
         "lower_secs_total"),
        ("dmlc_compute_site_backend_secs_total", "counter",
         "seconds in XLA's compile, or the persistent cache's read and "
         "load, per jit site", "backend_secs_total"),
        ("dmlc_compute_site_first_call_secs_total", "counter",
         "host seconds of fresh executables' first launches per jit site",
         "first_call_secs_total"),
        ("dmlc_compute_site_persistent_cache_hits_total", "counter",
         "backend compiles the persistent cache answered, per jit site",
         "cache_hits"),
        ("dmlc_compute_site_persistent_cache_misses_total", "counter",
         "backend compiles written to the persistent cache, per jit site",
         "cache_misses"),
    )
    lines = []
    for fam, typ, help_txt, key in fams:
        lines.append(f"# HELP {fam} {help_txt}")
        lines.append(f"# TYPE {fam} {typ}")
        for site, st in site_map.items():
            lines.append(f'{fam}{{site="{site}"}} {st[key]}')
    return "\n".join(lines) + "\n"


def reset_compute() -> None:
    """Forget every ledger (tests / fresh bench runs)."""
    global _last_hbm
    with _lock:
        _sites.clear()
    with _hbm_lock:
        _last_hbm = None

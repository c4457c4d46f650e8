"""What every runner shares: a cell's files found by name, the device
and its peaks, the compile counter, the tracer, the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


class BenchFailure(Exception):
    """The run is not a result: exit non-zero, reason on stderr."""


def need(cond, msg):
    if not cond:
        raise BenchFailure(msg)


def log(msg):
    """Everything but the result line goes to stderr."""
    print(f"[bench +{time.monotonic() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


T0 = time.monotonic()


def _load(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list  # BENCHMARK.json entries, each with its "spec" file

    @property
    def kind(self):
        return self.config["kind"]

    @property
    def out_dir(self):
        return os.path.join(HERE, "out", self.name)


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def _spec_file(here: str, metric: str, kind: str) -> str:
    """``layer_metrics/<metric>.json``.  A per-layer metric names the
    one end-to-end metric it moves, so a reading that every kind of
    cell has exists once per kind in BENCHMARK.json, as
    ``<kind>_<reading>``: those names share ``<reading>.json``."""
    own = os.path.join(here, "layer_metrics", metric + ".json")
    if not os.path.exists(own) and metric.startswith(kind + "_"):
        return os.path.join(here, "layer_metrics",
                            metric[len(kind) + 1:] + ".json")
    return own


def load_cell(name: str, root: str = ROOT) -> Cell:
    """A cell is {name, config, traffic, chips, why} in BENCHMARK.json;
    its three kinds of files are found by those names."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    here = os.path.join(root, "benchmarks")
    cells = {w["name"]: w for w in bench["workloads"]}
    need(name in cells, f"no workload {name!r} in BENCHMARK.json; "
         f"it has {sorted(cells)}")
    w = cells[name]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == w["config"])
    config = _load(os.path.join(root, config_entry["file"]))
    traffic = _load(os.path.join(here, "traffic", w["traffic"] + ".json"))
    need(config["chips"] == w["chips"],
         f"cell {name} asks for {w['chips']} chips, its configuration "
         f"for {config['chips']}")
    need(traffic["kind"] == config["kind"],
         f"traffic {w['traffic']} is for {traffic['kind']} cells, "
         f"configuration {w['config']} is a {config['kind']} one")
    per_layer = []
    for m in bench["per_layer"]:
        if _applies(m, name):
            spec = _load(_spec_file(here, m["name"], config["kind"]))
            per_layer.append(dict(m, spec=spec))
    return Cell(name, w["chips"], config, w["traffic"], traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer)


def _merge(into: dict, over: dict) -> None:
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            _merge(into[key], value)
        else:
            into[key] = value


def apply_rehearsal(cell: Cell) -> None:
    """--rehearse: the toy sizes of rehearse.json over the cell's."""
    toy = _load(os.path.join(HERE, "rehearse.json"))
    _merge(cell.config, toy["config"])
    _merge(cell.traffic, toy["traffic"][cell.kind])


def runner_for(kind: str):
    """The runner of a configuration's ``kind`` is the module of that
    name under benchmarks/runners/."""
    return importlib.import_module(f"benchmarks.runners.{kind}")


def reference_for(config: dict):
    """A configuration's plain reference is the module it names, beside
    the benchmark's other files: ``mean_loss``, ``logits_at`` and
    ``Q_BLOCK`` as benchmarks/reference.py has them."""
    return importlib.import_module("benchmarks." + config["reference"])


def layer_reader(spec: dict):
    """``<name>.py`` beside the data file, or a reader of readers.py."""
    metric_name = spec["name"]
    own = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    if os.path.exists(own):
        mod_spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + metric_name.replace(".", "_"),
            own)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read
    from benchmarks import readers

    need(spec.get("reader") in readers.READERS,
         f"layer metric {metric_name}: unknown reader "
         f"{spec.get('reader')!r} and no {metric_name}.py")
    return readers.READERS[spec["reader"]]


def read_layer_metrics(cell: Cell, obs: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = layer_reader(m["spec"])(obs, m["spec"].get("params", {}))
        if value is None:
            log(f"layer metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---- device ----------------------------------------------------------

def claim_device(cell: Cell, rehearse: bool) -> dict:
    """Place the compile cache, see what JAX sees, refuse what the
    cell did not ask for.  Returns {"platform", "kind", "count",
    "peaks"}."""
    from dmlc_tpu.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    # every program goes to the cache, the sub-second ones too, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    log(f"devices: {info}; compile cache at {cache_dir}")
    need(info["count"] >= cell.chips,
         f"cell {cell.name} needs {cell.chips} chip(s), JAX reports "
         f"{info['count']}")
    if rehearse:
        peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    else:
        need(info["platform"] == "tpu",
             f"JAX found no accelerator (platform {info['platform']!r}, "
             f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); the "
             "benchmark has no CPU fallback")
        need(info["count"] == cell.chips,
             f"cell {cell.name} is defined on {cell.chips} chip(s), this "
             f"machine has {info['count']}")
        table = _load(os.path.join(HERE, "peaks.json"))
        need(info["kind"] in table,
             f"device kind {info['kind']!r} has no row in "
             "benchmarks/peaks.json; an unknown device is an error, "
             "not a default")
        peaks = table[info["kind"]]
    pinned = sorted(k for k in os.environ if k.startswith("DMLC_PEAK_"))
    need(not pinned, f"peaks pinned by the environment: {pinned}")
    return dict(info, peaks=peaks)


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip, or None where the backend
    does not say (the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts every program JAX compiles or loads from its cache, by
    listening to JAX's own monitoring events: the benchmark's count,
    which covers plain jax.jit call sites that profiled_jit's ledger
    does not."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.count = 0   # programs compiled or loaded
        self.misses = 0  # of them, not found in the persistent cache
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_miss)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1

    def _on_miss(self, event, **kwargs):
        if event == self.MISS:
            self.misses += 1


def program_state() -> dict:
    """The program's counters and profiled_jit sites, right now."""
    from dmlc_tpu import telemetry
    from dmlc_tpu.telemetry import compute

    from benchmarks import measure

    return {"counters": measure.flatten(
                "counters", telemetry.counters_snapshot(), {}),
            "sites": {name: site.stats()
                      for name, site in compute.sites().items()}}


def observations(cell: Cell, device: dict, facts: dict, before: dict,
                 after: dict, compiles_in_window: int, reduction,
                 responses=None) -> dict:
    """What the per-layer readers get (readers.py describes it), after
    refusing a window in which any program compiled."""
    from benchmarks import measure

    need(compiles_in_window == 0 and all(
        stats["traces"] == before["sites"].get(site, {}).get("traces", 0)
        for site, stats in after["sites"].items()),
        f"{compiles_in_window} program(s) compiled inside the window: "
        "not a result")
    numbers = measure.flatten(
        "facts", dict(facts, compiles_in_window=compiles_in_window), {})
    numbers.update(measure.delta(after["counters"], before["counters"]))
    numbers["device.count"] = float(cell.chips)
    peak = memory_peak_bytes()
    if peak is not None:
        numbers["device.memory_peak_bytes"] = float(peak)
    if reduction is not None:
        numbers["trace.window_s"] = reduction.window_s
        numbers["trace.busy_s"] = reduction.busy_s
    return {"numbers": numbers,
            "sites": {"open": before["sites"], "close": after["sites"]},
            "responses": responses, "reduction": reduction,
            "model": cell.config["model"], "traffic": cell.traffic,
            "config": cell.config, "peaks": device["peaks"]}


def check_program_health(state: dict, rehearse: bool) -> None:
    """A chip run that reached a lax or interpreted kernel, fell back
    from an AOT program or requeued after a crash is not a result."""
    c = state["counters"]
    fallbacks = sum(s["aot_fallbacks"] for s in state["sites"].values())
    need(fallbacks == 0, f"{fallbacks} AOT fallback(s): {state['sites']}")
    need(c.get("counters.serving.crash_requeues", 0) == 0,
         "the engine requeued requests after a crashed iteration")
    if not rehearse:
        need(c.get("counters.kernels.lax_traces", 0) == 0
             and c.get("counters.kernels.interpret_traces", 0) == 0,
             "a chip run reached a lax or interpreted kernel: "
             f"{ {k: v for k, v in c.items() if 'kernels' in k} }")
        need(c.get("counters.kernels.mosaic_traces", 0) > 0,
             "no Mosaic kernel was traced")


# ---- tracing ---------------------------------------------------------

def annotate(name: str):
    """The benchmark's own host span, in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(out_dir: str):
    """Profile the block into ``out_dir`` (host TraceMe events, no
    Python call tracing: it would slow the host it observes).  Yields a
    dict that holds the trace file's path after the block."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    found = {}
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        with annotate("bench.trace_window"):
            yield found
    finally:
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            out_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        found["path"] = files[-1] if files else None


def reduce_traced_window(path: str, platform: str):
    """The reduction of the ``bench.trace_window`` span of a trace."""
    from benchmarks import reduce_trace

    ops = reduce_trace.TPU_OPS if platform == "tpu" \
        else reduce_trace.CPU_OPS  # a rehearsal
    trace = reduce_trace.load_xplane(path, ops)
    window = trace.span("bench.trace_window")
    need(window is not None, "the trace holds no bench.trace_window span")
    need(trace.devices, f"the trace holds no ops line matching {ops}")
    return reduce_trace.Reduction(trace, window.start, window.end)


# ---- the result line -------------------------------------------------

def result_line(device: dict, *, correct, attempted, failed, metrics,
                reduction=None) -> str:
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": memory_peak_bytes()}
    doc = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": dev}
    if reduction is not None:
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        doc["breakdown"] = reduction.breakdown()
    return json.dumps(doc)

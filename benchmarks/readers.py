"""Per-layer metric readers.  A metric is a data file,
``benchmarks/layer_metrics/<name>.json``: ``{"reader": <one of READERS>,
"params": {...}}``; where no reader here can express it, a
``<name>.py`` beside the file defines ``read(obs, params)`` instead.
A reader that finds nothing to read returns None, and the harness
leaves the metric out of the line.

``obs`` is what one run observed:

  obs["numbers"]    flat {name: number}: ``facts.*`` (the runner's own
                    clock and counts), ``counters.<stage>.<name>`` (the
                    program's counters, growth over the window),
                    ``device.*``, ``peaks.*`` and, in a traced run,
                    ``trace.*``
  obs["sites"]      {"open": {site: stats}, "close": {site: stats}}: the
                    program's profiled_jit sites at the window's edges
  obs["responses"]  the requests answered inside the window (serve)
  obs["reduction"]  reduce_trace.Reduction of the traced window, or None
  obs["model"], obs["traffic"], obs["config"], obs["peaks"]
"""

from __future__ import annotations

from benchmarks import costs, measure, reduce_trace


def _term(numbers, term):
    if isinstance(term, (int, float)):
        return float(term)
    sign = -1.0 if term.startswith("-") else 1.0
    name = term.lstrip("-")
    if name not in numbers:
        return None
    return sign * numbers[name]


def _sum(numbers, terms):
    values = [_term(numbers, t) for t in terms]
    return None if any(v is None for v in values) else sum(values)


def ratio(obs, params):
    """scale x sum(num) / sum(den); terms are names in obs["numbers"]
    (a leading "-" subtracts) or constants."""
    num = _sum(obs["numbers"], params["num"])
    den = _sum(obs["numbers"], params.get("den", [1.0]))
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den


def site_stat(obs, params):
    """A profiled_jit statistic summed over the program's sites: its
    value at the window's opening (``at: open``), or its growth over
    the window (``at: window``)."""
    field, at = params["field"], params["at"]
    def total(edge):
        return float(sum(s.get(field, 0) for s in obs["sites"][edge].values()))
    if not obs["sites"]["close"]:
        return None
    return total("open") if at == "open" else total("close") - total("open")


def response_percentile(obs, params):
    """Nearest-rank percentile of a field of the answers."""
    values = [r[params["field"]] for r in obs.get("responses") or []
              if r.get(params["field"]) is not None]
    return measure.percentile(values, params["q"]) * params.get(
        "scale", 1.0) if values else None


def utilization(obs, params):
    """100 x rate x cost per unit / (chips x peak): an end-to-end
    utilisation, not a roofline share."""
    rate = obs["numbers"].get(params["rate"])
    if rate is None:
        return None
    per_unit = getattr(costs, params["cost_fn"])(
        obs["model"], obs["traffic"]["T"])
    peak = obs["peaks"][params["peak"]] * obs["numbers"]["device.count"]
    return 100.0 * rate * per_unit / peak


def trace_share(obs, params):
    """100 x self time of the ops matching ``patterns`` over the traced
    window's busy time (``over: busy``) or length (``over: window``)."""
    red = obs.get("reduction")
    if red is None:
        return None
    match = reduce_trace.matcher(params["patterns"],
                                 params.get("field", "any"))
    base = red.busy_s if params.get("over", "busy") == "busy" \
        else red.window_s
    return 100.0 * red.seconds(match) / base if base else None


def trace_idle_during(obs, params):
    """100 x the device's idle time that falls inside host spans whose
    name matches ``patterns`` (the benchmark's TraceAnnotations), over
    the traced window: how long the device waited for that host work."""
    red = obs.get("reduction")
    if red is None:
        return None
    match = reduce_trace.matcher(params["patterns"], "name")
    return 100.0 * red.idle_seconds_during(match) / red.window_s


def kernel_roofline(obs, params):
    """100 x the least time the chip could take for the kernel's calls
    in the traced window over the time they took.  ``cost_fn`` names a
    function of costs.py and says whether its cost is per train step or
    per call; which peak bounds it goes to obs["notes"]."""
    red = obs.get("reduction")
    if red is None:
        return None
    match = reduce_trace.matcher(params["patterns"],
                                 params.get("field", "any"))
    took = red.seconds(match)
    if not took:
        return None
    fn = getattr(costs, params["cost_fn"])
    if params.get("args") == "context":
        per_step = obs["numbers"].get("facts.ctx_tokens_per_decode_step")
        if per_step is None:
            return None
        cost = fn(obs["model"], per_step)
    else:
        cost = fn(obs["model"], obs["traffic"], obs["config"])
    least, bound = costs.min_seconds(cost, obs["peaks"])
    units = (red.count(match) if cost["per"] == "call"
             else obs["numbers"].get("facts.trace_steps"))
    if not units:
        return None
    obs.setdefault("notes", {})[params["cost_fn"]] = f"{bound}-bound"
    return 100.0 * units * least / took


READERS = {f.__name__: f for f in (
    ratio, site_stat, response_percentile, utilization, trace_share,
    trace_idle_during, kernel_roofline)}

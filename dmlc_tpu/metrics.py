"""Back-compatible shim over :mod:`dmlc_tpu.telemetry` (SURVEY.md §5).

This module used to own the flat per-stage counters; the telemetry
package subsumed it (histograms with percentiles, span tracing,
exporters, cluster aggregation — see ``dmlc_tpu/telemetry/``).  Existing
call sites (io/input_split.py, feed/device_feed.py,
models/transformer.py, data/parser.py, bench.py, examples) keep
working unchanged:

  * ``inc`` / ``timed`` / ``annotate`` delegate directly
    (``timed`` additionally feeds a histogram now — free distributions
    for every previously flat ``<name>_secs`` counter);
  * ``snapshot()`` returns the legacy flat ``{stage: {name: value}}``
    counter view (``telemetry.snapshot()`` has the structured one);
  * ``reset()`` clears the whole telemetry registry (test isolation).
"""

from __future__ import annotations

from typing import Dict

from . import telemetry

__all__ = ["inc", "timed", "snapshot", "reset", "annotate"]

inc = telemetry.inc
timed = telemetry.timed
annotate = telemetry.annotate
reset = telemetry.reset


def snapshot() -> Dict[str, Dict[str, float]]:
    """Point-in-time copy of every stage's flat counters (legacy shape)."""
    return telemetry.counters_snapshot()

"""Metric-name contract pass — the absorbed ``scripts/lint.py``
cross-file check.

Every metric family literal telemetry call sites can emit
(``telemetry.inc("stage", "name")`` -> ``dmlc_<stage>_<name>``; a
``telemetry.span("stage.a.b", stage="stage")`` -> the pair
``dmlc_<stage>_a_b_secs`` / ``dmlc_<stage>_a_b_count``), plus
every literal ``dmlc_*`` token anywhere (scrape assertions,
hand-rendered families), must be registered in
``dmlc_tpu/telemetry/metric_names.py`` — the MIGRATION.md "no renames,
additive only" promise, enforced.  Check id: ``metric-name``.
"""

from __future__ import annotations

import ast
import os
import re
from typing import List

from .core import Finding, Pass, RepoIndex

# roots whose telemetry call sites define REAL metric families; tests
# register throwaway stages ("stage", "smoke") that are not contract
METRIC_ROOTS = ("dmlc_tpu", "scripts", "examples", "bench.py")
_METRIC_FUNCS = {"inc", "set_gauge", "observe", "observe_duration",
                 "timed"}
_SPAN_FUNCS = {"span", "_span"}
_METRIC_TOKEN_RE = re.compile(r"dmlc_[a-z0-9]+(?:_[a-z0-9]+)*")
_METRIC_SUFFIXES = ("_bucket", "_sum", "_count", "_total")


def _registry():
    from ..telemetry import metric_names

    return metric_names


def _literal(node):
    return (node.value if isinstance(node, ast.Constant)
            and isinstance(node.value, str) else None)


def _span_families(node: ast.Call, fname: str) -> List[str]:
    """The counter pair a literal ``span(name, stage=...)`` call site
    feeds (telemetry.core.span_counter_suffix's rule); the serving
    engine's ``self._span(name)`` helper is stage ``serving``."""
    name = _literal(node.args[0]) if node.args else None
    if name is None:
        return []
    stage = "serving" if fname == "_span" else "dmlc"
    if fname == "span":
        if len(node.args) > 1:
            stage = _literal(node.args[1])
        for kw in node.keywords:
            if kw.arg == "stage":
                stage = _literal(kw.value)
    if stage is None:
        return []
    from ..telemetry.core import span_counter_suffix

    base = f"dmlc_{stage}_{span_counter_suffix(name, stage)}"
    return [base + "_secs", base + "_count"]


def _is_registered(token: str, known: set) -> bool:
    if token in known:
        return True
    for suf in _METRIC_SUFFIXES:
        if token.endswith(suf) and token[: -len(suf)] in known:
            return True
    return False


class MetricsPass(Pass):
    name = "metrics"
    checks = ("metric-name",)

    def run(self, index: RepoIndex) -> List[Finding]:
        reg = _registry()
        known = (set(reg.METRIC_NAMES) | set(reg.SPAN_ANNOTATIONS)
                 | set(reg.NON_METRIC_TOKENS))
        registry_rel = os.path.join("dmlc_tpu", "telemetry",
                                    "metric_names.py")
        findings: List[Finding] = []
        for ctx in index.files:
            if ctx.rel == registry_rel:
                continue  # the registry trivially contains itself
            if ctx.tree is None:
                continue  # style pass reports the syntax error
            in_metric_root = any(
                ctx.rel == r or ctx.rel.startswith(r + os.sep)
                for r in METRIC_ROOTS)
            for node in ast.walk(ctx.tree):
                # derived families: telemetry.inc("stage", "name", ...)
                # with literal args resolve to dmlc_<stage>_<name>
                if in_metric_root and isinstance(node, ast.Call):
                    fn = node.func
                    fname = (fn.attr if isinstance(fn, ast.Attribute)
                             else fn.id if isinstance(fn, ast.Name)
                             else None)
                    args = node.args
                    if (fname in _METRIC_FUNCS and len(args) >= 2
                            and all(isinstance(a, ast.Constant)
                                    and isinstance(a.value, str)
                                    for a in args[:2])):
                        suffix = ("_secs" if fname in ("observe_duration",
                                                       "timed") else "")
                        name = (f"dmlc_{args[0].value}_"
                                f"{args[1].value}{suffix}")
                        if not _is_registered(name, known):
                            findings.append(Finding(
                                ctx.rel, node.lineno, "metric-name",
                                f"metric family {name!r} not in "
                                f"telemetry/metric_names.py (add it, or "
                                f"fix the typo'd stage/name)"))
                    if fname in _SPAN_FUNCS:
                        for name in _span_families(node, fname):
                            if name not in known:
                                findings.append(Finding(
                                    ctx.rel, node.lineno, "metric-name",
                                    f"span counter family {name!r} not "
                                    f"in telemetry/metric_names.py"))
                # literal names: scrape assertions, hand-rendered rows
                if (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)):
                    for token in _METRIC_TOKEN_RE.findall(node.value):
                        if not _is_registered(token, known):
                            findings.append(Finding(
                                ctx.rel, node.lineno, "metric-name",
                                f"dmlc_* token {token!r} not in "
                                f"telemetry/metric_names.py"))
        return findings

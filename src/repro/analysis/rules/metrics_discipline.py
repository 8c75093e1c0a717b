"""R6 metrics-discipline — metrics flow through the telemetry registry
and instrument drains stay out of traced regions.

Three checks:

  * **ad-hoc accumulators**: a module-level ``NAME = <number>`` that the
    same module mutates (an ``AugAssign`` target or a ``global``
    declaration) is a shadow metric — an unregistered, undocumented,
    unexported counter. Declare it through
    ``repro.telemetry.registry.REGISTRY`` (name + unit + doc, collisions
    rejected at import) and count it on an ``Instruments`` surface, or
    keep the state on an instance. Module-level numeric *constants*
    (assigned once, never mutated) are untouched.

  * **drains in traced regions**: ``.drain()`` / ``.event()`` calls
    (the ``Instruments``/``Telemetry`` sync points) inside a jit/scan
    body block on every bound device metric *per traced step* — the
    whole point of binding device accumulators is that they drain once
    per scheduler event, on the host control path.

  * **bare trace marks**: ``jax.named_scope`` or ``TraceAnnotation``
    outside ``repro.telemetry``. Device scopes and host phases are
    declared once in ``repro.telemetry.scopes`` (layer, doc, the metric
    that reads them) and entered through its ``scope()``/``phase()``, so a
    rename cannot silently drop a benchmark metric.

Waivers use the standard protocol: a
``# repro: allow(metrics-discipline): …`` comment on the finding line or
the line above, naming the budget/justification.
"""
from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.engine import (Finding, RepoContext, Rule, SourceFile,
                                   register_rule)
from repro.analysis.visitors import dotted, walk_calls

#: the instrument sync entry points (Instruments.drain/resolve,
#: Telemetry.event/finalize)
DRAIN_METHODS = {"drain", "event", "resolve", "finalize"}

#: trace marks that only ``repro.telemetry`` may create directly
MARK_NAMES = {"named_scope", "TraceAnnotation"}
MARKS_HOME = "src/repro/telemetry/"


def _module_numeric_assigns(tree: ast.Module) -> dict:
    """name -> assign node for top-level ``NAME = <int|float literal>``."""
    out = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, (int, float))
                and not isinstance(node.value.value, bool)):
            out[node.targets[0].id] = node
    return out


def _mutated_names(tree: ast.Module) -> set:
    """Names the module augments or declares ``global`` anywhere."""
    mutated = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.AugAssign)
                and isinstance(node.target, ast.Name)):
            mutated.add(node.target.id)
        elif isinstance(node, ast.Global):
            mutated.update(node.names)
    return mutated


def _mark_name(node: ast.AST):
    """``named_scope``/``TraceAnnotation`` when ``node`` names one: an
    attribute (``jax.named_scope``, ``jax.profiler.TraceAnnotation``) or
    an import of it."""
    if isinstance(node, ast.Attribute) and node.attr in MARK_NAMES:
        return dotted(node) or node.attr
    if isinstance(node, ast.ImportFrom):
        for a in node.names:
            if a.name in MARK_NAMES:
                return a.name
    return None


class MetricsDiscipline(Rule):
    name = "metrics-discipline"
    contract = ("every counter/gauge/histogram is declared through the "
                "telemetry registry; instrument drains stay out of "
                "traced regions; trace scopes and phases are declared in "
                "repro.telemetry.scopes")

    def check(self, sf: SourceFile, ctx: RepoContext) -> Iterator[Finding]:
        assigns = _module_numeric_assigns(sf.tree)
        if assigns:
            for name in sorted(_mutated_names(sf.tree) & set(assigns)):
                yield self.finding(
                    sf, assigns[name],
                    f"module-level accumulator '{name}' is an ad-hoc "
                    "metric (unregistered, undocumented, invisible to "
                    "exporters) — declare a counter/gauge through "
                    "repro.telemetry.registry.REGISTRY and count it on "
                    "an Instruments surface")
        if not sf.rel.startswith(MARKS_HOME):
            for node in ast.walk(sf.tree):
                name = _mark_name(node)
                if name is not None:
                    yield self.finding(
                        sf, node,
                        f"bare {name} — declare the scope or phase in "
                        "repro.telemetry.scopes.MARKS and enter it "
                        "through scope()/phase()")
        tm = sf.trace_map()
        for call in walk_calls(sf.tree):
            if tm.under_compile_time_eval(call):
                continue
            if not (isinstance(call.func, ast.Attribute)
                    and call.func.attr in DRAIN_METHODS):
                continue
            hit = tm.traced_region_of(call)
            if hit is not None:
                _, kind = hit
                yield self.finding(
                    sf, call,
                    f".{call.func.attr}() inside a {kind} body syncs "
                    "every bound device instrument per traced step — "
                    "drain once per scheduler event on the host control "
                    "path")


register_rule(MetricsDiscipline())

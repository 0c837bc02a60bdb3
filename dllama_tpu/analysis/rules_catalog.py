"""Single-site catalog discipline (``catalog-*`` rules).

The observability stack's whole design is "one definition site per
catalog": every metric family in ``obs/instruments.py``, every span/event
name in ``obs/trace.{SPAN,EVENT}_CATALOG``, every fault point in
``utils/faults.POINTS``. scripts/checks.sh keeps the README tables synced
to those catalogs; these rules close the other half of the loop — CODE
that registers or emits outside the catalog fails at the callsite with a
real location (the grep gates this replaces could only say "something,
somewhere"). The profiler's clock has ONE writer,
``obs/trace.profiler_annotation(prefix, leaf, args)``: a call of it is a
span emission whose literal prefix (``"dllama.sched."``) is its catalog
name; so is a call of the phase seam (``obs/perf.PhaseClock``, always
reached as ``phases(...)`` / ``self.phases(...)`` or a local ``ph``) with
a literal phase name.
"""

from __future__ import annotations

import ast

from dllama_tpu.analysis.core import Diagnostic, dotted, str_arg
from dllama_tpu.obs.trace import EVENT_CATALOG, SPAN_CATALOG
from dllama_tpu.utils.faults import POINTS

#: the only modules allowed to create metric families (metrics.py defines
#: the registry helpers themselves)
METRIC_SITES = ("dllama_tpu/obs/instruments.py", "dllama_tpu/obs/metrics.py")

_FACTORIES = {"counter", "gauge", "histogram"}

#: receivers whose .span/.span_at/.event calls are tracer emissions
_TRACER_BASES = {"tr", "tracer", "TRACER"}


def _is_metric_factory(call: ast.Call) -> bool:
    d = dotted(call.func)
    if d is None:
        return False
    parts = d.split(".")
    if parts[-1] not in _FACTORIES or len(parts) < 2:
        return False
    return parts[-2] in ("metrics", "REGISTRY") or parts[0] == "REGISTRY"


#: callables that open a span by a literal name: the profiler clock's one
#: writer and the phase seam
_SPAN_OPENERS = {"profiler_annotation", "phases", "ph"}


def _is_tracer_call(call: ast.Call, src_rel: str) -> str | None:
    """'span' | 'event' when the call is a tracer emission (a call of the
    profiler clock's writer or of the phase seam is a span)."""
    f = call.func
    if (f.id if isinstance(f, ast.Name) else
            f.attr if isinstance(f, ast.Attribute) else None) in _SPAN_OPENERS:
        return "span"
    if not isinstance(f, ast.Attribute):
        return None
    kind = {"span": "span", "span_at": "span", "event": "event"}.get(f.attr)
    if kind is None:
        return None
    base = dotted(f.value)
    if base is None:
        return None
    last = base.split(".")[-1]
    if last in _TRACER_BASES:
        return kind
    if base == "self" and src_rel == "dllama_tpu/obs/trace.py":
        return kind  # the tracer's own catalog-named emissions
    return None


def _emitted_name(call: ast.Call) -> str | None:
    """The literal name a tracer call emits (of the profiler clock's
    writer: the prefix, its first argument); of a ``"prefix." + x`` the
    constant head."""
    name = call.args[0] if call.args else None
    if isinstance(name, ast.BinOp):
        name = name.left
    if isinstance(name, ast.Constant) and isinstance(name.value, str):
        return name.value
    return None


def check(project) -> list[Diagnostic]:
    diags: list[Diagnostic] = []
    for src in project.py_sources("dllama_tpu/"):
        for node in ast.walk(src.tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr == "PROFILER_HOOK"
                    and isinstance(node.ctx, ast.Load)
                    and src.rel != "dllama_tpu/obs/trace.py"):
                diags.append(Diagnostic(
                    src.rel, node.lineno, "catalog-span",
                    "PROFILER_HOOK is read in ONE function, "
                    "obs/trace.profiler_annotation — open the annotation "
                    "through it (utils/profiling only installs the hook)"))
            if not isinstance(node, ast.Call):
                continue
            if _is_metric_factory(node) and src.rel not in METRIC_SITES:
                name = str_arg(node, 0)
                diags.append(Diagnostic(
                    src.rel, node.lineno, "catalog-metric",
                    f"metric family {name or '<dynamic>'!r} created outside "
                    "obs/instruments.py — the catalog (and its README "
                    "drift check) is the single registration site"))
            kind = _is_tracer_call(node, src.rel)
            if kind is not None:
                name = _emitted_name(node)
                if name is not None:
                    catalog = SPAN_CATALOG if kind == "span" \
                        else EVENT_CATALOG
                    if name not in catalog:
                        which = "SPAN_CATALOG" if kind == "span" \
                            else "EVENT_CATALOG"
                        diags.append(Diagnostic(
                            src.rel, node.lineno, f"catalog-{kind}",
                            f"{kind} name {name!r} is not in "
                            f"obs/trace.{which} — add the catalog row "
                            "(and its README entry) with the emit site"))
            d = dotted(node.func)
            if d in ("faults.fire", "faults.flag"):
                point = str_arg(node, 0)
                if point is not None and point not in POINTS:
                    diags.append(Diagnostic(
                        src.rel, node.lineno, "catalog-fault",
                        f"fault point {point!r} is not in "
                        "utils/faults.POINTS — an undeclared point can "
                        "never be armed, so the drill silently never "
                        "fires"))
    return diags

"""jit-dispatch discipline (rules ``jit-scope`` / ``jit-label``).

Every *dispatch* of a cached-jit callable in ``dllama_tpu/engine/`` must
be lexically inside a ``with LEDGER.scope(fn, key):`` bracket whose fn
label is an ``obs/compile.COMPILE_FNS`` literal — the compile ledger can
only attribute what the callsite scopes, and an unscoped dispatch is a
future "untracked compile mid-traffic" nobody can bill (the PR 12 ledger
catches that only when the path runs; this fails CI at the callsite).

What counts as a cached-jit callable (collected over all engine modules):

* ``self.X = jax.jit(...)`` / ``self.X = named_jit(fn, ...)`` attribute
  bindings (and ``@jax.jit``-decorated methods — called as ``self.X(...)``);
* ``self.X[...] = factory(...)`` where `factory` is an engine function
  whose body returns ``jax.jit(...)`` (the spec-decoder table);
* ``@jax.jit``-decorated module-level functions, including when imported
  into a sibling engine module.

Calls inside *impl* functions — functions handed TO ``jax.jit`` (directly,
via ``functools.partial``, or decorated) — are traced code, not dispatch
sites, and are skipped.
"""

from __future__ import annotations

import ast

from dllama_tpu.analysis.core import Diagnostic, dotted, str_arg
from dllama_tpu.obs.compile import COMPILE_FNS

ENGINE_PREFIX = "dllama_tpu/engine/"

#: dotted receivers that ARE the compile ledger (scope() brackets)
_SCOPE_CALLS = ("LEDGER.scope", "ledger.scope")


def _is_scope_call(call: ast.Call) -> bool:
    d = dotted(call.func)
    return d is not None and (d in _SCOPE_CALLS
                              or d.endswith(".LEDGER.scope"))


#: calls that build a cached-jit callable: jax.jit itself, and the engine's
#: wrapper that jits a program under its launch_record.PROGRAMS name
_JIT_BUILDERS = ("jax.jit", "named_jit", "launch_record.named_jit")


def _is_jax_jit(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and dotted(node.func) in _JIT_BUILDERS


def _decorated_jit(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        if dotted(dec) == "jax.jit":
            return True
        if isinstance(dec, ast.Call) and any(
                dotted(a) == "jax.jit" for a in dec.args):
            return True  # @functools.partial(jax.jit, ...)
    return False


def _collect(project):
    """(jit_attrs, module_callables, impl_names) over engine/ —
    impl_names is PER MODULE: a function handed to jax.jit in one module
    must not shadow a same-named dispatch method elsewhere."""
    factories: set[str] = set()
    impl_names: dict[str, set[str]] = {}  # rel -> traced fn names
    for src in project.py_sources(ENGINE_PREFIX):
        impls = impl_names.setdefault(src.rel, set())
        for node in ast.walk(src.tree):
            if isinstance(node, ast.FunctionDef):
                if _decorated_jit(node):
                    impls.add(node.name)
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Return)
                            and sub.value is not None
                            and _is_jax_jit(sub.value)):
                        factories.add(node.name)
            if _is_jax_jit(node):
                # functions handed to jax.jit are impls (self._decode_impl,
                # partial(self._x_impl, ...), plain names)
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Attribute):
                        impls.add(sub.attr)
                    elif isinstance(sub, ast.Name):
                        impls.add(sub.id)
    jit_attrs: dict[str, set[str]] = {}  # module rel -> tracked attr names
    mod_callables: dict[str, set[str]] = {}  # rel -> callable bare names
    for src in project.py_sources(ENGINE_PREFIX):
        attrs: set[str] = set()
        names: set[str] = set()
        for node in ast.walk(src.tree):
            if isinstance(node, ast.FunctionDef) and _decorated_jit(node):
                # class-level: self.NAME(...); module-level: NAME(...)
                attrs.add(node.name)
                names.add(node.name)
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self" and _is_jax_jit(node.value)):
                    attrs.add(t.attr)
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and isinstance(t.value.value, ast.Name)
                        and t.value.value.id == "self"
                        and isinstance(node.value, ast.Call)):
                    fname = dotted(node.value.func)
                    if fname and fname.split(".")[-1] in factories:
                        attrs.add(t.value.attr)
                if (isinstance(t, ast.Name) and _is_jax_jit(node.value)):
                    names.add(t.id)
        jit_attrs[src.rel] = attrs
        mod_callables[src.rel] = names
    # imported jit-decorated module functions count in the importing module
    all_names = set().union(*mod_callables.values()) if mod_callables else set()
    for src in project.py_sources(ENGINE_PREFIX):
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name in all_names:
                        mod_callables[src.rel].add(
                            alias.asname or alias.name)
    return jit_attrs, mod_callables, impl_names


class _Visitor(ast.NodeVisitor):
    def __init__(self, src, attrs, names, impl_names, diags):
        self.src = src
        self.attrs = attrs
        self.names = names
        self.impl_names = impl_names
        self.diags = diags
        self.scope_depth = 0
        self.impl_depth = 0

    def visit_FunctionDef(self, node: ast.FunctionDef):
        impl = node.name in self.impl_names or _decorated_jit(node)
        if impl:
            self.impl_depth += 1
        self.generic_visit(node)
        if impl:
            self.impl_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda):
        # lambdas handed to jax.vmap / inside jit args are traced; a
        # dispatch inside a bare lambda is rare enough to skip safely
        self.impl_depth += 1
        self.generic_visit(node)
        self.impl_depth -= 1

    def visit_With(self, node: ast.With):
        scoped = any(isinstance(item.context_expr, ast.Call)
                     and _is_scope_call(item.context_expr)
                     for item in node.items)
        for item in node.items:
            self.visit(item.context_expr)
        if scoped:
            self.scope_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        if scoped:
            self.scope_depth -= 1

    def _dispatch_name(self, call: ast.Call) -> str | None:
        f = call.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "self" and f.attr in self.attrs):
            return f"self.{f.attr}"
        if isinstance(f, ast.Subscript):
            base = f.value
            if (isinstance(base, ast.Attribute)
                    and isinstance(base.value, ast.Name)
                    and base.value.id == "self"
                    and base.attr in self.attrs):
                return f"self.{base.attr}[...]"
        if isinstance(f, ast.Name) and f.id in self.names:
            return f.id
        return None

    def visit_Call(self, node: ast.Call):
        name = self._dispatch_name(node)
        if name is not None and self.impl_depth == 0 \
                and self.scope_depth == 0:
            self.diags.append(Diagnostic(
                self.src.rel, node.lineno, "jit-scope",
                f"cached-jit dispatch {name}(...) outside a "
                "LEDGER.scope(fn, key) bracket — the compile ledger "
                "cannot attribute its compiles (obs/compile.COMPILE_FNS "
                "has the labels)"))
        self.generic_visit(node)


def check(project) -> list[Diagnostic]:
    diags: list[Diagnostic] = []
    jit_attrs, mod_callables, impl_names = _collect(project)
    for src in project.py_sources(ENGINE_PREFIX):
        _Visitor(src, jit_attrs.get(src.rel, set()),
                 mod_callables.get(src.rel, set()),
                 impl_names.get(src.rel, set()), diags).visit(src.tree)
    # jit-label: every literal scope label anywhere in the package must be
    # a COMPILE_FNS member (non-literal labels — warmup's loop variable —
    # are runtime-checked by ShapeContract.declare instead)
    for src in project.py_sources("dllama_tpu/"):
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and _is_scope_call(node):
                label = str_arg(node, 0)
                if label is not None and label not in COMPILE_FNS:
                    diags.append(Diagnostic(
                        src.rel, node.lineno, "jit-label",
                        f"LEDGER.scope fn label {label!r} is not in "
                        f"obs/compile.COMPILE_FNS "
                        f"({', '.join(sorted(COMPILE_FNS))})"))
    return diags

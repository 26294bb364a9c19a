"""Compile-discipline rules — the jit layer's static enforcers.

Three checkers over ONE shared model (``ops/jit_model.py``, loaded
standalone like ``tile_math`` — no jax import), closing the gap the
decorator-based ``host-sync-in-hot-path`` rule cannot see: decode.py
jits its impl methods via ``jax.jit(self._impl)`` at init, so their
bodies were never analysed as jitted code.

- ``jit-retrace-hazard``: a ``jax.jit(...)`` created and immediately
  invoked (or wrapping a lambda inside a function) rebuilds its compile
  cache every call; ``static_argnums``/``static_argnames`` that are not
  literals cannot be statically audited; and inside a REGISTERED impl
  body, a Python ``if``/``while`` on a traced parameter,
  ``float()/int()/bool()`` on one, or ``np.asarray``/``np.array``
  anywhere is a trace-time failure or silent retrace for the first
  data-dependent geometry that reaches it.
- ``donation-discipline``: every ``jax.jit`` creation site wrapping a
  registered impl must pass EXACTLY the ``donate_argnums`` /
  ``static_argnums`` the registry records (the profiler's clone of the
  decode jit can no longer drift from the engine's); and at a call
  site of a donated program, the donated buffer expression must be
  rebound by the same statement — a later read of a donated buffer is
  use-after-donate, and a donated ``self.`` attribute that is never
  rebound dangles a deleted buffer.
- ``warmup-coverage``: in a class that jits registered impls, every
  registered program with a ``warmed_by`` contract must have that
  warmup routine present AND invoking the program's attr/factory; a
  ``jax.jit`` wrapping an UNREGISTERED callable in such a class is a
  finding — new hot-path programs must join the registry (with a
  warmup or a written lazy_reason) or carry a reasoned pragma. The
  (bucket x group x horizon) grid itself is enforced at runtime: the
  compile ledger cross-checks warmup's compile counts against
  ``jit_model.required_for`` (the dynamic half of this rule).
"""

from __future__ import annotations

import ast
import importlib.util
import sys
from typing import Any, Dict, List, Optional, Set, Tuple

from tools.lint.core import (
    REPO_ROOT, Checker, FileCtx, Scope, dotted_name as _dotted, in_dirs,
)
from tools.lint.host_sync import _nonstatic_params, _traced_names_in_test

_JIT_MODEL_PATH = (
    REPO_ROOT / "ray_dynamic_batching_tpu" / "ops" / "jit_model.py"
)

_model_cache: List[Any] = []


def _jit_model():
    """The registry, loaded standalone (importlib, no jax) and cached
    for the run — fixture trees lint against the REAL registry, exactly
    like vmem's tile_math load."""
    if not _model_cache:
        spec = importlib.util.spec_from_file_location(
            "_rdb_lint_jit_model", _JIT_MODEL_PATH
        )
        mod = importlib.util.module_from_spec(spec)
        # dataclass processing resolves the module via sys.modules —
        # register before exec (removed again: this is NOT an import).
        sys.modules[spec.name] = mod
        try:
            spec.loader.exec_module(mod)
        finally:
            sys.modules.pop(spec.name, None)
        _model_cache.append(mod)
    return _model_cache[0]


def _is_jit_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func) or ""
    return dotted == "jax.jit" or dotted == "jit"


def _wrapped_tails(node: ast.Call) -> List[str]:
    """Trailing names of the callable(s) a jax.jit call wraps:
    ``self._decode_impl`` -> ``_decode_impl``; a lambda yields none."""
    if not node.args:
        return []
    target = node.args[0]
    if isinstance(target, ast.Attribute):
        return [target.attr]
    if isinstance(target, ast.Name):
        return [target.id]
    return []


def _literal_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """The literal value of a (tuple of) int constant(s), else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def _jit_kwarg(node: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


_NP_NAMES = {"np", "numpy"}


def _walk_shallow(fn: ast.AST):
    """Walk ``fn``'s body without descending into nested function
    definitions — a donated call in a nested def is that def's own
    analysis, not the enclosing one's."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            stack.extend(ast.iter_child_nodes(node))


class JitRetraceHazardChecker(Checker):
    rule = "jit-retrace-hazard"

    def applies(self, relpath: str) -> bool:
        return in_dirs(
            relpath, {"engine", "ops", "models", "parallel", "profiles"}
        )

    # --- registered-impl body context -----------------------------------
    def _impl_ctx(
        self, scope: Scope
    ) -> Optional[Tuple[ast.AST, Set[str]]]:
        """(impl function, static param names) when the innermost named
        function is a REGISTERED jit impl — its body is traced code even
        though no decorator says so (jitted via jax.jit(self._impl))."""
        jm = _jit_model()
        for fn, _ in reversed(scope.func_stack):
            if isinstance(fn, ast.Lambda):
                continue
            if fn.name not in jm.registered_impls():
                return None  # nearest named function wins
            donate, static = jm.donation_contract(fn.name)
            args = [a.arg for a in fn.args.posonlyargs + fn.args.args]
            offset = 1 if args and args[0] == "self" else 0
            statics = {
                args[i + offset]
                for i in static if i + offset < len(args)
            }
            return fn, statics
        return None

    def visit(self, node: ast.AST, ctx: FileCtx, scope: Scope) -> None:
        # (a) jit created and immediately invoked: the compile cache
        # dies with the expression — every call re-traces.
        if isinstance(node, ast.Call) and _is_jit_call(node.func):
            self.report(
                ctx, node,
                "jax.jit(...) created and immediately invoked — the "
                "compiled function (and its cache) is discarded after "
                "this call, so EVERY call re-traces and re-compiles; "
                "hoist the jit to module/init scope or memoize it "
                "(annotate a deliberate cold-path one-shot with a "
                "reasoned pragma)", scope,
            )
            return

        if _is_jit_call(node):
            # (b) jit-of-lambda inside a function: a fresh lambda object
            # per enclosing call means a fresh jit cache per call.
            if (
                node.args and isinstance(node.args[0], ast.Lambda)
                and scope.func_stack
            ):
                self.report(
                    ctx, node,
                    "jax.jit of a lambda inside a function — the lambda "
                    "is a new object per enclosing call, so the jit "
                    "cache can never hit; name the function at "
                    "module/class scope (and register it in "
                    "ops/jit_model.py if it is hot-path)", scope,
                )
            # (c) non-literal statics: unauditable, and a computed
            # static list drifting per call retraces silently.
            for kwname in ("static_argnums", "static_argnames"):
                val = _jit_kwarg(node, kwname)
                if val is None:
                    continue
                literal_ok = (
                    _literal_int_tuple(val) is not None
                    or isinstance(val, ast.Constant)
                    or (
                        isinstance(val, (ast.Tuple, ast.List))
                        and all(isinstance(e, ast.Constant)
                                for e in val.elts)
                    )
                )
                if not literal_ok:
                    self.report(
                        ctx, node,
                        f"{kwname} is not a literal — static argument "
                        "sets must be auditable constants; a computed "
                        "set that varies between creations retraces "
                        "silently", scope,
                    )
            return

        # (d) traced-value discipline inside registered impl bodies —
        # the decorator-less jitted functions host-sync cannot see.
        impl = self._impl_ctx(scope)
        if impl is None:
            return
        fn, statics = impl
        params = _nonstatic_params(fn, statics)
        if isinstance(node, (ast.If, ast.While)):
            kind = "if" if isinstance(node, ast.If) else "while"
            for name in _traced_names_in_test(node.test, params):
                self.report(
                    ctx, node,
                    f"Python `{kind}` on traced parameter '{name}' "
                    f"inside registered jit impl `{fn.name}` "
                    "(ops/jit_model.py) — branches on traced values "
                    "fail at trace time for the first data-dependent "
                    "geometry; use jnp.where/lax.cond or make the "
                    "argument static in the registry contract", scope,
                )
        elif isinstance(node, ast.Call):
            dotted = _dotted(node.func) or ""
            attr = node.func.attr if isinstance(
                node.func, ast.Attribute) else ""
            if dotted.split(".", 1)[0] in _NP_NAMES and attr in (
                "asarray", "array"
            ):
                self.report(
                    ctx, node,
                    f"{dotted} inside registered jit impl `{fn.name}` "
                    "materializes the tracer on the host (trace-time "
                    "failure or silent constant folding) — use jnp "
                    "equivalents", scope,
                )
            elif (
                isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int", "bool")
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in params
            ):
                self.report(
                    ctx, node,
                    f"{node.func.id}() on traced parameter "
                    f"'{node.args[0].id}' inside registered jit impl "
                    f"`{fn.name}` concretizes the tracer — keep it an "
                    "array or make the argument static in the registry "
                    "contract", scope,
                )


class DonationDisciplineChecker(Checker):
    rule = "donation-discipline"

    def applies(self, relpath: str) -> bool:
        return in_dirs(
            relpath, {"engine", "ops", "models", "parallel", "profiles"}
        )

    # --- creation-site contract pin -------------------------------------
    def _check_creation(self, node: ast.Call, ctx: FileCtx,
                        scope: Scope) -> None:
        jm = _jit_model()
        for tail in _wrapped_tails(node):
            if tail not in jm.registered_impls():
                continue
            want_donate, want_static = jm.donation_contract(tail)
            got: Dict[str, Optional[Tuple[int, ...]]] = {}
            for kwname in ("donate_argnums", "static_argnums"):
                val = _jit_kwarg(node, kwname)
                got[kwname] = (
                    () if val is None else _literal_int_tuple(val)
                )
            for kwname, want in (
                ("donate_argnums", want_donate),
                ("static_argnums", want_static),
            ):
                have = got[kwname]
                if have is None:
                    self.report(
                        ctx, node,
                        f"{kwname} for registered impl `{tail}` is not "
                        "a literal — the donation contract "
                        "(ops/jit_model.py) must be auditable", scope,
                    )
                elif tuple(have) != tuple(want):
                    self.report(
                        ctx, node,
                        f"jit of registered impl `{tail}` passes "
                        f"{kwname}={tuple(have)} but ops/jit_model.py "
                        f"records {tuple(want)} — un-donating a KV/pool "
                        "buffer doubles its HBM high-water mark; change "
                        "the registry WITH the call site or fix the "
                        "drift", scope,
                    )

    # --- call-site use-after-donate -------------------------------------
    def _donating_attrs(self) -> Dict[str, Tuple[int, ...]]:
        """attr -> donated positions, for attrs that map to exactly one
        donation shape (tuple-returning factories are runtime-checked
        via the ledger instead — their call sites unpack locals the
        static pass cannot bind)."""
        jm = _jit_model()
        by_attr: Dict[str, Set[Tuple[int, ...]]] = {}
        for p in jm.HOT_PROGRAMS:
            by_attr.setdefault(p.attr, set()).add(tuple(p.donate))
        return {
            attr: next(iter(shapes))
            for attr, shapes in by_attr.items()
            if len(shapes) == 1 and next(iter(shapes))
        }

    def visit(self, node: ast.AST, ctx: FileCtx, scope: Scope) -> None:
        if _is_jit_call(node):
            self._check_creation(node, ctx, scope)
            return
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        donating = self._donating_attrs()

        # One pass over this function's statements: find donated-program
        # call sites, their enclosing assignment targets, and every
        # load/store of dotted names (for the after-the-call scan).
        calls: List[Tuple[ast.Call, Tuple[int, ...], Set[str]]] = []
        loads: List[Tuple[str, int]] = []
        stores: List[Tuple[str, int]] = []

        def target_names(t: ast.AST, out: Set[str]) -> None:
            if isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    target_names(el, out)
            else:
                d = _dotted(t)
                if d:
                    out.add(d)

        for stmt in _walk_shallow(node):
            if isinstance(stmt, ast.Assign):
                targets: Set[str] = set()
                for t in stmt.targets:
                    target_names(t, targets)
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Call):
                        attr = self._program_attr(sub, donating)
                        if attr is not None:
                            calls.append(
                                (sub, donating[attr], targets)
                            )
            elif isinstance(stmt, ast.Call):
                attr = self._program_attr(stmt, donating)
                if attr is not None:
                    # Bare-expression call (no assignment): nothing
                    # rebinds the donated buffers.
                    calls.append((stmt, donating[attr], set()))
            if isinstance(stmt, (ast.Name, ast.Attribute)):
                d = _dotted(stmt)
                if d is None:
                    continue
                if isinstance(stmt.ctx, ast.Store):
                    stores.append((d, stmt.lineno))
                elif isinstance(stmt.ctx, ast.Load):
                    loads.append((d, stmt.lineno))

        seen_assigned: Set[int] = set()
        for call, positions, targets in calls:
            if id(call) in seen_assigned:
                continue
            seen_assigned.add(id(call))
            end = getattr(call, "end_lineno", call.lineno)
            for pos in positions:
                if pos >= len(call.args):
                    continue
                donated = _dotted(call.args[pos])
                if donated is None or donated == "self":
                    continue  # fresh temporaries are fine to donate
                if donated in targets:
                    continue  # canonical x = fn(x) rebind
                rebound_lines = [
                    ln for d, ln in stores if d == donated and ln > end
                ]
                first_rebind = min(rebound_lines) if rebound_lines \
                    else None
                bad_loads = [
                    ln for d, ln in loads
                    if d == donated and ln > end
                    and (first_rebind is None or ln < first_rebind)
                ]
                if bad_loads:
                    self.report(
                        ctx, call,
                        f"`{donated}` is donated at argument {pos} of "
                        "this call but read again at line "
                        f"{min(bad_loads)} before any rebind — "
                        "use-after-donate reads a deleted buffer "
                        "(or silently forces a copy)", scope,
                    )
                elif donated.startswith("self.") and first_rebind is \
                        None:
                    self.report(
                        ctx, call,
                        f"`{donated}` is donated at argument {pos} but "
                        "never rebound in this function — the "
                        "attribute now holds a deleted buffer for the "
                        "next reader; assign the call's result back "
                        "(x = fn(x)) or annotate why the buffer is "
                        "dead", scope,
                    )

    @staticmethod
    def _program_attr(
        call: ast.Call, donating: Dict[str, Tuple[int, ...]]
    ) -> Optional[str]:
        """'_decode_fn' for ``self._decode_fn(...)``."""
        d = _dotted(call.func) or ""
        if d.startswith("self."):
            attr = d[len("self."):]
            if attr in donating:
                return attr
        return None


class WarmupCoverageChecker(Checker):
    rule = "warmup-coverage"

    def applies(self, relpath: str) -> bool:
        return in_dirs(relpath, {"engine"})

    def visit(self, node: ast.AST, ctx: FileCtx, scope: Scope) -> None:
        if not isinstance(node, ast.ClassDef):
            return
        jm = _jit_model()
        registered = jm.registered_impls()

        # jit creation sites in this class, by wrapped tail name.
        creations: Dict[str, ast.Call] = {}
        for sub in ast.walk(node):
            if _is_jit_call(sub):
                for tail in _wrapped_tails(sub):
                    creations.setdefault(tail, sub)
                if not _wrapped_tails(sub):
                    creations.setdefault("<lambda>", sub)
        if not any(t in registered for t in creations):
            return  # not an engine class under the registry's purview

        methods = {
            f.name: f for f in node.body
            if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
        }

        for tail, site in creations.items():
            if tail not in registered:
                self.report(
                    ctx, site,
                    f"jax.jit wraps `{tail}`, which is not in the "
                    "ops/jit_model.py registry — every hot-path jit "
                    "program must register its donation contract and "
                    "either a warmup routine or a written lazy_reason "
                    "(or carry a reasoned pragma if it is genuinely "
                    "not hot-path)", scope,
                )
                continue
            for prog in jm.HOT_PROGRAMS:
                if prog.impl != tail or not prog.warmed_by:
                    continue
                warm = methods.get(prog.warmed_by)
                if warm is None:
                    self.report(
                        ctx, site,
                        f"registered program `{prog.name}` declares "
                        f"warmed_by `{prog.warmed_by}` but this class "
                        "defines no such method — the warmup contract "
                        "points at nothing", scope,
                    )
                    continue
                invoked = any(
                    isinstance(s, (ast.Attribute, ast.Name))
                    and (_dotted(s) or "").split(".")[-1] == prog.attr
                    for s in ast.walk(warm)
                )
                if not invoked:
                    self.report(
                        ctx, site,
                        f"registered program `{prog.name}` must be "
                        f"compiled by `{prog.warmed_by}`, but that "
                        f"method never invokes `{prog.attr}` — its "
                        "shape grid would first-compile mid-serving "
                        "(the runtime half of this check is the "
                        "compile ledger's required_for cross-check at "
                        "engine warmup)", scope,
                    )

    def contribute_extras(self, extras: Dict[str, Any]) -> None:
        jm = _jit_model()
        extras["jit_registry"] = {
            p.name: {
                "impl": p.impl,
                "attr": p.attr,
                "donate": list(p.donate),
                "static": list(p.static),
                "warmed_by": p.warmed_by or None,
                "lazy": not p.warmed_by,
                "arm": p.arm,
            }
            for p in jm.HOT_PROGRAMS
        }

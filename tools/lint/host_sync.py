"""host-sync-in-hot-path — device syncs and trace breaks, made explicit.

Two statically-decidable hazard classes around the jit boundary:

- **hot host loops** (the decode/step dispatch path): every
  ``jax.block_until_ready``, ``jax.device_get``, and ``np.asarray``/
  ``np.array`` on a non-literal is a potential device->host sync that
  serializes the dispatch pipeline. The engine is DESIGNED around
  exactly one fetch per scan round — so every sync point must either
  not exist or carry a reasoned pragma naming itself as that one fetch
  (or as host-only data). Hot functions are the configured set below
  plus any ``def`` line marked ``# rdb-lint: hot-path``.
- **jitted functions** (decorated ``@jax.jit`` /
  ``@functools.partial(jax.jit, ...)``): a Python ``if``/``while`` on a
  traced (non-static) parameter is a TracerBoolConversionError waiting
  for the first geometry that reaches it; ``float()/int()/bool()`` on a
  traced parameter and ``np.asarray`` anywhere inside concretize the
  tracer. ``x is None`` / ``x is not None`` tests are exempt (identity
  against None is static), as are attribute reads (``x.ndim``,
  ``x.shape`` are static under trace).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.lint.core import (
    Checker, FileCtx, Scope, dotted_name as _dotted, in_dirs,
)

# The decode/step dispatch path: the steady-state loop bodies whose
# wall-clock IS the serving latency. Key: path suffix relative to the
# lint root; value: function names. Extend with `# rdb-lint: hot-path`
# on a def line rather than editing this table for one-offs.
HOT_FUNCTIONS: Dict[str, Set[str]] = {
    "engine/decode.py": {
        "_step", "_spec_step", "_plain_turn", "_harvest",
        "_interleave_step",
        # ISSUE 37: one iteration of the loop, its scan issued and fetched
        # LAST (the fetch lives in ``_complete_turn``), and what it
        # completes behind the scan.
        "_iterate", "_issue_turn", "_complete_turn", "_complete_issued",
        # ISSUE 15: the token-budget prefill scheduler runs between
        # every decode turn — its chunk dispatches are steady-state
        # serving latency exactly like the scan, with ONE designed
        # fetch (the fused first-token ids) per chunk program.
        "_pump_prefill", "_spend_prefill_budget",
        "_issue_chunk_group", "_complete_chunk_group",
        "_grant_train_pages",
    },
    "engine/worker.py": {"_run_placement"},
}

_NP_NAMES = {"np", "numpy"}
_HOST_LITERALS = (
    ast.List, ast.Tuple, ast.ListComp, ast.GeneratorExp, ast.Constant,
    ast.Dict, ast.Set,
)


def _jit_static_names(fn: ast.AST) -> Optional[Set[str]]:
    """For a ``@jax.jit``-decorated function: the static argument
    names; None when the function is not jit-decorated."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    for dec in fn.decorator_list:
        target = dec
        partial_kwargs: List[ast.keyword] = []
        if isinstance(dec, ast.Call):
            dotted = _dotted(dec.func) or ""
            if dotted.endswith("partial") and dec.args:
                target = dec.args[0]
                partial_kwargs = dec.keywords
            else:
                target = dec.func
                partial_kwargs = dec.keywords
        dotted = _dotted(target) or ""
        if not (dotted == "jit" or dotted.endswith(".jit")):
            continue
        statics: Set[str] = set()
        arg_names = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        for kw in partial_kwargs:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(
                        n.value, str
                    ):
                        statics.add(n.value)
            elif kw.arg == "static_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(
                        n.value, int
                    ) and 0 <= n.value < len(arg_names):
                        statics.add(arg_names[n.value])
        return statics
    return None


def _nonstatic_params(fn: ast.AST, statics: Set[str]) -> Set[str]:
    """The traced (non-static, non-self) parameter names of a jitted
    function — shared by the branch check and the coercion check so the
    two can never disagree on the exemption set."""
    return {
        a.arg
        for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
    } - statics - {"self"}


def _traced_names_in_test(test: ast.AST, traced: Set[str]) -> List[str]:
    """Traced parameter names referenced by a branch test, skipping
    identity-vs-None compares and attribute bases (.ndim/.shape are
    static under trace)."""
    hits: List[str] = []

    def walk(node: ast.AST) -> None:
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            return
        if isinstance(node, ast.Attribute):
            return
        if isinstance(node, ast.Name) and node.id in traced:
            hits.append(node.id)
            return
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(test)
    return hits


class HostSyncChecker(Checker):
    rule = "host-sync-in-hot-path"

    def applies(self, relpath: str) -> bool:
        return in_dirs(relpath, {"engine", "ops", "models", "parallel"})

    def _hot(self, ctx: FileCtx, scope: Scope) -> bool:
        fn = scope.current_function()
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        if fn.lineno in ctx.hot_marked_lines:
            return True
        for suffix, names in HOT_FUNCTIONS.items():
            if ctx.relpath.endswith(suffix) and fn.name in names:
                return True
        return False

    def _jit_ctx(self, scope: Scope) -> Optional[Tuple[ast.AST, Set[str]]]:
        for fn, _ in reversed(scope.func_stack):
            statics = _jit_static_names(fn)
            if statics is not None:
                return fn, statics
        return None

    def visit(self, node: ast.AST, ctx: FileCtx, scope: Scope) -> None:
        jit = self._jit_ctx(scope)
        if jit is not None and isinstance(node, (ast.If, ast.While)):
            fn, statics = jit
            params = _nonstatic_params(fn, statics)
            for name in _traced_names_in_test(node.test, params):
                kind = "if" if isinstance(node, ast.If) else "while"
                self.report(
                    ctx, node,
                    f"Python `{kind}` on traced parameter '{name}' inside "
                    "a jitted function — branches on traced values fail "
                    "at trace time for the first data-dependent "
                    "geometry; use jnp.where/lax.cond or make the "
                    "argument static", scope,
                )
            return

        if not isinstance(node, ast.Call):
            return
        dotted = _dotted(node.func) or ""
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else ""

        if jit is not None:
            fn, statics = jit
            params = _nonstatic_params(fn, statics)
            head = dotted.split(".", 1)[0]
            if head in _NP_NAMES and attr in ("asarray", "array"):
                self.report(
                    ctx, node,
                    f"{dotted} inside a jitted function materializes the "
                    "tracer on the host (trace-time failure or silent "
                    "constant folding) — use jnp equivalents", scope,
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
                    f"'{node.args[0].id}' inside a jitted function "
                    "concretizes the tracer — keep it an array or make "
                    "the argument static", scope,
                )
            return

        if not self._hot(ctx, scope):
            return
        if attr == "block_until_ready" or dotted == \
                "jax.block_until_ready":
            self.report(
                ctx, node,
                "block_until_ready in the decode/step hot path "
                "serializes dispatch against the device — the loop's "
                "cadence should come from its single designed fetch; "
                "annotate a deliberate sync with a reasoned pragma",
                scope,
            )
        elif dotted == "jax.device_get":
            self.report(
                ctx, node,
                "jax.device_get in the decode/step hot path is a "
                "device->host sync — batch it into the loop's single "
                "designed fetch or annotate why it must stand alone",
                scope,
            )
        elif dotted.split(".", 1)[0] in _NP_NAMES and attr in (
            "asarray", "array"
        ):
            arg = node.args[0] if node.args else None
            if arg is None or isinstance(arg, _HOST_LITERALS):
                return  # host literal: no device value to sync on
            if isinstance(arg, ast.Call):
                inner = _dotted(arg.func) or ""
                if inner.split(".", 1)[0] in _NP_NAMES:
                    return  # np-of-np: already host-side
            self.report(
                ctx, node,
                f"{dotted} in the decode/step hot path forces a "
                "device->host fetch if its argument is a device value — "
                "the engine budgets ONE fetch per scan round; annotate "
                "this as that fetch (or as host-only data) with a "
                "reasoned pragma", scope,
            )

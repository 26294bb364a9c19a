"""vmem-budget + tile-alignment — Pallas BlockSpec checkers for ops/.

Both rules statically evaluate ``pl.BlockSpec`` block shapes with the
SAME padded-footprint model the runtime KV-tile picker uses
(``ray_dynamic_batching_tpu/ops/tile_math.py``, loaded standalone so
the linter never imports jax). That sharing is the point: PR 1 fixed a
real production bug where hand-computed footprint math undercounted
lane padding (H=64 tiles looked half their true VMEM size); with one
implementation the static model and ``_pick_sb`` cannot drift apart.

- **vmem-budget**: per ``pl.pallas_call``, sum the padded bytes of
  every statically-resolvable BlockSpec (in_specs + out_specs), apply
  the double-buffering multiplier, add the ``pltpu.VMEM`` scratch the
  call allocates (once each: a kernel that leaves an operand in HBM,
  ``memory_space=pl.ANY``, and copies its tiles into scratch itself — the
  paged decode kernel's page ring — holds them there, not in pipelined
  blocks), and compare against
  ``VMEM_BLOCK_BUDGET_BYTES``. Dims are resolved through module- and
  function-level integer-constant assignments; the footprint assumes
  f32 (itemsize 4) — provably the worst case, since sublane packing
  times itemsize is a constant 32 bytes. A call whose shapes cannot be
  resolved is fine ONLY when the module actually IMPORTS the shared
  ``tile_math`` model (or the budget constant) — i.e. a runtime picker
  guards what the static model cannot see; otherwise the kernel has
  unbounded tiles and no guard, and that is the finding.
- **tile-alignment**: any resolvable trailing (lane) dim that is not a
  multiple of 128, or sublane dim not a multiple of 8, silently pads in
  VMEM — e.g. a ``(kb, 1)`` trailing pair pads to ``(8, 128)``, a ~128x
  blowup invisible to export-based lowering tests
  (``ops/decode_attention.py`` documents the real case).

Mesh shards (ROADMAP item 2): a head-sharded paged kernel streams
``tile_math.shard_heads(K, tp)`` kv heads per core, so its true VMEM
block divides by the TP degree where the head block spans the axis.
The TP degree is a runtime property the static pass cannot see, so the
checker's role is the escape-hatch discipline above — mesh-shaped
kernels resolve their blocks through the runtime guard in
``paged_decode_attention``, which budgets the per-shard block with the
SAME standalone-loaded model (``shard_heads`` agreement pinned by
``tests/test_lint.py::TestSharedTileMath``).
"""

from __future__ import annotations

import ast
import importlib.util
from typing import Dict, List, Optional, Sequence

from tools.lint.core import Checker, FileCtx, REPO_ROOT, Scope, in_dirs

_TILE_MATH_PATH = (
    REPO_ROOT / "ray_dynamic_batching_tpu" / "ops" / "tile_math.py"
)

# Statically-assumed itemsize: f32. SUBLANE_PACK[i] * i == 32 for every
# supported dtype, so ceil(n/pack)*pack*itemsize is maximized at
# itemsize 4 — the f32 evaluation upper-bounds every narrower dtype.
ASSUMED_ITEMSIZE = 4


def _load_tile_math():
    spec = importlib.util.spec_from_file_location(
        "_rdb_lint_tile_math", _TILE_MATH_PATH
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_tile_math = _load_tile_math()


def _const_int(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def _iter_scope_nodes(root: ast.AST):
    """Nodes in ``root``'s OWN scope: descends into control flow but not
    into nested function/class scopes — their locals are not visible
    here, and leaking them would resolve dims against stale bindings."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(node))


def _scan_env(root: ast.AST) -> Dict[str, Optional[int]]:
    """Single-assignment integer-constant environment for ONE scope: a
    name assigned one literal int resolves; reassigned or non-constant
    names poison (resolve to None). Function parameters are poisoned up
    front — they are runtime values and must shadow any same-named
    module constant rather than resolve to it."""
    env: Dict[str, Optional[int]] = {}
    if isinstance(root, (ast.FunctionDef, ast.AsyncFunctionDef)):
        args = root.args
        for a in args.posonlyargs + args.args + args.kwonlyargs:
            env[a.arg] = None
        for a in (args.vararg, args.kwarg):
            if a is not None:
                env[a.arg] = None
    for node in _iter_scope_nodes(root):
        targets: List[ast.expr] = []
        value: Optional[ast.AST] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        elif isinstance(node, (ast.AugAssign, ast.For)):
            targets = [node.target]
        for t in targets:
            names = [n.id for n in ast.walk(t) if isinstance(n, ast.Name)]
            for name in names:
                val = _const_int(value) if value is not None else None
                if isinstance(node, (ast.AugAssign, ast.For)):
                    val = None
                if name in env and env[name] != val:
                    env[name] = None
                elif name not in env:
                    env[name] = val
    return env


def resolve_dim(node: ast.AST, env: Dict[str, Optional[int]]
                ) -> Optional[int]:
    v = _const_int(node)
    if v is not None:
        return v
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = resolve_dim(node.operand, env)
        return -inner if inner is not None else None
    if isinstance(node, ast.BinOp):
        left = resolve_dim(node.left, env)
        right = resolve_dim(node.right, env)
        if left is None or right is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.FloorDiv):
                return left // right
            if isinstance(node.op, ast.Mod):
                return left % right
        except (ZeroDivisionError, ValueError):
            return None
    return None


def _is_blockspec_call(node: ast.Call) -> bool:
    fn = node.func
    return (isinstance(fn, ast.Attribute) and fn.attr == "BlockSpec") or (
        isinstance(fn, ast.Name) and fn.id == "BlockSpec"
    )


def _blockspec_shape(node: ast.Call) -> Optional[ast.Tuple]:
    if node.args and isinstance(node.args[0], ast.Tuple):
        return node.args[0]
    for kw in node.keywords:
        if kw.arg == "block_shape" and isinstance(kw.value, ast.Tuple):
            return kw.value
    return None


def _stays_in_hbm(node: ast.Call) -> bool:
    """A BlockSpec that names a memory space and no block
    (``memory_space=pl.ANY``): the operand is not brought into VMEM by
    the pipeline; what the kernel copies of it lands in its scratch."""
    return _blockspec_shape(node) is None and any(
        kw.arg == "memory_space" and isinstance(kw.value, ast.Attribute)
        and kw.value.attr in ("ANY", "HBM") for kw in node.keywords)


def _is_vmem_scratch(node: ast.Call) -> bool:
    fn = node.func
    return isinstance(fn, ast.Attribute) and fn.attr == "VMEM"


def _imports_tile_math(tree: ast.AST) -> bool:
    """True only for a REAL import of the shared model (``tile_math`` or
    ``VMEM_BLOCK_BUDGET_BYTES``) — a comment or docstring mention must
    not satisfy the guard requirement (the escape hatch is 'a runtime
    picker built on the shared model exists in this module', and only an
    import makes that possible)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any("tile_math" in (a.name or "") for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if "tile_math" in (node.module or ""):
                return True
            if any(a.name in ("tile_math", "VMEM_BLOCK_BUDGET_BYTES")
                   for a in node.names):
                return True
    return False


class _BlockSpecMixin(Checker):
    def applies(self, relpath: str) -> bool:
        return in_dirs(relpath, {"ops"})

    def begin_file(self, ctx: FileCtx) -> None:
        self._module_env = _scan_env(ctx.tree)
        self._func_envs: Dict[int, Dict[str, Optional[int]]] = {}
        self._guard_imported = _imports_tile_math(ctx.tree)

    def _env_for(self, scope: Scope) -> Dict[str, Optional[int]]:
        env = dict(self._module_env)
        for fn, _ in scope.func_stack:
            if id(fn) not in self._func_envs:
                self._func_envs[id(fn)] = _scan_env(fn)
            env.update(self._func_envs[id(fn)])
        return env


class TileAlignmentChecker(_BlockSpecMixin):
    rule = "tile-alignment"

    def visit(self, node: ast.AST, ctx: FileCtx, scope: Scope) -> None:
        if not (isinstance(node, ast.Call) and _is_blockspec_call(node)):
            return
        shape = _blockspec_shape(node)
        if shape is None or not shape.elts:
            return
        env = self._env_for(scope)
        dims = shape.elts
        lane = resolve_dim(dims[-1], env)
        if lane is not None and lane > 0 and lane % _tile_math.LANE != 0:
            padded = _tile_math.pad_lane(lane)
            self.report(
                ctx, node,
                f"BlockSpec lane (last) dim {lane} is not a multiple of "
                f"128 — Mosaic pads it to {padded} in VMEM "
                f"(~{padded // lane}x silent blowup); make the trailing "
                "dim a 128 multiple or span the array's last axis with "
                "an aligned layout", scope,
            )
        if len(dims) >= 2:
            sub = resolve_dim(dims[-2], env)
            if sub is not None and sub > 0 and sub % 8 != 0:
                padded = _tile_math.pad_sublane(sub, ASSUMED_ITEMSIZE)
                self.report(
                    ctx, node,
                    f"BlockSpec sublane (second-to-last) dim {sub} is not "
                    f"a multiple of the dtype packing (8 for f32; 16/32 "
                    f"for bf16/int8) — it pads to >= {padded}, wasting "
                    "sublanes on every tile", scope,
                )


_SPEC_KWARGS = ("in_specs", "out_specs")
# Scratch shapes that hold no VMEM: semaphores and scalar memory.
_NOT_VMEM = ("DMA", "REGULAR", "BARRIER", "SMEM")


class VmemBudgetChecker(_BlockSpecMixin):
    rule = "vmem-budget"

    def visit(self, node: ast.AST, ctx: FileCtx, scope: Scope) -> None:
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call"
        ):
            return
        blocks = [s for s in self._collect(node, scope, _SPEC_KWARGS,
                                           _is_blockspec_call)
                  if s is None or not _stays_in_hbm(s)]
        scratch = self._collect(node, scope, ("scratch_shapes",),
                                _is_vmem_scratch)
        if not blocks and not scratch:
            return
        env = self._env_for(scope)

        def padded(calls) -> Optional[int]:
            """Padded bytes of the calls' shapes; None if one cannot be
            read (an opaque entry, a runtime dim)."""
            total = 0
            for call in calls:
                shape = None if call is None else _blockspec_shape(call)
                dims = [] if shape is None else [
                    resolve_dim(d, env) for d in shape.elts]
                if not dims or any(d is None or d <= 0 for d in dims):
                    return None
                total += _tile_math.padded_block_bytes(
                    dims, ASSUMED_ITEMSIZE)
            return total

        total, held = padded(blocks), padded(scratch)
        unresolved = total is None or held is None
        if unresolved:
            # Runtime-shaped tiles: fine only when the module shares the
            # runtime/static footprint model (a picker like _pick_sb
            # guards what we cannot evaluate here).
            if not self._guard_imported:
                self.report(
                    ctx, node,
                    "pallas_call BlockSpec shapes are not statically "
                    "resolvable and the module imports neither "
                    "tile_math nor VMEM_BLOCK_BUDGET_BYTES — add a "
                    "runtime footprint guard built on ops/tile_math.py "
                    "(see decode_attention._pick_sb) so tiles cannot "
                    "silently exceed VMEM", scope,
                )
            return
        budget = _tile_math.VMEM_BLOCK_BUDGET_BYTES
        footprint = _tile_math.DOUBLE_BUFFER * total + held
        if footprint > budget:
            self.report(
                ctx, node,
                f"pallas_call block footprint "
                f"{footprint / 2 ** 20:.1f} MB (padded, blocks "
                f"double-buffered, VMEM scratch once, "
                f"f32-itemsize upper bound) exceeds "
                f"VMEM_BLOCK_BUDGET_BYTES = {budget / 2 ** 20:.0f} MB — "
                "shrink the tile (this is the H=64 lane-padding "
                "undercount class PR 1 fixed in _pick_sb)", scope,
            )

    def _collect(self, call: ast.Call, scope: Scope,
                 kwargs: Sequence[str], wanted
                 ) -> List[Optional[ast.Call]]:
        """The ``wanted`` calls (BlockSpecs, or VMEM scratch shapes)
        reachable from the ``kwargs`` of a pallas_call: literal lists
        inline; a Name resolves through every list
        assignment/append/extend in the enclosing function (an
        over-approximation — conservative for a budget). A
        ``grid_spec=`` kwarg (``PrefetchScalarGridSpec`` — the
        page-table-indexed decode kernel's form — or a plain
        ``GridSpec``), inline or reached through a Name, is transparent:
        its own kwargs are collected as if passed directly, so moving
        specs into a grid spec cannot silently exempt a kernel from the
        budget. Where a list is built by something this pass cannot read
        (a helper's return value, ``[spec] * n`` with a runtime n) the
        result holds a None: the call then counts as unresolved."""
        calls: List[ast.Call] = [call]
        for kw in call.keywords:
            if kw.arg != "grid_spec":
                continue
            if isinstance(kw.value, ast.Call):
                calls.append(kw.value)
            elif isinstance(kw.value, ast.Name):
                fn = scope.current_function()
                calls += [
                    sub.value for sub in (ast.walk(fn) if fn else ())
                    if isinstance(sub, ast.Assign)
                    and isinstance(sub.value, ast.Call)
                    and any(isinstance(t, ast.Name)
                            and t.id == kw.value.id for t in sub.targets)]
        out: List[Optional[ast.Call]] = []
        for c in calls:
            for kw in c.keywords:
                if kw.arg in kwargs:
                    out.extend(self._from(kw.value, scope, wanted, set()))
        return out

    def _from(self, node: ast.AST, scope: Scope, wanted,
              seen: set) -> List[Optional[ast.Call]]:
        out: List[Optional[ast.Call]] = []
        if isinstance(node, ast.Call) and wanted(node):
            out.append(node)
        elif isinstance(node, (ast.List, ast.Tuple)):
            for elt in node.elts:
                out.extend(self._from(elt, scope, wanted, seen))
        elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            out.extend(self._from(node.left, scope, wanted, seen))
            out.extend(self._from(node.right, scope, wanted, seen))
        elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            # [spec] * n: n copies (an unreadable n: opaque)
            times = _const_int(node.right)
            out.extend([None] if times is None else
                       self._from(node.left, scope, wanted, seen) * times)
        elif isinstance(node, ast.Call):
            # a helper's return value: unreadable here, unless it is one
            # of the OTHER kind's constructors (a semaphore among the
            # scratch shapes holds no VMEM)
            if not (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _NOT_VMEM):
                out.append(None)
        elif isinstance(node, ast.Name):
            if node.id in seen:  # e.g. specs = specs[:3] self-reference
                return out
            seen.add(node.id)
            fn = scope.current_function()
            if fn is None:
                return out
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == node.id
                    for t in sub.targets
                ):
                    out.extend(self._from(sub.value, scope, wanted, seen))
                elif isinstance(sub, ast.AugAssign) and isinstance(
                    sub.target, ast.Name
                ) and sub.target.id == node.id:
                    out.extend(self._from(sub.value, scope, wanted, seen))
                elif (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("append", "extend")
                    and isinstance(sub.func.value, ast.Name)
                    and sub.func.value.id == node.id
                ):
                    for arg in sub.args:
                        out.extend(self._from(arg, scope, wanted, seen))
        return out


def tile_math_module():
    """The standalone-loaded shared model (tests pin agreement on it)."""
    return _tile_math

"""Shared static model of the engine's hot-path jit programs.

Single source of truth for WHICH jit entry points exist on the decode
hot path, what their donation contracts are, what shape grid each one
retraces over, and which warmup routine is responsible for compiling it
before serving. Consumed by BOTH enforcers (the ``tile_math`` /
``concurrency.LOCK_RANKS`` pattern applied to the jit layer):

- at runtime, ``DecodeEngine.warmup`` cross-checks the compile
  ledger (``utils/compile_ledger.py``) against :func:`required_for` —
  a registered program the engine needs that warmup did NOT compile is a
  hard error at startup, not a 20-40s XLA stall mid-serving;
- statically, three rdb-lint rules load this module standalone
  (importlib, no jax): ``jit-retrace-hazard`` analyses the registered
  impl bodies (decode.py jits them via ``jax.jit(self._impl)`` at init,
  invisible to the decorator-based host-sync rule),
  ``donation-discipline`` pins every ``jax.jit`` creation site's
  ``donate_argnums``/``static_argnums`` to the contract recorded here,
  and ``warmup-coverage`` requires every registered program to be
  invoked inside its declared ``warmed_by`` routine (and every
  UNregistered ``self._*_fn = jax.jit(...)`` assignment to either join
  the registry or carry a reasoned pragma).

Deliberately dependency-free (no jax import): the linter loads this
module standalone so ``python -m tools.lint`` stays fast and runs in
environments without an accelerator stack.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Tuple

# Which engines run a program (see required_for): warmup is not
# required to compile the draft's programs on an engine without a draft.
ARM_ALWAYS = "always"            # every engine
ARM_SPEC = "spec"                # draft model attached


@dataclass(frozen=True)
class JitProgram:
    """One hot-path jit entry point and its contracts.

    ``attr`` is the engine attribute (or factory method) holding the
    compiled callable; ``impl`` the method jit-wrapped at creation.
    ``donate``/``static`` are the EXACT ``donate_argnums`` /
    ``static_argnums`` the creation site must pass — ``donated`` names
    the buffers those positions carry, so a contract change has to say
    what it un-donates. ``grid`` documents the shape axes the program
    retraces over; ``warmed_by`` names the warmup routine that must
    invoke ``attr`` (empty iff lazy, with a mandatory ``lazy_reason``).
    """

    name: str
    attr: str
    impl: str
    donate: Tuple[int, ...] = ()
    static: Tuple[int, ...] = ()
    donated: Tuple[str, ...] = ()
    grid: str = ""
    warmed_by: str = ""
    lazy_reason: str = ""
    arm: str = ARM_ALWAYS

    def __post_init__(self) -> None:
        if not self.warmed_by and not self.lazy_reason:
            raise ValueError(
                f"jit program {self.name!r}: not warmed and no "
                "lazy_reason — every hot-path program is either warmed "
                "or explains why a first-hit compile is acceptable"
            )


HOT_PROGRAMS: Tuple[JitProgram, ...] = (
    JitProgram(
        name="decode_step",
        attr="_decode_fn", impl="_decode_impl",
        donate=(1, 8), static=(3,),
        donated=("cache", "counts"),
        grid="horizon in {1, ttft_horizon, decode_horizon}",
        warmed_by="_warmup_decode", arm=ARM_ALWAYS,
    ),
    JitProgram(
        name="chunk_prefill",
        attr="_chunk_paged_fn", impl="_chunk_group_paged_impl",
        donate=(2,),
        donated=("pool cache",),
        grid="(bucket x group) via _admit_group_sizes",
        warmed_by="_warmup_impl", arm=ARM_ALWAYS,
    ),
    JitProgram(
        name="spec_verify",
        attr="_spec_fn", impl="_spec_impl",
        donate=(1, 2),
        donated=("cache", "draft cache"),
        grid="one shape: (num_slots x spec_window)",
        warmed_by="_warmup_decode", arm=ARM_SPEC,
    ),
    JitProgram(
        name="draft_catchup",
        attr="_draft_catchup_fn", impl="_draft_catchup_impl",
        donate=(1,),
        donated=("draft cache",),
        grid="window h in {1, ttft_horizon, decode_horizon}",
        warmed_by="_warmup_decode", arm=ARM_SPEC,
    ),
    JitProgram(
        name="zero_counts",
        attr="_zero_counts_fn", impl="_reset_counts",
        donate=(0,),
        donated=("counts",),
        grid="one shape: (num_slots x vocab)",
        warmed_by="_warmup_decode", arm=ARM_ALWAYS,
    ),
    # --- registered-lazy programs. Each lazy_reason is load-bearing:
    # warmup-coverage treats an UNregistered lazy jit as a finding, so
    # adding a factory means writing down why its first-hit compile is
    # acceptable.
    JitProgram(
        name="draft_long_chunk",
        attr="_draft_long_fill", impl="chunk_impl",
        donate=(3,),
        donated=("draft row cache",),
        grid="chunk = largest bucket",
        lazy_reason="spec engines see long prompts rarely; the draft's "
        "chunk program compiles once at the first long admission and "
        "the chunk-stall bound already prices that turn",
        arm=ARM_SPEC,
    ),
    JitProgram(
        name="draft_long_commit",
        attr="_draft_long_fill", impl="commit_row",
        donate=(0,),
        donated=("draft cache",),
        grid="chunk = largest bucket",
        lazy_reason="paired with draft_long_chunk — same cold path",
        arm=ARM_SPEC,
    ),
)

_BY_NAME: Dict[str, JitProgram] = {p.name: p for p in HOT_PROGRAMS}


def program(name: str) -> JitProgram:
    return _BY_NAME[name]


def program_names() -> Tuple[str, ...]:
    return tuple(_BY_NAME)


def warmed_programs() -> Tuple[JitProgram, ...]:
    return tuple(p for p in HOT_PROGRAMS if p.warmed_by)


def lazy_programs() -> Tuple[JitProgram, ...]:
    return tuple(p for p in HOT_PROGRAMS if not p.warmed_by)


def registered_impls() -> FrozenSet[str]:
    """Impl callable names the registry knows — the retrace rule's
    analysis set and warmup-coverage's registration check."""
    return frozenset(p.impl for p in HOT_PROGRAMS)


def registered_attrs() -> FrozenSet[str]:
    """Engine attributes / factories that legally hold jit programs."""
    return frozenset(p.attr for p in HOT_PROGRAMS)


def donation_contract(impl: str) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """(donate_argnums, static_argnums) the creation site wrapping
    ``impl`` must pass. KeyError for unregistered impls — callers decide
    whether unknown means 'not hot path' or 'finding'."""
    for p in HOT_PROGRAMS:
        if p.impl == impl:
            return (p.donate, p.static)
    raise KeyError(impl)


def required_for(has_draft: bool) -> Tuple[JitProgram, ...]:
    """Warmed programs an engine MUST compile during warmup — the
    runtime coverage check's ground truth: the chunk program, the decode
    scan and the counts reset; an engine with a draft model adds verify
    + catch-up."""
    arms = {ARM_ALWAYS, ARM_SPEC} if has_draft else {ARM_ALWAYS}
    return tuple(p for p in warmed_programs() if p.arm in arms)

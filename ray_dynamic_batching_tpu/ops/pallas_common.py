"""What every Pallas kernel wrapper in ``ops/`` shares: how a wrapper
says why it declined, and the rule for interpret mode.

A wrapper declines (returns None) for shapes its kernel is not built
for; the dispatcher (``ops/attention.py``) then tries the next path. A
decline nobody can see hides the device — a program believed to run a
fused kernel may be running the XLA reference — so every decline names
its reason through :func:`declined` and the dispatcher records it.
"""

from __future__ import annotations

from typing import List, Optional

import jax


def declined(why: Optional[List[str]], reason: str) -> None:
    """Record ``reason`` on the caller's list (when it passed one) and
    answer None — ``return declined(why, "...")`` is a wrapper's
    decline."""
    if why is not None:
        why.append(reason)
    return None


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode is the CPU stand-in for a kernel (tests); on a TPU
    backend the kernel compiles through Mosaic or the call is an error —
    an interpreted kernel there would be a silent, very slow fallback.
    ``interpret=False`` off-TPU stays legal: ``jax.export`` lowers for
    the TPU from a CPU host."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: Pallas kernels compile "
            "through Mosaic there; interpret mode is for CPU tests"
        )
    return bool(interpret)

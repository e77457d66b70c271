"""Backend-aware default for the Pallas ``interpret`` flag.

Every Pallas kernel in this package takes ``interpret: bool | None =
None`` and resolves ``None`` through :func:`default_interpret`: compiled
Pallas (``interpret=False``) on accelerator backends, interpreter mode
on CPU, where compiled Pallas calls are refused.  Passing an explicit
``True``/``False`` always wins (the compiled/interpret parity tests pass
``False`` on purpose and record the skip reason when the backend
refuses).
"""
from __future__ import annotations

import jax

__all__ = ["default_interpret", "resolve_interpret"]


def default_interpret() -> bool:
    """True iff the default JAX backend needs Pallas interpreter mode."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument: explicit values pass
    through; ``None`` picks the backend-aware default."""
    return default_interpret() if interpret is None else bool(interpret)

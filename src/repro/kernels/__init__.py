# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas kernel families (flash_attention, ssop, count_sketch, lora).

All ``pallas_call`` entry points take ``interpret=None`` and resolve it
through :func:`resolve_interpret`: compiled Mosaic on TPU, the Pallas
interpreter everywhere else (CPU/GPU test runs).  ``set_interpret``
overrides the default process-wide — e.g. ``set_interpret(True)`` to
force interpreter semantics on TPU while debugging, or
``set_interpret(False)`` on a backend with native Pallas lowering.
"""
from __future__ import annotations

from typing import Optional

_INTERPRET_OVERRIDE: Optional[bool] = None


def set_interpret(value: Optional[bool]) -> None:
    """Force the ``interpret`` default for every kernel family.

    ``True``/``False`` pins the mode; ``None`` restores the backend-aware
    default (``interpret = jax.default_backend() != "tpu"``).
    """
    global _INTERPRET_OVERRIDE
    _INTERPRET_OVERRIDE = value


def resolve_interpret(value: Optional[bool] = None) -> bool:
    """Resolve a per-call ``interpret`` argument to a concrete bool."""
    if value is not None:
        return value
    if _INTERPRET_OVERRIDE is not None:
        return _INTERPRET_OVERRIDE
    import jax
    return jax.default_backend() != "tpu"


def block_size(dim: int, cap: int = 512, align: int = 128) -> int:
    """Tile extent for a kernel axis of length ``dim``: the largest
    multiple of ``align`` that is <= ``cap`` and divides ``dim``, else
    ``dim`` itself (a full-extent block is always legal for Mosaic).
    BERT-base's 768 gets 384 where a fixed 512 would not divide it."""
    for b in range(min(cap, dim) // align * align, 0, -align):
        if dim % b == 0:
            return b
    return dim

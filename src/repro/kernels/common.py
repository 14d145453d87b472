"""What every Pallas kernel here shares: its execution mode and its tiles."""

from __future__ import annotations

from typing import Optional

import jax

# A block's last two dims must be multiples of (SUBLANE, LANE), or span the
# whole array dim, for the TPU lowering to accept it.
SUBLANE = 8
LANE = 128


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Compiled on a TPU, interpreted anywhere else.

    ``None`` follows the default backend; an explicit flag wins (compiling
    for a described TPU from a CPU process passes ``False``).
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def tile(request: int, dim: int, align: int) -> int:
    """Largest multiple of ``align`` <= ``request`` that divides ``dim``;
    the whole ``dim`` when there is none."""
    b = min(request, dim) // align * align
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim

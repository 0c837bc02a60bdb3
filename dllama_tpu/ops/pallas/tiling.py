"""Shared tiling helpers for the Pallas kernels."""

from __future__ import annotations


def pick_tile(dim: int, candidates: tuple[int, ...]) -> int:
    """Largest candidate that divides `dim`, else `dim` itself (one tile)."""
    for c in candidates:
        if dim % c == 0:
            return c
    return dim

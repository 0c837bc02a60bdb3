"""x[m, k] @ W[k, n] with W in Q40: FLOPs 2*m*k*n; bytes = the packed
weights (18 bytes per 32 weights: 16 of nibbles, 2 of f16 scale) + the bf16
activations in + the bf16 result out. Copied arithmetic of the program's
`obs/perf.decode_step_bytes` / `experiments/hbm_traffic.py` (the originals
stay for a later PR to delete)."""

from __future__ import annotations

import re

Q_BLOCK, Q40_BLOCK_BYTES = 32, 18


def cost(m: int, k: int, n: int) -> tuple[float, float]:
    weights = k * n // Q_BLOCK * Q40_BLOCK_BYTES
    return 2.0 * m * k * n, float(weights + 2 * m * k + 2 * m * n)


_OUT = re.compile(r"= f32\[(\d+),(\d+)\]")
_PACKED = re.compile(r"u8\[(?:\d+,)?(\d+),(\d+)\]")


def calls(config: dict, trace_op: dict):
    """One traced `_blockdot_call`: the HLO text gives the result f32[m, n]
    (m = the rows the kernel computes, slots padded to its tile) and the
    packed operand u8[(layers,) k/2, n]. None when the text does not parse:
    no share is then reported."""
    out = _OUT.search(trace_op["hlo"])
    packed = _PACKED.search(trace_op["hlo"])
    if not out or not packed or out.group(2) != packed.group(2):
        return None
    return cost(int(out.group(1)), 2 * int(packed.group(1)), int(out.group(2)))

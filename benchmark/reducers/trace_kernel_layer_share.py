"""The share of the device's busy time, in percent, that a kernel's whole
layer took in the capture: the kernel's calls and the XLA ops around them
(for the expert layer: router, top-k, the sort into expert order, the
gathers, the activation between the projections, the weighted combine).

XLA ops carry no names of their own on the device plane, so they are found
by shape; but no row count is written in the metric's file. Every size is
read from the capture and the configuration:

- the kernel's calls (`match`, on the op's group) state the padded row order
  they work on: the leading dimension of their 2-D operands and results, and
  the length of their 1-D s32 tile maps. A tile height, a slice size or a
  padding that a later change picks shows up there by itself, a prompt's
  last, shorter slice included;
- the configuration states the experts a layer (`experts_key`), the experts
  a token (`active_key`) and the stream's width (`hidden_key`). The chosen
  experts' [.., active] arrays give the token-expert rows (their element
  count); the router's product is the op that holds [.., hidden, experts];
  its logits, their top-k sort and the softmax over them are the rank-3
  [.., experts] arrays (a rank-2 [rows, 64] is also a rope table at a head
  size of 128, and is left out).

An XLA op is the layer's if a shape in its text (result or operand) leads
with one of those row counts, is a tile map, or is one of the router's
arrays. Returns None without a trace or where the capture holds no call of
the kernel: a share is never reported as 0 on a guess."""

import math
import re

_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _shapes(text: str) -> list:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def layer_ops(params: dict, config: dict, ops: list) -> list:
    """The ops of `ops` (trace_reduce's: group, hlo, seconds) that are the
    layer's: the kernel's calls first."""
    by_group = re.compile(params["match"])
    kernels = [op for op in ops if by_group.search(op["group"])]
    if not kernels:
        return []
    experts = int(config[params["experts_key"]])
    active = int(config[params["active_key"]])
    hidden = int(config[params["hidden_key"]])
    # a loop's own line carries every array its body touches: not an op
    rest = [(op, _shapes(op["hlo"])) for op in ops
            if not by_group.search(op["group"])
            and op["group"] not in ("while", "conditional", "call")]
    rows, maps = set(), set()
    for op in kernels:
        for dt, dims in _shapes(op["hlo"]):
            if len(dims) == 2:
                rows.add(dims[0])
            elif len(dims) == 1 and dt == "s32" and dims[0] > 1:
                maps.add(dims[0])
    for _, shapes in rest:  # token-expert rows: what [.., active] arrays hold
        rows.update(math.prod(dims) for _, dims in shapes
                    if len(dims) >= 2 and dims[-1] == active)

    def mine(shapes) -> bool:
        for dt, dims in shapes:
            if not dims:
                continue
            if dims[0] in rows and (len(dims) == 1 or dims[0] > experts):
                return True
            if len(dims) <= 2 and dims[0] in maps and dt in ("s32", "u32", "pred"):
                return True
            if active in dims[1:] and len(dims) <= 3:
                return True
            if dims[-2:] == (hidden, experts):
                return True
            if len(dims) == 3 and dims[-1] == experts:
                return True
        return False

    return kernels + [op for op, shapes in rest if mine(shapes)]


def reduce(params: dict, run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    picked = layer_ops(params, run["config"], trace.get("ops", ()))
    if not picked:
        return None
    planes = max(1, int(trace.get("device_planes") or 1))
    return 100.0 * sum(op["seconds"] for op in picked) / planes / trace["busy_s"]

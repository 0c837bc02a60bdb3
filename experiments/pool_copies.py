"""Does a step program copy the KV page pool? Read it off the compiled
program, for the chip's compiler, with no chip attached.

Builds the paged `BatchEngine` over shapes at DeepSeek-LLM-7B width as the
benchmark's cell serves it (30 layers, MHA 32/32 heads of 128, 12 slots, 66
pages of 128 rows: a K pool and a V pool of 2.1 GB each, a layer's slice 70
MB), compiles its decode n=4 and hybrid programs for the described v5e
(`experiments/aot_check.py`'s topology) and prints, per program:

* `memory_analysis()`: arguments, outputs, aliased bytes and the TEMP: a
  temp near the pool's size means a second pool lives beside the first;
* every instruction of the optimised HLO that moves bytes (`copy`,
  `dynamic-slice`, `dynamic-update-slice`, `scatter`, or a fusion of them)
  and writes at least one layer's K slice (its result; for an in-place
  dynamic-update-slice its update), with the computation it sits in and
  whether that computation is a loop body.

Nothing runs, so this says what the program would move, not how long it
takes: the trace of a chip run does (`PERF.md` section 5).

Usage: python experiments/pool_copies.py [--layers N] [--pages N] [--slots N]
Exit 1 if a loop body holds such an instruction.
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from experiments import aot_check  # sets the CPU/libtpu environment before jax loads

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "u8": 1, "s8": 1,
          "pred": 1, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(")
_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice", "scatter",
           "gather", "fusion", "slice", "concatenate", "pad", "transpose")


def largest_array_bytes(type_text: str) -> int:
    """Bytes of the largest array in an HLO result type (a tuple's largest
    element): what the instruction has to write somewhere."""
    best = 0
    for dt, dims in _SHAPE.findall(type_text):
        if dt not in _BYTES:
            continue
        n = _BYTES[dt]
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n)
    return best


def _computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    name = None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def _update_bytes(lines: list[str], only: str | None = None):
    """Bytes of the update operand of a dynamic-update-slice among `lines`
    (one computation; the instruction named `only`, or the first), None if
    there is none: an in-place update writes its update, not its result."""
    types = {m.group(1): m.group(2) for m in map(_INSTR.match, lines) if m}
    for line in lines:
        m = _INSTR.match(line)
        if (m and m.group(3) == "dynamic-update-slice"
                and only in (None, m.group(1))):
            operands = re.findall(r"%([\w.\-]+)", line[m.end():])
            if len(operands) > 1 and operands[1] in types:
                return largest_array_bytes(types[operands[1]])
    return None


def big_movers(hlo: str, floor: int) -> list[tuple[str, bool, str, str, int]]:
    """(computation, in a loop body, opcode, instruction name, bytes) of
    every byte-moving instruction that writes >= `floor` bytes: its result,
    or for a dynamic-update-slice (alone or as a fusion) its update."""
    comps = _computations(hlo)
    # a computation called from a loop body (a nested loop's, a call's)
    # runs once a trip too: close the set over the callee attributes
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    grew = True
    while grew:
        grew = False
        for c in list(bodies):
            for line in comps.get(c, ()):
                for callee in re.findall(
                        r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
                    if callee not in bodies:
                        bodies.add(callee)
                        grew = True
    fused = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", hlo))
    out = []
    for comp, lines in comps.items():
        if comp in fused:
            continue  # a fusion is judged at its call site
        for line in lines:
            m = _INSTR.match(line)
            if not m or m.group(3) not in _MOVERS:
                continue
            n = largest_array_bytes(m.group(2))
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            upd = None
            if m.group(3) == "dynamic-update-slice":
                upd = _update_bytes(lines, m.group(1))
            elif m.group(3) == "fusion" and callee:
                upd = _update_bytes(comps.get(callee.group(1), []))
            n = n if upd is None else upd
            if n >= floor:
                out.append((comp, comp in bodies, m.group(3), m.group(1), n))
    return out


def programs(layers: int, pages: int, slots: int):
    """(bytes of the K pool, [(name, thunk)]) for the cell's engine: the step
    programs aot_check.engine_programs() builds, at the cell's sizes, with
    the hybrid step at a fused-scatter slice (p=16) and an XLA pre-scatter
    one (p=64). Spec-verify only exists on a spec engine, hence K=4."""
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.ops.pallas.paged_attention import pool_lanes

    topo = aot_check.topology()
    cfg = LlamaConfig(dim=4096, hidden_dim=11008, n_layers=layers, n_heads=32,
                      n_kv_heads=32, vocab_size=102400, seq_len=aot_check.SEQ)
    params = aot_check.abstract_params(cfg, aot_check.on_one_chip(topo))
    pool_bytes = (layers * (pages + 1) * cfg.n_kv_heads * 128
                  * pool_lanes(cfg.head_size) * 2)
    return pool_bytes, aot_check.engine_programs(
        topo, "7b", cfg, params, slots, 4, kv_pages=pages, hybrid_p=(16, 64))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=30)
    ap.add_argument("--pages", type=int, default=66)
    ap.add_argument("--slots", type=int, default=12)
    args = ap.parse_args()
    from dllama_tpu.ops import matmul as mmod

    mmod.device_platform = lambda: "tpu"  # the chip is described, not attached
    pool_bytes, progs = programs(args.layers, args.pages, args.slots)
    floor = pool_bytes // args.layers  # one layer's K (or V) slice
    print(f"K pool {pool_bytes / 1e9:.3f} GB (V the same), one layer's slice "
          f"{floor / 1e6:.1f} MB")
    in_loop = 0
    for name, thunk in progs:
        compiled = thunk()
        ma = compiled.memory_analysis()
        print(f"\n== {name}: args {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"out {ma.output_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.3f} GB, TEMP "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB")
        found = big_movers(compiled.as_text(), floor)
        for comp, body, op, iname, n in found:
            where = "LOOP BODY" if body else "outside loops"
            print(f"   {n / 1e6:9.1f} MB  {op:22s} {iname:50s} in {comp} [{where}]")
            in_loop += body
        if not found:
            print("   no instruction moves a layer's slice or more")
    print(f"\n{in_loop} pool-sized or slice-sized moves inside loop bodies")
    return 1 if in_loop else 0


if __name__ == "__main__":
    sys.exit(main())

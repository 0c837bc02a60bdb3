"""A cell's serving programs compiled ON the chip, one after another, with
no model file: the engine of the cell's `serve` line over ZERO weights at the
configuration's published widths, and its warm worklist (`BatchEngine.
_warm_worklist`: every prefill chunk, the first-token sampling, decode and
hybrid programs with and without penalties) lowered and compiled one by one.

Why it exists (PR 43): a whole program can compile for the DESCRIBED chip
(`experiments/aot_check.py`, `tests/test_chip_compile.py`) and still abort
the attached chip's own compile. XLA's memory space assignment died
(`algorithm.cc:5928 Check failed: peak_memory_usage_[i] <= ...`) on every
hybrid program that held two of the expert layer's placement dots, which
only a server start showed, after 5-6 minutes and without the program's
name. The abort kills the process: the last name printed is the program.
Run it before a cell run whenever the XLA ops of a layer body changed.

Usage: chiprun -- python experiments/warm_compile.py CELL [ONLY[,ONLY...]]
       python experiments/warm_compile.py --smoke [ONLY[,ONLY...]]
CELL is one of the benchmark's cells listed in CELLS (or its configuration's name
before the dot); ONLY keeps the programs whose `fn.key.` holds one of the
strings (`hybrid.`, `decode.n4.`, `p256`). 3-5 minutes a cell on one chip.
`--smoke` walks the same code on the CPU over a tiny stack (tier-1).
Prints `<fn> <key> ACCEPT <s>` / `REJECT <error>` a program and `WARM
DONE <accepted>/<programs>`; exits 1 on a REJECT.
"""

import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SMOKE = "--smoke" in sys.argv
if SMOKE:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp

#: cell -> (configuration, its parameters, then what the cell's `serve` line
#: gives: slots, global pages, rows a sequence, rows a slice; a hybrid launch
#: carries at most `hybrid` slice rows)
CELLS = {
    "granite4h.reason_closed": dict(
        cfg="hybrid_cfg", params="hybrid_params", slots=48, pages=456,
        seq=2048, chunk=256, hybrid=64),
    "lagunaxs2.reason_long_closed": dict(
        cfg="attn_kinds_cfg", params="attn_kinds_params", slots=24, pages=816,
        seq=4224, chunk=256, hybrid=256),
    "kimilinear.reason_closed": dict(
        cfg="delta_latent_cfg", params="delta_latent_params", slots=48, pages=456,
        seq=8192, chunk=256, hybrid=64),
    "axk1.long_reason_closed": dict(
        cfg="rot_latent_cfg", params="rot_latent_params", slots=32, pages=2368,
        seq=16384, chunk=512, hybrid=512),
    "smallthinker.long_decode_closed": dict(
        cfg="window_moe_cfg", params="window_moe_params", slots=16, pages=1232,
        seq=16384, chunk=512, hybrid=512),
    # no cache rows: `pages` 0 = full coverage, which costs nothing
    "brumby14b.reason_closed": dict(
        cfg="retention_cfg", params="retention_params", slots=24, pages=0,
        seq=32768, chunk=256, hybrid=64),
}


def build(cell: dict):
    """The cell's engine over zero weights (nothing is read from a file)."""
    import aot_check as A
    from dllama_tpu.engine.batch import BatchEngine

    cfg = getattr(A, cell["cfg"])()
    if SMOKE:  # the same stack, cut to what a CPU compiles in seconds
        cfg = dataclasses.replace(
            A.window_moe_cfg(4), dim=128, hidden_dim=64, n_heads=2, n_kv_heads=1,
            head_dim=64, vocab_size=256, seq_len=256, n_experts=4,
            n_active_experts=2, window=64)
    params = getattr(A, cell["params"])(cfg, lambda shape, dt: jnp.zeros(shape, dt))
    return BatchEngine(cfg, params, n_slots=cell["slots"], max_seq_len=cell["seq"],
                       kv_layout="paged", page_size=cell.get("page", 128),
                       kv_pages=cell["pages"], max_prefill_chunk=cell["chunk"],
                       radix_cache="off")


def main() -> int:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    if SMOKE:
        name, cell = "smoke", dict(CELLS["smallthinker.long_decode_closed"], slots=2,
                                   pages=8, seq=256, chunk=4, hybrid=4, page=16)
    else:
        found = [c for c in CELLS if args and c.startswith(args[0])]
        if len(found) != 1:
            print(__doc__)
            print("cells:", ", ".join(CELLS))
            return 2
        name, cell = found[0], CELLS[found[0]]
    only = args[0 if SMOKE else 1:][:1]
    only = only[0].split(",") if only else []
    be = build(cell)
    print(f"warm_compile {name}: route {be.kernel_route}", flush=True)
    accepted = programs = 0
    for fn, key, thunk in be._warm_worklist(4, cell["hybrid"]):
        if only and not any(o in f"{fn}.{key}." for o in only):
            continue
        t0 = time.perf_counter()
        print(fn, key, "...", flush=True)
        lowered = thunk(lower=True)
        if lowered is None:
            print(fn, key, "eager", flush=True)
            continue
        programs += 1
        try:
            lowered.compile()
            accepted += 1
            print(fn, key, "ACCEPT", f"{time.perf_counter() - t0:.0f}s", flush=True)
        except Exception as ex:  # the compiler's own refusal, by name
            print(fn, key, "REJECT", repr(ex)[:300], flush=True)
    print(f"WARM DONE {accepted}/{programs}", flush=True)
    return 0 if accepted == programs else 1


if __name__ == "__main__":
    sys.exit(main())

"""Admission-stall A/B bench: what do decoding batch-mates experience while a
long prompt joins the batch? (VERDICT r3 #4 / weak #5.)

Runs the serving tier three times — 'synchronous' (legacy: the whole chunked
prefill runs between two decode chunks), 'strict' (one prefill chunk per
decode chunk; the r4 default whose joiner TTFT was unbounded, r4 weak #3) and
'paced' (the shipped default: prefill chunks pumped per visit until the
scheduler's stall budget is spent) — and reports, for each mode:

* client_gap_ms_max — the largest inter-token gap a DECODING request's
  stream observed while the admission was in flight (chunk-granular, i.e.
  the stall a user actually sees), vs its pre-admission baseline gap.
* scheduler admission_stall_ms_max/mean — the decode-to-decode gaps the
  scheduler attributed to admission work.

It finishes with the overlap-pipeline A/B (bench.bench_overlap): aggregate
decode tok/s and the inter-chunk host gap with the scheduler's overlapped
dispatch on vs off — same prompts/seeds, identical token streams, so the
delta is pure pipeline efficiency.

The reference has no analog tier (its server is single-request blocking,
dllama-api.cpp:522-533); this bench exists to prove the non-blocking claim
with numbers. Window config (TPU): ABENCH_PRESET=8b ABENCH_SLOTS=32
ABENCH_PROMPT=2048. '--smoke' runs a seconds-scale CPU config in CI.
"""

import os
import sys
import time

import numpy as np

t0 = time.time()
import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    smoke = "--smoke" in sys.argv
    print("devices:", jax.devices(), f"({time.time()-t0:.0f}s)", flush=True)

    import jax.numpy as jnp

    from bench import PRESETS
    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import random_params_fast
    from dllama_tpu.obs.compile import place_compile_cache
    from dllama_tpu.serve.scheduler import Scheduler

    place_compile_cache()

    if smoke:
        # ONE protocol with bench.bench_admission (bench.ADMISSION_PROTOCOL):
        # the bench `admission` record and this experiment must be the same
        # experiment, or their headline ratios drift apart again (the
        # BENCH_r05 1.1x vs ADMISSION_CPU.md PASS confusion — see the
        # "Reconciliation (r6)" section there)
        from bench import ADMISSION_PROTOCOL as _P

        preset = "tiny"
        n_slots, prompt_len, chunk, pf_chunk, bg_steps = (
            _P["n_slots"], _P["prompt_len"], _P["chunk"], _P["pf_chunk"],
            _P["bg_steps"])
    else:
        preset = os.environ.get("ABENCH_PRESET", "8b")
        n_slots = int(os.environ.get("ABENCH_SLOTS", "32"))
        prompt_len = int(os.environ.get("ABENCH_PROMPT", "2048"))
        chunk = int(os.environ.get("ABENCH_CHUNK", "4"))
        pf_chunk = 256
        bg_steps = 256
    cfg = LlamaConfig(**PRESETS[preset])
    if prompt_len >= cfg.seq_len - bg_steps:
        prompt_len = cfg.seq_len - bg_steps - 8
    params = random_params_fast(cfg, seed=0, dtype=jnp.bfloat16)
    print(f"params ready: {preset} slots={n_slots} prompt={prompt_len} "
          f"({time.time()-t0:.0f}s)", flush=True)

    from bench import admission_streams

    # distinct-prefix streams + full pow-2 width warmup shared with
    # bench.bench_admission (prefix-cache reuse would gut the A/B otherwise)
    warm_prompt, bg_maker, long_prompt = admission_streams(cfg, pf_chunk, prompt_len)

    def run(mode: str, **kw) -> dict:
        eng = BatchEngine(cfg, params, n_slots=n_slots, cache_dtype=jnp.bfloat16,
                          max_prefill_chunk=pf_chunk)
        sched = Scheduler(eng, chunk=chunk, **kw)
        try:
            w = sched.submit(warm_prompt, 0.0, 0.9, chunk, frozenset(), seed=7)
            list(w.tokens())
            sched.reset_latency_stats()  # compile gaps are not stalls
            bg = [
                sched.submit(bg_maker(s), 0.8, 0.9, bg_steps, frozenset(), seed=s)
                for s in range(max(1, n_slots // 2))
            ]
            # timestamp bg[0]'s stream at chunk granularity
            stamps: list[float] = []
            it = bg[0].tokens()
            warm_tokens = max(4, 4 * chunk)
            for _ in range(warm_tokens):
                next(it)
                stamps.append(time.perf_counter())
            t_admit = time.perf_counter()
            r_long = sched.submit(long_prompt, 0.0, 0.9, 2 * chunk, frozenset(), seed=99)
            for tok in it:
                stamps.append(time.perf_counter())
            long_toks = list(r_long.tokens())
            for r in bg[1:]:
                list(r.tokens())
            arr = np.asarray(stamps)
            gaps = np.diff(arr) * 1000.0
            before = gaps[arr[1:] <= t_admit]
            after = gaps[arr[1:] > t_admit]
            s = sched.latency_summary()
            return {
                "mode": mode,
                "client_gap_ms_base": round(float(np.max(before)), 1) if before.size else None,
                "client_gap_ms_max": round(float(np.max(after)), 1) if after.size else None,
                "sched_stall_ms_max": round(s["admission_stall_ms_max"], 1)
                if s["admission_stall_ms_max"] else None,
                "sched_stall_ms_mean": round(s["admission_stall_ms_mean"], 1)
                if s["admission_stall_ms_mean"] else None,
                "long_ttft_ms": round(r_long.ttft_ms, 1),
                "long_tokens": len(long_toks),
            }
        finally:
            sched.shutdown()

    from bench import ADMISSION_MODES

    # same policy table as bench.bench_admission; 'sync' reads better as
    # 'synchronous' in these human-facing rows
    modes = {("synchronous" if m == "sync" else m): kw
             for m, kw in ADMISSION_MODES.items()}
    rows = {}
    for mode, kw in modes.items():
        try:
            r = run(mode, **kw)
            rows[mode] = r
            print(r, flush=True)
        except Exception as e:
            print(f"{mode}: FAILED {e!r}"[:300], flush=True)

    # overlap-pipeline A/B (shared with bench.py's `overlap` record):
    # inter-chunk host gap + aggregate tok/s, overlapped dispatch on vs off
    from bench import bench_overlap

    try:
        ov = bench_overlap(cfg, params, n_slots=n_slots, chunk=chunk,
                           steps=(24 if smoke else 128), pf_chunk=pf_chunk)
        print({"overlap_ab": ov}, flush=True)
        on, off = ov.get("overlap_on", {}), ov.get("overlap_off", {})
        if "agg_tok_s" in on and "agg_tok_s" in off:
            print(f"overlap host-gap reduction: "
                  f"{ov.get('host_gap_reduction_x')}x "
                  f"(mean {off.get('host_gap_ms_mean')}ms -> "
                  f"{on.get('host_gap_ms_mean')}ms); "
                  f"agg tok/s on/off: {ov.get('tok_s_ratio_on_off')}", flush=True)
    except Exception as e:
        print(f"overlap A/B: FAILED {e!r}"[:300], flush=True)
    if len(rows) == 3 and all(r["client_gap_ms_max"] is not None
                              for r in rows.values()):
        # timer-noise floor: a 0.0 best-case yields a large finite ratio
        gap = {m: rows[m]["client_gap_ms_max"] for m in rows}
        ttft = {m: rows[m]["long_ttft_ms"] for m in rows}
        print(f"stall reduction (sync/paced): {gap['synchronous'] / max(gap['paced'], 0.05):.1f}x",
              flush=True)
        # the r4 weak-#3 acceptance bar: the default (paced) must keep BOTH
        # metrics within 2x of the best mode for that metric
        best_gap, best_ttft = min(gap.values()), min(ttft.values())
        ok = (gap["paced"] <= 2 * max(best_gap, 0.05)
              and ttft["paced"] <= 2 * max(best_ttft, 0.05))
        print(f"paced within 2x of best on stall ({gap['paced']:.1f} vs {best_gap:.1f}) "
              f"and ttft ({ttft['paced']:.1f} vs {best_ttft:.1f}): "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
    print(f"ABENCH DONE fails={3 - len(rows)}", flush=True)


if __name__ == "__main__":
    main()

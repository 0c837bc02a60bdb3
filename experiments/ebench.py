"""Engine-knob A/B on the real TPU: ONE process, one 1B param set, a matrix
of (layer_unroll, attn_impl, q40 style) combos timed through the production
InferenceEngine. Each combo prints (flushed) as soon as it's measured, so a
run cut short keeps its earlier rows.

Usage: python experiments/ebench.py [n_decode]
"""

import os
import sys
import time

import numpy as np

t0 = time.time()
import jax
import jax.numpy as jnp

print("devices:", jax.devices(), f"({time.time()-t0:.0f}s)", flush=True)

from dllama_tpu.engine.engine import InferenceEngine
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params_fast
from dllama_tpu.obs.compile import place_compile_cache
from dllama_tpu.ops import layers as layers_mod
from dllama_tpu.ops.pallas import q40_matmul as qmod

place_compile_cache()

N_DECODE = int(sys.argv[1]) if len(sys.argv) > 1 else 64

if os.environ.get("EBENCH_TINY") == "1":  # CPU smoke of the harness itself
    cfg = LlamaConfig(dim=256, hidden_dim=512, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=512, seq_len=128)
else:
    cfg = LlamaConfig(dim=2048, hidden_dim=8192, n_layers=16, n_heads=32,
                      n_kv_heads=8, vocab_size=128256, seq_len=1024)
params = random_params_fast(cfg, seed=0, dtype=jnp.bfloat16)
print(f"params ready ({time.time()-t0:.0f}s)", flush=True)

# (label, unroll, attn_impl, style, fuse)
COMBOS = [
    ("base u1 flash bd", 1, "auto", "auto", False),
    ("fused-qkv-w13", 1, "auto", "auto", True),
    ("fused+u4", 4, "auto", "auto", True),
    ("u4", 4, "auto", "auto", False),
    ("ufull", True, "auto", "auto", False),
    ("jnp-attn", 1, "jnp", "auto", False),
    ("maskdot", 1, "auto", "maskdot", False),
    ("loopdot", 1, "auto", "loopdot", False),
    ("deq-decode", 1, "auto", "deq", False),
    # reserve Pallas rms_norm (VERDICT r3 weak #8): flip only on a win here
    ("pallas-norm", 1, "auto", "auto", False),
]

PROMPT_LEN = min(512, cfg.seq_len // 2)
prompt = (np.arange(1, PROMPT_LEN + 1, dtype=np.int32)[None]) % cfg.vocab_size
first = np.array([[1]], np.int32)

fails = []
for label, unroll, attn, style, fuse in COMBOS:
    qmod.STYLE = style
    layers_mod.RMS_NORM_IMPL = "pallas" if label == "pallas-norm" else "jnp"
    try:
        eng = InferenceEngine(cfg, params, cache_dtype=jnp.bfloat16,
                              max_prefill_chunk=512, layer_unroll=unroll,
                              attn_impl=attn, fuse_weights=fuse)
        tc = time.perf_counter()
        eng.prefill(prompt)
        eng.decode_greedy_n(first, N_DECODE)
        compile_s = time.perf_counter() - tc
        eng.reset(0)
        tp = time.perf_counter()
        eng.prefill(prompt)
        jax.block_until_ready(eng.cache.k)
        t_pre = time.perf_counter() - tp
        td = time.perf_counter()
        eng.decode_greedy_n(first, N_DECODE)
        t_dec = time.perf_counter() - td
        print(f"{label}: decode={1000*t_dec/N_DECODE:.2f}ms/tok "
              f"({N_DECODE/t_dec:.0f}tok/s) prefill={PROMPT_LEN/t_pre:.0f}tok/s "
              f"compile={compile_s:.0f}s", flush=True)
        del eng
    except Exception as e:
        fails.append(label)
        print(f"{label}: FAILED {e!r}"[:300], flush=True)
    finally:
        qmod.STYLE = "auto"
        layers_mod.RMS_NORM_IMPL = "jnp"

# machine-checkable completion marker: the CI smoke asserts fails=0; in a live
# window partial failure still exits 0 so later session stages run (tee'd log
# keeps the rows that did measure)
print(f"EBENCH DONE fails={len(fails)}" + (" " + ",".join(fails) if fails else ""),
      flush=True)

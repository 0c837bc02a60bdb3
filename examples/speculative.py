"""Speculative-decoding demo: same tokens, fewer forwards.

Runs plain fused greedy decode and prompt-lookup speculative decode
(engine/speculative.py) on a repetitive prompt and a random prompt, prints
tokens/forward and agreement. Synthetic weights — output ids are noise, the
point is the EXACTNESS (identical streams) and the forward-count accounting.

    JAX_PLATFORMS=cpu python examples/speculative.py
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax.numpy as jnp

from dllama_tpu.engine.engine import InferenceEngine
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params

cfg = LlamaConfig(dim=128, hidden_dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=256, seq_len=256)
params = random_params(cfg, seed=0, dtype=jnp.bfloat16, quantize=True)

for label, prompt in (
    ("repetitive", ([17, 23, 5, 9] * 10)[:40]),
    ("random", list(np.random.default_rng(0).integers(1, cfg.vocab_size, 40))),
):
    eng = InferenceEngine(cfg, params, cache_dtype=jnp.bfloat16)
    logits = eng.prefill(np.asarray([prompt], np.int32))
    first = int(np.argmax(np.asarray(logits)[0]))
    ref = [int(t) for t in eng.decode_greedy_n(np.array([[first]]), 48)[:, 0]]

    eng2 = InferenceEngine(cfg, params, cache_dtype=jnp.bfloat16)
    eng2.prefill(np.asarray([prompt], np.int32))
    got = [int(t) for t in eng2.decode_spec_greedy_n(list(prompt), first, 48, k=8)]
    st = eng2._spec_stats
    print(f"{label:>10}: identical={got == ref}  "
          f"tokens/forward={st['emitted'] / st['cycles']:.2f}  "
          f"({st['emitted']} tokens in {st['cycles']} forwards vs 48 plain)")

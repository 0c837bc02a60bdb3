"""What the architecture test files share (helpers, not tests): a tiny file
written through the benchmark's layout and loaded as `benchmark/check.py`
wants it, the check against the plain reference by route, and the one
forward over 60 tokens that the controls are read on.

The sizes of the check are set HERE, by what each route's run is there to
show. Every `check.run` builds a BatchEngine of its own and compiles its
programs anew (one a prefill-chunk length, one decode chunk), and in
interpret mode a program's compile is most of a test: a run pays for each
chunk length it brings and little for a step.
"""

import importlib
import types

import jax.numpy as jnp
import numpy as np

from benchmark import check, files
from dllama_tpu.engine.engine import InferenceEngine
from dllama_tpu.models import formats
from dllama_tpu.models.llama import KVCache, forward
from dllama_tpu.ops.layers import build_rope_cache

#: CPU readings, float32 weights and activations, seed 5: the sound model
#: reads 4e-7 to 4e-6 on both routes of every architecture, the controls
#: 0.01 to 1.3 (each file names its own readings)
TOL = {"rel_l2_mean": 1e-4, "deficit_sigma_mean": 1e-3}
#: pages of 8 rows and prefill slices of at most 16, so that a prompt of 24
#: is already two slices and three pages
ENGINE = dict(n_slots=4, kv_layout="paged", page_size=8, kv_pages=120,
              radix_cache="auto", max_prefill_chunk=16)
#: the check's sizes by `kernels`, that is by what the run shows:
#: - "xla": the serving path's own arithmetic against the reference at 1e-6
#:   OVER LENGTH: prompts of one slice, of three and of seven (chunk lengths
#:   16, 8, 4, 1), 64 decode steps with the slots at different lengths, the
#:   tail on the kept rows;
#: - "pallas": the kernels (interpret mode) reach the same reading in
#:   float32, and bf16's rounding at the stated precision (bfloat16
#:   activations, the grouped Q40 expert kernel): two prompts of more than
#:   one slice each and of different lengths (24 = 16 + 8, 40 = 16 + 16 + 8:
#:   the tail's 8 is a chunk length they already brought), 20 decode steps =
#:   two page boundaries and a part of the third for each slot (and, where the
#:   model has a 16-row window, every step past it and two pages a slot
#:   handed back), the tail.
CHECK = {
    "xla": {"prompt_lengths": [9, 40, 100], "decode_steps": 64, "tail_tokens": 7},
    "pallas": {"prompt_lengths": [24, 40], "decode_steps": 20, "tail_tokens": 7},
}


def loaded(path, dtype=jnp.float32):
    """The file as `benchmark/check.py` wants it: config, params, an engine
    that names the cache dtype and the rows a sequence may hold."""
    cfg, header = formats.read_header(path, 256)
    params = formats.load_params(path, cfg, header, dtype=dtype)
    eng = InferenceEngine(cfg, params, cache_dtype=dtype, max_seq_len=256)
    return types.SimpleNamespace(path=path, config=cfg, params=params, engine=eng)


def tiny_file(tmp_path_factory, name, config, seed=5):
    """`config` written through its layout under a directory `name`, loaded
    in float32 (so that the program's own arithmetic reads against the
    reference at 1e-6 and each control stands out)."""
    path = str(tmp_path_factory.mktemp(name) / "tiny.m")
    files.write_model(path, config, seed)
    return loaded(path)


def tokens(n, seed=0, hi=250):
    return np.random.default_rng(seed).integers(1, hi, n).tolist()


def run_check(tiny, config, kernels, attn_impl, tolerances=TOL):
    """`benchmark/check.py`'s whole check of `tiny` at CHECK[kernels]."""
    cfg = dict(config, engine=dict(ENGINE, kernels=kernels, attn_impl=attn_impl),
               check=CHECK[kernels], tolerances=tolerances)
    return check.run(tiny, cfg, tiny.path, 5)


def sixty(tiny, config):
    """60 tokens (past a 16-row window, past 32 original positions) and the
    reference's logits at the last."""
    ref = importlib.import_module(config["reference"])
    seq = np.asarray(tokens(60, seed=3), np.int32)
    return seq, ref.logits_at(tiny.path, [seq], [[59]])[0][0]


def logits_rel_l2(params, cfg, seq, want, rope=None):
    """One forward over `seq` on the dense jnp route: the last row's
    relative L2 error against `want`."""
    cache = KVCache.create(cfg, 1, jnp.float32, 128)
    got, _ = forward(cfg, params, jnp.asarray(seq[None]), 0, cache,
                     build_rope_cache(cfg, 128) if rope is None else rope)
    return check.rel_l2(np.asarray(got[0, -1]), want)

"""Pipeline parallelism: GPipe-style stage-split inference over the 'pp' axis.

The reference has NO pipeline axis — every node executes every layer in
lockstep (SURVEY.md §2.4 positions dllama *against* layer-split designs
because on 1GbE the per-layer activation hop would dominate). On TPU the
tradeoff flips: stages map to pods/slices linked by ICI/DCN and a
`ppermute` activation hop is cheap, so PP is the axis that scales *depth*
(70B/405B across pods) where TP scales width.

Design: the stacked per-layer params and KV cache keep their layout — the
leading layer axis is simply sharded over 'pp' (stage s owns layers
[s*L/pp, (s+1)*L/pp)). Inside one jitted shard_map:

  step t: stage 0 injects microbatch t (embedding lookup), every stage runs
  its layer slice on its in-flight activation, activations hop one stage via
  non-cyclic ppermute, the last stage banks finished microbatches. After
  M + pp - 1 steps the last stage norms + projects logits, broadcast by a
  masked psum. Cache writes are masked on inactive (bubble) steps, so the
  schedule is exact, not approximate.

Microbatches split the *batch* axis (all sequences share one position, so
decode with B=1 degenerates to sequential layer-split — the PP bubble is the
price of depth; throughput serving should drive PP with B >= pp).

Composition: the shard_map is *partial-manual* — only 'pp' is a manual axis
(`axis_names={'pp'}`); tp/dp stay under GSPMD, so weights placed with
P('pp', ..., 'tp') compose stage-split with tensor-parallel automatically
(the matmul psum over 'tp' is inserted by XLA inside each stage). pp x sp is
rejected by LlamaShardings (ring attention inside a manual stage is not
supported).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import KVCache, run_layers
from dllama_tpu.ops.layers import rms_norm
from dllama_tpu.ops.matmul import matmul
from dllama_tpu.ops.quant import QTensor


def _shift_right(x: jax.Array, pp: int) -> jax.Array:
    """Send to the next stage; stage 0 receives zeros (non-cyclic edge)."""
    return jax.lax.ppermute(x, "pp", [(i, i + 1) for i in range(pp - 1)])


def _stage_body(cfg: LlamaConfig, attn_fn, mm, layers, x, pos, k, v, rope):
    x, k, v, _ = run_layers(cfg, layers, x, pos, k, v, rope, attn_fn, mm=mm)
    return x, k, v


def make_pp_forward(cfg: LlamaConfig, mesh: Mesh, n_micro: int = 1, attn_fn=None, mm=None):
    """Build `fn(params, tokens, pos, cache, rope_cache) -> (logits, cache)`.

    params: the standard stacked pytree, with every `layers` leaf and the
    cache sharded P('pp', ...) on the layer axis (see `pp_param_specs`).
    tokens: [B, T] with B % n_micro == 0.
    """
    pp = mesh.shape["pp"]
    if cfg.n_layers % pp != 0:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={pp}")

    def fn(params, tokens, pos, cache: KVCache, rope_cache):
        b, t = tokens.shape
        if b % n_micro != 0:
            raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
        mbs = b // n_micro
        rope = jax.lax.dynamic_slice_in_dim(rope_cache, pos, t, axis=0)

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(), params["embedding"]),
                jax.tree.map(
                    lambda _: P("pp"),
                    params["layers"],
                    is_leaf=lambda l: isinstance(l, QTensor),
                ),
                P(),  # final_norm
                jax.tree.map(lambda _: P(), params["wcls"], is_leaf=lambda l: isinstance(l, QTensor)),
                P(),  # tokens
                P("pp"),  # k cache (layer axis)
                P("pp"),  # v cache
                P(),  # rope rows
            ),
            out_specs=(P(), P("pp"), P("pp")),
            axis_names=frozenset({"pp"}),  # tp/dp stay GSPMD-auto inside stages
            check_vma=False,
        )
        def pipeline(embedding, layers, final_norm, wcls, toks, k_all, v_all, rope_rows):
            stage = jax.lax.axis_index("pp")
            toks_mb = toks.reshape(n_micro, mbs, t)
            x = jnp.zeros((mbs, t, cfg.dim), embedding.dtype)
            out = jnp.zeros((n_micro, mbs, t, cfg.dim), embedding.dtype)

            for step in range(n_micro + pp - 1):
                m_in = jnp.clip(step - stage, 0, n_micro - 1)
                active = (step >= stage) & (step - stage < n_micro)
                # stage 0 injects microbatch `step` (if any); others use recv
                inject = embedding[toks_mb[jnp.clip(step, 0, n_micro - 1)]]
                x = jnp.where((stage == 0) & active, inject, x)

                # batch-slice of this stage's cache for the in-flight microbatch
                k_mb = jax.lax.dynamic_slice_in_dim(k_all, m_in * mbs, mbs, axis=1)
                v_mb = jax.lax.dynamic_slice_in_dim(v_all, m_in * mbs, mbs, axis=1)
                y, k_new, v_new = _stage_body(cfg, attn_fn, mm, layers, x, pos, k_mb, v_mb, rope_rows)
                # bubble steps must not touch the cache
                k_upd = jax.lax.dynamic_update_slice_in_dim(k_all, k_new, m_in * mbs, axis=1)
                v_upd = jax.lax.dynamic_update_slice_in_dim(v_all, v_new, m_in * mbs, axis=1)
                k_all = jnp.where(active, k_upd, k_all)
                v_all = jnp.where(active, v_upd, v_all)

                # last stage banks its finished microbatch
                m_out = step - (pp - 1)
                banked = jax.lax.dynamic_update_slice_in_dim(
                    out, y[None], jnp.clip(m_out, 0, n_micro - 1), axis=0
                )
                out = jnp.where((stage == pp - 1) & (m_out >= 0), banked, out)

                x = _shift_right(y, pp)

            h = rms_norm(out.reshape(b, t, cfg.dim), final_norm, cfg.norm_epsilon)
            logits = (mm or matmul)(h, wcls).astype(jnp.float32)
            # only the last stage holds real logits; broadcast via masked psum
            logits = jax.lax.psum(
                jnp.where(stage == pp - 1, logits, jnp.zeros_like(logits)), "pp"
            )
            return logits, k_all, v_all

        logits, k_new, v_new = pipeline(
            params["embedding"],
            params["layers"],
            params["final_norm"],
            params["wcls"],
            tokens,
            cache.k,
            cache.v,
            rope,
        )
        return logits, KVCache(k_new, v_new)

    return fn


def pp_param_specs(params) -> dict:
    """PartitionSpec tree for pp placement: layer-stacked leaves on 'pp',
    everything else replicated."""

    def rep(leaf):
        return QTensor(P(), P()) if isinstance(leaf, QTensor) else P()

    def staged(leaf):
        s = P("pp")
        return QTensor(s, s) if isinstance(leaf, QTensor) else s

    is_q = lambda l: isinstance(l, QTensor)
    return {
        "embedding": rep(params["embedding"]),
        "final_norm": P(),
        "wcls": rep(params["wcls"]),
        "layers": jax.tree.map(staged, params["layers"], is_leaf=is_q),
    }


def put_pp(params, cache: KVCache, mesh: Mesh):
    """Place params + cache for the pipeline mesh."""
    specs = pp_param_specs(params)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    cs = NamedSharding(mesh, P("pp"))
    cache = KVCache(jax.device_put(cache.k, cs), jax.device_put(cache.v, cs))
    return params, cache

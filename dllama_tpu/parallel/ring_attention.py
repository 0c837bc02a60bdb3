"""Ring / sequence-parallel attention — the long-context capability the
reference explicitly lacks (SURVEY.md §5.7: its only lever is `--max-seq-len`
RAM clamping; each node holds the full sequence of its KV-head slice,
nn-core.cpp:170-177).

Two primitives, both exact (online-softmax rescaling, f32 accumulation):

* :func:`ring_attention` — blockwise causal attention with queries AND keys
  sharded over the `sp` axis; KV blocks rotate around the ring with
  `lax.ppermute` while each shard accumulates its queries' partial softmax.
  O(S/sp) memory per device, comm overlapped with the next block's compute by
  XLA. This is the prefill path for sequences that don't fit one device.

* :func:`sp_cache_attention` — decode/chunked-prefill attention over a KV
  *cache* whose sequence axis is sharded on `sp` (replicated queries): each
  shard computes a partial (numerator, max, denominator) over its cache slice,
  merged with one `pmax` + `psum` of per-head scalars — tiny collectives vs.
  all-gathering the cache.

Both run inside `jax.shard_map`; `NEG` is the mask value (finite, so fully
masked shards produce exp(NEG-m)=0 instead of NaN).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG = -1e30


def _block_scores(q, k, scale):
    """q [B,T,Hkv,G,d] x k [B,Hkv,S,d] -> scores f32 [B,Hkv,G,T,S]."""
    return jnp.einsum(
        "bthgd,bhsd->bhgts",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale


def _merge(acc, o, m, l):
    """Online-softmax merge of a new block's (unnormalized out, max, denom)."""
    o0, m0, l0 = acc
    m_new = jnp.maximum(m0, m)
    a0 = jnp.exp(m0 - m_new)
    a1 = jnp.exp(m - m_new)
    return (
        o0 * a0[..., None] + o * a1[..., None],
        m_new,
        l0 * a0 + l * a1,
    )


def _partial_attn(q, k, v, mask, scale):
    """-> (o_unnorm [B,Hkv,G,T,d], m [B,Hkv,G,T], l [B,Hkv,G,T])."""
    s = jnp.where(mask, _block_scores(q, k, scale), NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)  # kill exp(NEG-NEG)=1 rows where all-masked
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgts,bhsd->bhgtd", p, v.astype(jnp.float32))
    return o, m, l


def ring_attention(
    q: jax.Array,  # [B, Tl, Hq, d] this shard's query block (global pos = idx*Tl + t)
    k: jax.Array,  # [B, Hkv, Sl, d] this shard's KV block (same global layout)
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Exact blockwise-causal attention over the ring; call inside shard_map.

    Sequence layout: device i of the sp axis owns tokens [i*Tl, (i+1)*Tl).
    Each of the `sp` steps attends local queries to one rotating KV block and
    merges with the running softmax state; `ppermute` shifts KV to the next
    neighbor so every (query block, kv block) pair meets exactly once.
    """
    b, tl, hq, d = q.shape
    hkv, sl = k.shape[1], k.shape[2]
    g = hq // hkv
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, tl, hkv, g, d)
    q_pos = idx * tl + jax.lax.broadcasted_iota(jnp.int32, (tl, sl), 0)

    o = jnp.zeros((b, hkv, g, tl, d), jnp.float32)
    m = jnp.full((b, hkv, g, tl), NEG, jnp.float32)
    l = jnp.zeros((b, hkv, g, tl), jnp.float32)
    acc = (o, m, l)

    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        src = (idx - step) % sp  # owner of the KV block currently held
        if causal:
            k_pos = src * sl + jax.lax.broadcasted_iota(jnp.int32, (tl, sl), 1)
            mask = (k_pos <= q_pos)[None, None, None]
        else:
            mask = jnp.ones((1, 1, 1, tl, sl), bool)
        acc = _merge(acc, *_partial_attn(qg, k, v, mask, scale))
        if step + 1 < sp:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)

    o, m, l = acc
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tl, hq, d).astype(q.dtype)


def sp_cache_attention(
    q: jax.Array,  # [B, T, Hq, d] replicated over sp
    k_cache: jax.Array,  # [B, Hkv, Sl, d] local seq shard of the cache
    v_cache: jax.Array,
    pos_base: jax.Array,  # scalar i32 — absolute position of query 0
    *,
    axis_name: str = "sp",
) -> jax.Array:
    """GQA over an sp-sharded KV cache; call inside shard_map.

    Replaces a full-cache gather with an LSE merge: pmax of per-row maxima,
    psum of the rescaled numerator/denominator (scaling-book flash-decoding
    recipe). Exact vs. single-device softmax.
    """
    b, t, hq, d = q.shape
    hkv, sl = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, t, hkv, g, d)
    slot = idx * sl + jax.lax.broadcasted_iota(jnp.int32, (t, sl), 1)
    limit = pos_base + jax.lax.broadcasted_iota(jnp.int32, (t, sl), 0)
    mask = (slot <= limit)[None, None, None]

    o, m, l = _partial_attn(qg, k_cache, v_cache, mask, scale)
    m_g = jax.lax.pmax(m, axis_name)
    a = jnp.exp(m - m_g)
    num = jax.lax.psum(o * a[..., None], axis_name)
    den = jax.lax.psum(l * a, axis_name)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, d).astype(q.dtype)


def ring_cache_attention(
    q: jax.Array,  # [B, Tl, Hq, d] this shard's slice of the chunk's queries
    k_cache: jax.Array,  # [B, Hkv, Sl, d] local seq shard of the cache
    v_cache: jax.Array,
    pos_base: jax.Array,  # scalar i32 — absolute position of the chunk's query 0
    *,
    axis_name: str = "sp",
) -> jax.Array:
    """Chunked-prefill attention with queries sequence-sharded over `sp` and
    the KV *cache* ring-rotating; call inside shard_map.

    The chunk's own keys are already written into the sp-sharded cache (the
    cache update runs before attention in models/llama._layer), so each of the
    `sp` steps attends local queries to one rotating cache block — masked to
    global slots <= the query's absolute position — and merges the partial
    softmax. vs. sp_cache_attention this also parallelizes the *query* axis:
    qkv/FFN matmuls upstream shard over sp instead of being replicated, which
    is the long-context prefill capability the reference lacks (SURVEY §5.7).
    """
    b, tl, hq, d = q.shape
    hkv, sl = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, tl, hkv, g, d)
    q_pos = pos_base + idx * tl + jax.lax.broadcasted_iota(jnp.int32, (tl, sl), 0)

    o = jnp.zeros((b, hkv, g, tl, d), jnp.float32)
    m = jnp.full((b, hkv, g, tl), NEG, jnp.float32)
    l = jnp.zeros((b, hkv, g, tl), jnp.float32)
    acc = (o, m, l)

    k, v = k_cache, v_cache
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        src = (idx - step) % sp  # owner of the cache block currently held
        slot = src * sl + jax.lax.broadcasted_iota(jnp.int32, (tl, sl), 1)
        mask = (slot <= q_pos)[None, None, None]
        acc = _merge(acc, *_partial_attn(qg, k, v, mask, scale))
        if step + 1 < sp:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)

    o, m, l = acc
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tl, hq, d).astype(q.dtype)


def make_sp_attention(mesh, cache_batch_spec=None):
    """Build the shard_map-wrapped attention for llama.forward's `attn_fn` slot.

    Specs mirror LlamaShardings.cache_spec: cache [B, Hkv, S, d] ->
    P(dp?, 'tp', 'sp', None). Dispatch is static on the chunk width T:
    multi-token chunks divisible by sp take :func:`ring_cache_attention`
    (queries sharded over sp — true sequence-parallel prefill); decode and
    ragged chunks take :func:`sp_cache_attention` (replicated queries, LSE
    merge over the cache shards).
    """
    dp = cache_batch_spec
    sp = mesh.shape["sp"]

    def attn(q, k_cache, v_cache, pos_base):
        t = q.shape[1]  # static under jit
        if t > 1 and t % sp == 0:
            return jax.shard_map(
                partial(ring_cache_attention, axis_name="sp"),
                mesh=mesh,
                in_specs=(P(dp, "sp", "tp", None), P(dp, "tp", "sp", None),
                          P(dp, "tp", "sp", None), P()),
                out_specs=P(dp, "sp", "tp", None),
            )(q, k_cache, v_cache, pos_base)
        return jax.shard_map(
            partial(sp_cache_attention, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(dp, None, "tp", None), P(dp, "tp", "sp", None), P(dp, "tp", "sp", None), P()),
            out_specs=P(dp, None, "tp", None),
        )(q, k_cache, v_cache, pos_base)

    return attn

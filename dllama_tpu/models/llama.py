"""Llama decoder forward pass — the graph the reference builds as data
(buildLlmNet, llm.cpp:125-436) expressed as one scanned, jittable function.

Per layer (mirrors the reference's att+ff segments, SURVEY.md §3.4):
  x += wo( attention( rope(q), rope(k)→cache, v→cache ) )   [att segment]
  x += w2( act(w1 h) * w3 h )                               [ff segment]
with pre-RMSNorm before each block. The reference's SYNC_NODE_SLICES
all-gathers don't appear here — under pjit the tensor-parallel collectives are
inserted by XLA from the weight/cache shardings (parallel/sharding.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.ops.layers import activation, apply_rope, gqa_attention, moe_ffn, rms_norm
from dllama_tpu.ops.matmul import matmul


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """[n_layers, batch, n_kv_heads, seq_len, head_size] per tensor.

    Functional stand-in for the reference's per-layer k/v buffers written
    through position-indexed dynamic pointers (nn-cpu.cpp:198-222); here the
    write is a donated dynamic_update_slice at pos, which XLA turns into an
    in-place HBM update.
    """

    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int, dtype=jnp.bfloat16, seq_len: int | None = None):
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, seq_len or cfg.seq_len, cfg.head_size)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    @property
    def seq_len(self) -> int:
        return self.k.shape[3]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Paged KV layout: one global page pool
    [n_layers, n_pages + 1, n_kv_heads, page_size, head_size] per tensor plus
    per-slot block tables [n_slots, max_blocks] (i32 page indices, logical
    block b of slot s lives in pool page tables[s, b]).

    A slot reserves nothing up front — the engine-side allocator
    (engine/batch.PagePool) hands out pages as positions advance and
    refcounts them, so idle context windows cost no HBM and a shared prefix
    is ONE set of pages referenced by many tables (vLLM's PagedAttention
    layout, Kwon et al. 2023). The LAST pool page is the trash page: masked
    writes (inactive slots) scatter there instead of paying a
    whole-pool ``where``; the allocator never hands it out.

    Unallocated table entries point at page 0: reads through them surface
    whatever that page holds, which the causal mask zeroes exactly (stale
    pool values are finite, and softmax assigns masked positions
    probability 0.0 — so paged attention is bit-exact vs dense)."""

    k: jax.Array
    v: jax.Array
    tables: jax.Array  # i32 [n_slots, max_blocks]

    def tree_flatten(self):
        return (self.k, self.v, self.tables), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def create(cls, cfg: LlamaConfig, n_slots: int, n_pages: int,
               page_size: int, dtype=jnp.bfloat16, max_blocks: int = 0,
               lanes: int = 0):
        """``lanes`` widens the row (minor) dim past head_size — the paged
        Pallas kernel needs whole 128-lane rows
        (ops/pallas/paged_attention.pool_lanes); 0 = head_size."""
        shape = (cfg.n_layers, n_pages + 1, cfg.n_kv_heads, page_size,
                 lanes or cfg.head_size)
        tables = jnp.zeros((n_slots, max_blocks or 1), jnp.int32)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), tables)

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @property
    def n_pages(self) -> int:
        """Usable pages (the +1 trash page is excluded)."""
        return self.k.shape[1] - 1


def _cache_update(cache, new, pos_base, active):
    """Write [B, H, T, hd] rows at pos (scalar, or [B] per-row scatter); rows
    with active==False keep their old contents (continuous batching: frozen
    finished slots, masked prefill of a single slot)."""
    new = new.astype(cache.dtype)
    if jnp.ndim(pos_base) == 1:
        upd = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (0, p, 0))
        )(cache, new, pos_base)
    else:
        upd = jax.lax.dynamic_update_slice(cache, new, (0, 0, pos_base, 0))
    if active is not None:
        upd = jnp.where(active[:, None, None, None], upd, cache)
    return upd


def _paged_cache_update(pool, new, tables, pos_base, active):
    """Write [B, H, T, hd] rows into the page pool at block-table positions.

    pool: one layer's [P, H, page, hd] slice. Row pos+t of slot b lands in
    pool page tables[b, (pos+t) // page] at offset (pos+t) % page. Rows with
    active==False are routed to the TRASH page (index P-1, never allocated)
    — a per-row index swap instead of the dense path's whole-cache where().
    """
    from dllama_tpu.ops.layers import paged_write_targets

    new = new.astype(pool.dtype)
    b, h, t, hd = new.shape
    pages, off = paged_write_targets(tables, pos_base, t, pool.shape[2],
                                     pool.shape[0], active)
    return pool.at[pages, :, off, :].set(new.transpose(0, 2, 1, 3))


from dllama_tpu.ops.quant import slice_leaf as _slice_layer


def _layer(cfg: LlamaConfig, x, layers, li, k_cache, v_cache, rope, pos_base, attn_fn,
           active=None, col_fn=None, mm=None, mm_in=None, moe_impl="auto",
           tables=None):
    """One decoder layer. `layers` is the full stacked params dict and `li`
    the traced layer index — quantized weights are NOT sliced here: the matmul
    dispatcher either DMA-indexes the stack (Pallas scalar prefetch) or slices
    lazily (XLA path). Slicing stacked weights before a pallas_call would make
    XLA materialize a full HBM copy of every weight, every layer, every token.

    `k_cache`/`v_cache` are this layer's slice of the cache ([B, Hkv, S, hd],
    or [P, Hkv, page, hd] of a page pool) and come back updated — except on
    the paged kernel route (`attn_fn.fused_kv_scatter`), where they are the
    whole stacked pools [L, P, Hkv, page, lanes], for the same reason the
    weights are whole: the kernel reads and writes layer `li`'s pages in
    place, and a 70 MB slice cut out and put back per layer per step was
    45% of a 7B decode step's device time (PERF.md section 6, PR 27).

    `mm_in` is the matmul for the INPUT-dim-sharded weights (wo/w2 — the
    reference's col slices with merge-add): under sharded-Pallas it psums
    partials inside shard_map; default is plain `mm` (GSPMD inserts the
    collective itself on the XLA path).
    """
    mm = mm or matmul
    if col_fn is None:
        colmm = mm_in or mm  # `--sync q80` swaps in the Q80-exchange
        # shard_map instead (parallel/collectives.make_q80_col_matmul)
    else:
        def colmm(h, w, layer=None):
            return col_fn(h, _slice_layer(w, layer) if layer is not None else w)
    b, t, d = x.shape
    kvd = cfg.kv_dim
    # --- attention block (reference "att" segment, llm.cpp:198-312)
    h = rms_norm(x, layers["rms_att"][li], cfg.norm_epsilon)
    if "wqkv" in layers:  # fused launch (fuse_layer_weights)
        qkv = mm(h, layers["wqkv"], li)
        q, k, v = qkv[..., :d], qkv[..., d : d + kvd], qkv[..., d + kvd :]
    else:
        q = mm(h, layers["wq"], li)
        k = mm(h, layers["wk"], li)
        v = mm(h, layers["wv"], li)
    q = q.reshape(b, t, cfg.n_heads, cfg.head_size)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_size)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_size)
    q = apply_rope(q, rope)
    k = apply_rope(k, rope)
    if tables is None:
        k_cache = _cache_update(k_cache, k.transpose(0, 2, 1, 3), pos_base, active)
        v_cache = _cache_update(v_cache, v.transpose(0, 2, 1, 3), pos_base, active)
        att = attn_fn(q, k_cache, v_cache, pos_base).reshape(b, t, d)
    elif getattr(attn_fn, "fused_kv_scatter", False):
        # paged flash-decode kernel: the new rows' scatter write is fused
        # into the attention launch (ops/pallas/paged_attention) — no
        # separate per-layer scatter dispatch, identical pool contents.
        # k_cache/v_cache are the WHOLE layer-stacked pools here (run_layers
        # carries them): the kernel indexes layer `li` itself, like the
        # matmuls index the weight stacks
        att, k_cache, v_cache = attn_fn(
            q, k_cache, v_cache, tables, pos_base,
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), active, li)
        att = att.reshape(b, t, d)
    else:  # paged layout: scatter at block-table positions, same math
        k_cache = _paged_cache_update(k_cache, k.transpose(0, 2, 1, 3),
                                      tables, pos_base, active)
        v_cache = _paged_cache_update(v_cache, v.transpose(0, 2, 1, 3),
                                      tables, pos_base, active)
        att = attn_fn(q, k_cache, v_cache, tables, pos_base).reshape(b, t, d)
    x = x + colmm(att, layers["wo"], li)
    # --- feed-forward block (reference "ff" segment, llm.cpp:314-385);
    # sparse-MoE variant when the header carries N_EXPERTS (llm.hpp:17-18 —
    # a key the reference parses but never executes)
    h = rms_norm(x, layers["rms_ffn"][li], cfg.norm_epsilon)
    if "moe_gate" in layers:
        x = x + moe_ffn(
            cfg, h, layers["moe_gate"][li],
            _slice_layer(layers["moe_w1"], li),
            _slice_layer(layers["moe_w2"], li),
            _slice_layer(layers["moe_w3"], li),
            impl=moe_impl,
        )
    elif "w13" in layers:  # fused launch (fuse_layer_weights)
        gu = mm(h, layers["w13"], li)
        f = cfg.hidden_dim
        gate = activation(gu[..., :f].astype(jnp.float32), cfg.hidden_act).astype(x.dtype)
        x = x + colmm(gate * gu[..., f:], layers["w2"], li)
    else:
        gate = activation(mm(h, layers["w1"], li).astype(jnp.float32), cfg.hidden_act).astype(x.dtype)
        up = mm(h, layers["w3"], li)
        x = x + colmm(gate * up, layers["w2"], li)
    return x, k_cache, v_cache


def fuse_layer_weights(layers: dict) -> dict:
    """wq/wk/wv -> wqkv and w1/w3 -> w13, concatenated on the OUTPUT dim.

    The attention and gate/up matmuls share their input activation; fusing
    them turns 5 kernel launches per layer into 2 (decode at 1B runs ~113
    Pallas calls per token — launch count is real money at 1 ms/token). The
    reference issues q/k/v and w1/w3 as separate MATMUL ops in its segment
    graph (llm.cpp:198-312, 314-385) because each op is a unit of its
    executor's thread-pool scheduling; here the unit is a kernel launch, so
    concatenation is the analogous batching lever.
    QTensor concat is exact: packed nibbles and f16 scales both carry the
    output dim last. Unsharded engines only — under tp the q and kv blocks
    shard at different granularity, so fused weights would mis-slice.
    Dense (unquantized) leaves concatenate the same way."""
    from dllama_tpu.ops.quant import Q8Tensor, QTensor

    def cat(*ws):
        if isinstance(ws[0], QTensor):
            return QTensor(
                jnp.concatenate([w.packed for w in ws], axis=-1),
                jnp.concatenate([w.scales for w in ws], axis=-1),
            )
        if isinstance(ws[0], Q8Tensor):
            # same output-dim-last layout argument as QTensor
            return Q8Tensor(
                jnp.concatenate([w.codes for w in ws], axis=-1),
                jnp.concatenate([w.scales for w in ws], axis=-1),
            )
        return jnp.concatenate(ws, axis=-1)

    out = dict(layers)
    if all(k in out for k in ("wq", "wk", "wv")):
        out["wqkv"] = cat(out.pop("wq"), out.pop("wk"), out.pop("wv"))
    if all(k in out for k in ("w1", "w3")):
        out["w13"] = cat(out.pop("w1"), out.pop("w3"))
    return out


def run_layers(
    cfg: LlamaConfig,
    layer_params: dict,  # stacked [L, ...] leaves
    x: jax.Array,  # [B, T, D]
    pos_base: jax.Array,  # scalar, or [B] per-row positions
    k_cache: jax.Array,  # [L, B, Hkv, S, hd]
    v_cache: jax.Array,
    rope: jax.Array,  # [T, head_size/2, 2] rope rows (or [B, T, ...] per-row)
    attn_fn=None,
    active: jax.Array | None = None,  # [B] bool: rows allowed to write cache
    unroll: int | bool = 1,
    col_fn=None,  # wo/w2 matmul override (Q80 quantized exchange)
    mm=None,  # quantized-matmul fn (x, w, layer) -> out; default ops.matmul
    mm_in=None,  # matmul for input-dim-sharded weights (see _layer)
    moe_impl: str = "auto",  # MoE compute scheme (ops.layers.moe_ffn)
    tables: jax.Array | None = None,  # i32 [B, max_blocks] block tables —
    # presence selects the paged cache layout (k/v are then page pools)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scan the decoder layers (any contiguous stack — the full model, or one
    pipeline stage's slice). Returns (x, k_cache, v_cache).

    The scan iterates over the layer INDEX — the stacked weights stay
    closed-over and un-sliced, so the Pallas kernels can DMA-index them with
    zero copies (ops/pallas/q40_matmul.py docstring). The cache rides in one
    of two ways, decided by what the call was given:

    * a page pool whose attention function is the fused paged kernel
      (`tables` and `attn_fn.fused_kv_scatter`): the whole stacked pools are
      CARRIED beside x, the kernel indexes the layer, and every layer's
      aliased call updates the one buffer in place — nothing pool-sized or
      slice-sized is cut out, put back or copied;
    * every other layout (dense caches, a pipeline stage's slice, the sp
      ring, the paged gather route): the per-layer slices are the scan's
      xs and ys, as XLA attention over a layer's slice wants them.

    `unroll`: passed to lax.scan — trades compile time for cross-layer
    scheduling freedom."""
    if attn_fn is None:
        if tables is None:
            attn_fn = gqa_attention
        else:
            from dllama_tpu.ops.layers import paged_gqa_attention

            attn_fn = paged_gqa_attention
    n_layers = k_cache.shape[0]
    layer_ids = jnp.arange(n_layers, dtype=jnp.int32)

    if tables is not None and getattr(attn_fn, "fused_kv_scatter", False):
        def pool_scan_fn(carry, li):
            x, kp, vp = carry
            return _layer(cfg, x, layer_params, li, kp, vp, rope, pos_base,
                          attn_fn, active, col_fn, mm, mm_in, moe_impl,
                          tables), None

        (x, k_new, v_new), _ = jax.lax.scan(
            pool_scan_fn, (x, k_cache, v_cache), layer_ids, unroll=unroll)
        return x, k_new, v_new

    def scan_fn(carry, xs):
        x = carry
        li, kc, vc = xs
        x, kc, vc = _layer(cfg, x, layer_params, li, kc, vc, rope, pos_base, attn_fn,
                           active, col_fn, mm, mm_in, moe_impl, tables)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        scan_fn, x, (layer_ids, k_cache, v_cache), unroll=unroll,
    )
    return x, k_new, v_new


def forward(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # i32 [B, T]
    pos_base: jax.Array,  # scalar i32
    cache: KVCache,
    rope_cache: jax.Array,  # [seq, head_size/2, 2]
    attn_fn=None,  # (q, k_cache, v_cache, pos) -> out; default full-cache GQA.
    # A sequence-parallel mesh passes the shard_map'd LSE-merge attention here
    # (parallel/ring_attention.sp_cache_attention).
    active: jax.Array | None = None,  # [B] bool cache-write mask (batch mode)
    unroll: int | bool = 1,  # lax.scan unroll over layers (see run_layers)
    col_fn=None,  # wo/w2 matmul override (Q80 quantized exchange)
    mm=None,  # quantized-matmul fn (x, w, layer) -> out; default ops.matmul
    mm_in=None,  # matmul for input-dim-sharded weights (see _layer)
    moe_impl: str = "auto",  # MoE compute scheme (ops.layers.moe_ffn)
    last_only: bool = False,  # project logits for the last position only
) -> tuple[jax.Array, KVCache]:
    """Returns (logits f32 [B, T, vocab], updated cache).

    pos_base may be a scalar (all rows at one position — the single-sequence
    fast path) or an i32[B] vector giving each row its own position
    (continuous batching; rope rows are then gathered per row).

    ``last_only=True`` slices x to the final position before the lm-head
    matmul — prefill only needs next-token logits, and XLA cannot DCE rows of
    a dot, so without this a 128-token chunk would pay 128x the lm-head cost
    (the reference has the same shape: logits only materialize for the last
    token of a batch, dllama.cpp:69-88).

    `cache` may be a dense KVCache or a PagedKVCache — the paged layout
    threads its block tables through the layer scan (scatter writes at
    table positions, gather/block-indexed attention; identical math)."""
    x = params["embedding"][tokens]  # [B, T, D]
    t = tokens.shape[1]
    pos_base = jnp.asarray(pos_base, jnp.int32)
    if pos_base.ndim == 1:
        idx = pos_base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [B, T]
        rope = rope_cache[jnp.clip(idx, 0, rope_cache.shape[0] - 1)]
    else:
        rope = jax.lax.dynamic_slice_in_dim(rope_cache, pos_base, t, axis=0)
    paged = isinstance(cache, PagedKVCache)
    x, k_new, v_new = run_layers(
        cfg, params["layers"], x, pos_base, cache.k, cache.v, rope, attn_fn, active,
        unroll=unroll, col_fn=col_fn, mm=mm, mm_in=mm_in, moe_impl=moe_impl,
        tables=cache.tables if paged else None,
    )
    if last_only:
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"], cfg.norm_epsilon)
    logits = (mm or matmul)(x, params["wcls"]).astype(jnp.float32)
    if paged:
        return logits, PagedKVCache(k_new, v_new, cache.tables)
    return logits, KVCache(k_new, v_new)


def random_params_fast(cfg: LlamaConfig, seed: int = 0, dtype=jnp.bfloat16):
    """Synthetic Q40 params built from random *packed bytes* directly — no
    float weights, no quantization pass. ~30x faster than random_params for
    benchmark-sized models (an 8B preset materializes in seconds instead of
    minutes); the decoded values are valid Q40 numerics, just not
    normally-distributed. Perf benchmarks only — logits are meaningless."""
    import numpy as np

    from dllama_tpu.ops.quant import Q_BLOCK, QTensor

    rng = np.random.default_rng(seed)

    def qw(lead, k, n):
        packed = rng.integers(0, 256, (*lead, k // 2, n), dtype=np.uint8)
        # f16 scales like the file format; small positive spread
        scales = rng.random((*lead, k // Q_BLOCK, n), np.float32) * 0.02 + 1e-3
        return QTensor(jnp.asarray(packed), jnp.asarray(scales.astype(np.float16)))

    L = cfg.n_layers
    layers: dict = {
        "wq": qw((L,), cfg.dim, cfg.dim),
        "wk": qw((L,), cfg.dim, cfg.kv_dim),
        "wv": qw((L,), cfg.dim, cfg.kv_dim),
        "wo": qw((L,), cfg.dim, cfg.dim),
        "w1": qw((L,), cfg.dim, cfg.hidden_dim),
        "w2": qw((L,), cfg.hidden_dim, cfg.dim),
        "w3": qw((L,), cfg.dim, cfg.hidden_dim),
        "rms_att": jnp.ones((L, cfg.dim), jnp.float32),
        "rms_ffn": jnp.ones((L, cfg.dim), jnp.float32),
    }
    emb = (rng.random((cfg.vocab_size, cfg.dim), np.float32) - 0.5) * 0.04
    return {
        "embedding": jnp.asarray(emb, dtype),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "wcls": qw((), cfg.dim, cfg.vocab_size),
        "layers": layers,
    }


def random_params(cfg: LlamaConfig, seed: int = 0, dtype=jnp.bfloat16, quantize: bool = True):
    """Random-initialized parameter pytree in the same structure load_params
    produces — for tests and synthetic benchmarks (no real checkpoint needed)."""
    import numpy as np

    from dllama_tpu.ops.quant import QTensor

    rng = np.random.default_rng(seed)

    def w(k, n):
        x = (rng.standard_normal((k, n)) * 0.02).astype(np.float32)
        return QTensor.quantize(x) if quantize else jnp.asarray(x, dtype)

    def stack(fn):
        leaves = [fn() for _ in range(cfg.n_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *leaves)

    layers: dict = {
        "wq": stack(lambda: w(cfg.dim, cfg.dim)),
        "wk": stack(lambda: w(cfg.dim, cfg.kv_dim)),
        "wv": stack(lambda: w(cfg.dim, cfg.kv_dim)),
        "wo": stack(lambda: w(cfg.dim, cfg.dim)),
        "rms_att": stack(lambda: jnp.ones((cfg.dim,), jnp.float32)),
        "rms_ffn": stack(lambda: jnp.ones((cfg.dim,), jnp.float32)),
    }
    if cfg.n_experts:
        def expert_stack(k, n):
            leaves = [w(k, n) for _ in range(cfg.n_experts)]
            return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *leaves)

        layers["moe_gate"] = stack(
            lambda: jnp.asarray(rng.standard_normal((cfg.dim, cfg.n_experts)), jnp.float32)
        )
        layers["moe_w1"] = stack(lambda: expert_stack(cfg.dim, cfg.hidden_dim))
        layers["moe_w2"] = stack(lambda: expert_stack(cfg.hidden_dim, cfg.dim))
        layers["moe_w3"] = stack(lambda: expert_stack(cfg.dim, cfg.hidden_dim))
    else:
        layers["w1"] = stack(lambda: w(cfg.dim, cfg.hidden_dim))
        layers["w2"] = stack(lambda: w(cfg.hidden_dim, cfg.dim))
        layers["w3"] = stack(lambda: w(cfg.dim, cfg.hidden_dim))
    params = {
        "embedding": jnp.asarray(rng.standard_normal((cfg.vocab_size, cfg.dim)) * 0.02, dtype),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "wcls": w(cfg.dim, cfg.vocab_size),
        "layers": layers,
    }
    return params

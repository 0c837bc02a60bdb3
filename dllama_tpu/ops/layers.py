"""Core model ops: RMSNorm, RoPE, GQA attention, activations.

jnp reference implementations — under jit XLA fuses these into the surrounding
matmuls; Pallas variants exist only where fusion isn't enough (see ops/pallas/).
Numerics follow the reference kernels (nn-cpu-ops.cpp): norms, softmax and
attention accumulate in f32 regardless of activation dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.models.config import HiddenAct, LlamaConfig, RopeType


# 'jnp' lets XLA fuse the norm into neighbors (the right default); 'pallas'
# routes through ops/pallas/rms_norm — the single-pass fused kernel for the
# case where the norm feeds a Pallas matmul (an opaque call XLA won't fuse
# across). Not measured on the chip; flip only with a recorded win (a
# ledger line).
RMS_NORM_IMPL = "jnp"


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """y = x * w / rms(x) with f32 accumulation (nn-cpu-ops.cpp:108-183)."""
    if RMS_NORM_IMPL == "pallas":
        from dllama_tpu.ops.matmul import device_platform
        from dllama_tpu.ops.pallas.rms_norm import rms_norm as pallas_rms_norm

        return pallas_rms_norm(x, weight, eps,
                               interpret=device_platform() != "tpu")
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * weight.astype(jnp.float32)).astype(x.dtype)


def activation(x: jax.Array, act: HiddenAct) -> jax.Array:
    if act == HiddenAct.SILU:
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=False)


def llama31_scale_freqs(freqs: np.ndarray, cfg: LlamaConfig) -> np.ndarray:
    """Llama-3.1 NTK-by-parts frequency scaling.

    Note: the reference applies this scaling to the *rotated output values*
    (nn-cpu-ops.cpp:1139-1153), which deviates from Meta's reference model
    (and from every HF checkpoint's training-time rope). We implement the
    correct frequency-domain scaling; SURVEY.md §7.4.3 flags this as a
    reference idiosyncrasy we chose to fix, not reproduce.
    """
    wavelen = 2.0 * math.pi / freqs
    high_freq_wavelen = cfg.rope_scaling_orig_max_seq_len / cfg.rope_scaling_high_freq_factor
    low_freq_wavelen = cfg.rope_scaling_orig_max_seq_len / cfg.rope_scaling_low_freq_factor
    scaled = freqs / cfg.rope_scaling_factor
    smooth = (cfg.rope_scaling_orig_max_seq_len / wavelen - cfg.rope_scaling_low_freq_factor) / (
        cfg.rope_scaling_high_freq_factor - cfg.rope_scaling_low_freq_factor
    )
    smoothed = (1 - smooth) * scaled + smooth * freqs
    out = np.where(wavelen < high_freq_wavelen, freqs, np.where(wavelen > low_freq_wavelen, scaled, smoothed))
    return out.astype(np.float32)


def build_rope_cache(cfg: LlamaConfig, seq_len: int | None = None) -> jax.Array:
    """Precomputed [seq_len, head_size/2, 2] (cos, sin) table, f32.

    The analog of the reference's per-node rope_cache buffer
    (nn-cpu-ops.cpp:1082-1102), computed for the *interleaved-pair* layout the
    `.m` format stores Q/K in (converter permutation, convert-hf.py:11-14).
    """
    seq_len = seq_len or cfg.seq_len
    half = cfg.head_size // 2
    freqs = 1.0 / (cfg.rope_theta ** (np.arange(half, dtype=np.float64) * 2.0 / cfg.head_size))
    freqs = freqs.astype(np.float32)
    if cfg.rope_type == RopeType.LLAMA3_1 and cfg.rope_scaling_factor != 1.0:
        freqs = llama31_scale_freqs(freqs, cfg)
    t = np.arange(seq_len, dtype=np.float32)
    angles = np.outer(t, freqs)  # [S, half]
    cache = np.stack([np.cos(angles), np.sin(angles)], axis=-1)
    return jnp.asarray(cache, dtype=jnp.float32)


def apply_rope(x: jax.Array, rope: jax.Array) -> jax.Array:
    """Rotate interleaved pairs: x[..., 2i], x[..., 2i+1] by angle pos*freq_i.

    x: [B, T, H, head_size]; rope: [T, head_size/2, 2] rows already gathered
    for the absolute positions of the T tokens — or [B, T, head_size/2, 2]
    when rows differ per sequence (continuous batching: per-slot positions).
    """
    b, t, h, hs = x.shape
    xf = x.astype(jnp.float32).reshape(b, t, h, hs // 2, 2)
    if rope.ndim == 4:  # per-row rope rows
        cos = rope[:, :, None, :, 0]
        sin = rope[:, :, None, :, 1]
    else:
        cos = rope[None, :, None, :, 0]
        sin = rope[None, :, None, :, 1]
    x0, x1 = xf[..., 0], xf[..., 1]
    r0 = x0 * cos - x1 * sin
    r1 = x0 * sin + x1 * cos
    return jnp.stack([r0, r1], axis=-1).reshape(b, t, h, hs).astype(x.dtype)


def _dense_w(w, dtype):
    from dllama_tpu.ops.quant import QTensor

    return w.dequantize(dtype) if isinstance(w, QTensor) else w.astype(dtype)


def moe_ffn(
    cfg: LlamaConfig,
    h: jax.Array,  # [B, T, D] (already rms-normed)
    gate: jax.Array,  # router [D, E] f32
    w1, w2, w3,  # expert stacks: [E, D, F], [E, F, D], [E, D, F] (QTensor or dense)
    impl: str = "auto",  # 'auto' | 'dispatch' | 'sort' | 'dense'
    capacity_factor: float = 2.0,
) -> jax.Array:
    """Mixtral-style sparse MoE FFN: top-k router (softmax over the top-k
    logits), SwiGLU experts, probability-weighted combine.

    The reference *parses* N_EXPERTS from the header and its converter emits
    expert tensors, but the runtime has no MoE graph (SURVEY.md §2.4 — EP row);
    this is the capability it never shipped.

    Three compute schemes:
    * ``sort`` (default for T*B >= E): MegaBlocks-style grouped GEMM — sort
      the N*k (token, choice) rows by expert id (argsort + gathers, no
      scatters) and run ragged segment matmuls (``lax.ragged_dot``). Exact
      like dense (no capacity drops), O(k/E) FLOPs like dispatch, and none
      of dispatch's scatter risk on TPU.
    * ``dispatch``: GShard-style capacity-bucketed dispatch — each expert
      processes a fixed buffer of C = ~cf*k*N/E token rows (static shapes),
      so FLOPs are O(k/E) of dense. Tokens over an expert's capacity lose
      that expert's contribution (standard switch-transformer semantics;
      cf=2 makes drops rare), and the ``.at[].add`` combine may serialize
      on TPU (VERDICT r3 weak #6) — kept for the window A/B.
    * ``dense``: every expert runs on every token, combine weights zero the
      unrouted ones. Exact (no capacity drops) and gather-free — the
      correctness reference, and the cheaper choice for tiny batches where
      capacity C would equal N anyway.
    """
    e, k = cfg.n_experts, cfg.n_active_experts
    b, t, d = h.shape
    n = b * t
    if impl == "auto":
        # sort over dispatch: exact (no capacity drops), scatter-free (the
        # .at[].add scatters VERDICT r3 weak #6 suspects serialize on TPU),
        # 2.3x faster on CPU, and AOT-accepted for v5e/v6e (MOSAIC_AOT.md);
        # bench_moe's window A/B re-decides this with hardware numbers
        impl = "sort" if n >= e else "dense"
    logits = jnp.einsum(
        "btd,de->bte", h.astype(jnp.float32), gate.astype(jnp.float32)
    )
    topv, topi = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(topv, axis=-1)  # [B, T, k]

    if impl == "sort":
        hf = h.reshape(n, d)
        assign = topi.reshape(-1)  # [N*k] expert ids, token-major
        order = jnp.argsort(assign)  # stable: segments stay token-ordered
        inv = jnp.argsort(order)
        tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        xs = hf[tok[order]]  # [N*k, D] rows grouped by expert
        group_sizes = jnp.bincount(assign, length=e).astype(jnp.int32)
        g = jax.lax.ragged_dot(xs, _dense_w(w1, h.dtype), group_sizes,
                               preferred_element_type=jnp.float32)
        up = jax.lax.ragged_dot(xs, _dense_w(w3, h.dtype), group_sizes,
                                preferred_element_type=jnp.float32)
        act = activation(g, cfg.hidden_act).astype(h.dtype)
        y = jax.lax.ragged_dot(act * up.astype(h.dtype), _dense_w(w2, h.dtype),
                               group_sizes, preferred_element_type=jnp.float32)
        # un-sort (gather by the inverse permutation — still no scatter),
        # then the k choices of each token sit contiguous: weighted-sum them
        y = y[inv].reshape(n, k, d)
        out = jnp.sum(y * probs.reshape(n, k)[..., None], axis=1)
        return out.reshape(b, t, d).astype(h.dtype)

    if impl == "dispatch":
        import math

        c = min(n, max(1, math.ceil(capacity_factor * k * n / e)))
        if c > 8:
            c = min(n, -(-c // 8) * 8)  # round up to the f32 sublane
        hf = h.reshape(n, d)
        assign = topi.reshape(-1)  # [N*k] expert ids, token-major
        onehot = jax.nn.one_hot(assign, e, dtype=jnp.int32)
        # arrival rank of each (token, choice) within its expert's buffer
        rank = jnp.sum(onehot * (jnp.cumsum(onehot, axis=0) - onehot), axis=-1)
        keep = rank < c
        tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        ei = jnp.where(keep, assign, 0)
        ri = jnp.where(keep, rank, 0)
        # scatter token rows into [E, C, D] buffers; (ei, ri) pairs are unique
        # among kept rows, dropped rows contribute zeros at (0, 0)
        contrib = jnp.where(keep[:, None], hf[tok], 0).astype(h.dtype)
        buf = jnp.zeros((e, c, d), h.dtype).at[ei, ri].add(contrib)
        g = jnp.einsum("ecd,edf->ecf", buf, _dense_w(w1, h.dtype))
        up = jnp.einsum("ecd,edf->ecf", buf, _dense_w(w3, h.dtype))
        act = activation(g.astype(jnp.float32), cfg.hidden_act).astype(h.dtype)
        y = jnp.einsum("ecf,efd->ecd", act * up, _dense_w(w2, h.dtype))  # [E, C, D]
        y_tok = y[ei, ri].astype(jnp.float32)  # [N*k, D]
        wgt = probs.reshape(-1) * keep  # dropped choices contribute nothing
        out = jnp.zeros((n, d), jnp.float32).at[tok].add(y_tok * wgt[:, None])
        return out.reshape(b, t, d).astype(h.dtype)

    weights = jnp.sum(
        jax.nn.one_hot(topi, e, dtype=probs.dtype) * probs[..., None], axis=-2
    )  # [B, T, E]
    g = jnp.einsum("btd,edf->btef", h, _dense_w(w1, h.dtype))
    up = jnp.einsum("btd,edf->btef", h, _dense_w(w3, h.dtype))
    act = activation(g.astype(jnp.float32), cfg.hidden_act).astype(h.dtype)
    y = jnp.einsum("btef,efd->bted", act * up, _dense_w(w2, h.dtype))
    out = jnp.einsum("bted,bte->btd", y.astype(jnp.float32), weights)
    return out.astype(h.dtype)


def gqa_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_cache: jax.Array,  # [B, Hkv, S, hd]
    v_cache: jax.Array,  # [B, Hkv, S, hd]
    pos_base: jax.Array,  # i32 scalar, or [B] per-sequence positions
) -> jax.Array:
    """Causal GQA over the full KV cache (nn-cpu-ops.cpp:752-787 equivalent).

    Query t attends to cache slots s <= pos_base + t; unwritten future slots
    are masked out, so the cache can stay a fixed [S]-sized ring without
    dynamic shapes (XLA needs static shapes; the mask replaces the
    reference's `t = 0..pos` loop bound). A vector pos_base gives each batch
    row its own position (continuous batching).
    """
    b, t, hq, hd = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, t, hkv, g, hd)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    scores = jnp.einsum("bthgd,bhsd->bhgts", qf, kf) / math.sqrt(hd)
    spans = jax.lax.broadcasted_iota(jnp.int32, (t, s), 1)
    qoff = jax.lax.broadcasted_iota(jnp.int32, (t, s), 0)
    pos_base = jnp.asarray(pos_base, jnp.int32)
    if pos_base.ndim == 1:
        mask = spans[None] <= pos_base[:, None, None] + qoff[None]  # [B, t, s]
        mask = mask[:, None, None]
    else:
        mask = (spans <= pos_base + qoff)[None, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bthgd", probs, vf)
    return out.reshape(b, t, hq, hd).astype(q.dtype)


def paged_view(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Gather a [B, Hkv, max_blocks*page, hd] contiguous cache view from a
    [P, Hkv, page, hd] page pool through [B, max_blocks] block tables —
    logical row r of slot b reads pool[tables[b, r // page], :, r % page].
    Rows behind unallocated table entries surface stale page contents; the
    caller's causal mask assigns them probability exactly 0.0 (pool values
    are always finite), so a view-based attention is bit-exact vs dense."""
    b, nb = tables.shape
    p, hkv, page, hd = pool.shape
    kv = pool[tables]  # [B, nb, Hkv, page, hd]
    return kv.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * page, hd)


def paged_write_targets(tables: jax.Array, pos_base: jax.Array, t: int,
                        page: int, n_pool: int,
                        active: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """(pages, offsets) i32[B, T] for writing T new KV rows at block-table
    positions — THE single definition of paged write addressing: logical
    row pos+tt of slot b lands in pool page tables[b, (pos+tt) // page] at
    offset (pos+tt) % page, block index clipped to the table width, and
    rows of inactive slots routed to the trash page (n_pool - 1, never
    allocated). Shared by models/llama._paged_cache_update (the XLA
    scatter) and ops/pallas/paged_attention (the fused in-kernel scatter),
    so the two write paths cannot drift apart."""
    b, nb = tables.shape
    pos = jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32), (b,))
    rows = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [B, T]
    blk = jnp.clip(rows // page, 0, nb - 1)
    off = rows % page
    pages = jnp.take_along_axis(tables, blk, axis=1)  # [B, T]
    if active is not None:
        pages = jnp.where(active[:, None], pages, n_pool - 1)
    return pages.astype(jnp.int32), off.astype(jnp.int32)


def paged_gqa_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_pool: jax.Array,  # [P, Hkv, page, hd] (one layer's pool slice)
    v_pool: jax.Array,
    tables: jax.Array,  # i32 [B, max_blocks]
    pos_base: jax.Array,  # i32 scalar, or [B] per-sequence positions
) -> jax.Array:
    """Causal GQA over the paged KV cache: the jnp reference/fallback path —
    gather the block-table view, then run the dense attention math unchanged.
    This re-materializes the ENTIRE view through XLA every step; the routed
    production path (`kernel_select` route 'paged_kernel') is the
    flash-decode kernel in ops/pallas/paged_attention.py, which DMA-walks
    pages via scalar-prefetched tables instead — this gather stays the
    bit-for-bit correctness reference and serves attn_impl='jnp', f8 pools,
    and non-sublane-aligned page sizes."""
    return gqa_attention(q, paged_view(k_pool, tables),
                         paged_view(v_pool, tables), pos_base)

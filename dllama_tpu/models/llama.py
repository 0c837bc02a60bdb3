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
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from dllama_tpu.models.config import (
    SCHEDULE_DENSE_FFN,
    SCHEDULE_KIND_MASK,
    SCHEDULE_UNROTATED,
    SCHEDULE_WINDOWED,
    STATE_KINDS,
    LayerKind,
    LlamaConfig,
    RopeType,
)
from dllama_tpu.ops import delta, power, ssm
from dllama_tpu.ops.layers import (
    activation,
    apply_rope,
    gqa_attention,
    latent_attention,
    moe_ffn,
    paged_view,
    rms_norm,
    router_logits,
)
from dllama_tpu.ops.matmul import matmul


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RecurrentState:
    """The recurrent layers' per-sequence state, beside the KV cache
    (`LlamaConfig.state_shape` and `state_conv` say what a slot holds in a
    layer for each of the three kinds):

      s    [Ls, B, H, P, N]   the recurrence's running sum, float32 unless
                              constructed otherwise (it sums over the whole
                              context; a narrower `dtype` is the control the
                              tests hold against the tolerance): a
                              state-space head's [P, N], a delta-rule head's
                              [key, value], a retention kv head's [value + 1,
                              expanded key] (the row behind its values the
                              normaliser), padded to whole tiles
      conv [Ls, B, K-1, C]    the causal conv's window: the last K-1 rows
                              of x|B|C (state-space) or q|k|v (delta rule)
                              before activation, in the activations' own
                              type (bf16 as served: the rows ARE bf16
                              activations, nothing is lost); EMPTY
                              ([Ls, B, 0, 0]) for retention layers, which
                              have no conv

    Ls = recurrent layers, B = batch rows or serving slots. Fixed-size a
    slot, not pageable, and it CANNOT be rewound: it stands at one row of
    its sequence, and the engines keep which (engine/batch.BatchEngine).
    Carried whole through the layer scan and the step scan and updated in
    place, as the page pool is. `slot` (a traced scalar) narrows every read
    and write to that one slot at B = 1 — an admission's prefill slice cuts
    a slot's layer out of the stack and puts it back (2 MB for a
    state-space or delta-rule layer, 36.2 MB for a retention layer of 8 kv
    heads of 128: `slot_bytes`), never the stack.

    `step` (static, not a leaf) is the whole-batch decode step on the
    layer-stacked `s` that the engine's kernel selection resolved
    (engine/kernel_select.resolve_state_step, named in the route tag); None
    = the jnp step on a layer's slice (ops/ssm.ssm_step_ref and its
    siblings). The model asks nothing else about kernels."""

    s: jax.Array
    conv: jax.Array
    slot: jax.Array | None = None
    step: "Callable | None" = None

    def tree_flatten(self):
        return (self.s, self.conv, self.slot), self.step

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, step=aux)

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int, dtype=jnp.float32,
               conv_dtype=jnp.bfloat16, step=None):
        ls = cfg.n_state_layers
        return cls(
            jnp.zeros((ls, batch, *cfg.state_shape), dtype),
            jnp.zeros((ls, batch, *cfg.state_conv), conv_dtype),
            step=step)

    @property
    def nbytes(self) -> int:
        return int(self.s.nbytes + self.conv.nbytes)

    @property
    def slot_bytes(self) -> int:
        """One slot's state over every layer: what a B = 1 slice cuts out
        of the stack, and what it puts back."""
        return self.nbytes // self.s.shape[1]

    def at_slot(self, slot) -> "RecurrentState":
        return RecurrentState(self.s, self.conv, slot, self.step)

    def _cut(self, buf, si):
        if self.slot is None:
            return jax.lax.dynamic_index_in_dim(buf, si, axis=0, keepdims=False)
        start = (si, self.slot) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_slice(buf, start, (1, 1) + buf.shape[2:])[0]

    def _put(self, buf, si, new):
        start = (si, 0 if self.slot is None else self.slot) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, new[None].astype(buf.dtype), start)

    def window(self, si) -> jax.Array:
        """Layer si's conv window [B, K-1, C] (B = 1 under `slot`)."""
        return self._cut(self.conv, si)

    def layer_state(self, si) -> jax.Array:
        """Layer si's S [B, H, P, N] (B = 1 under `slot`)."""
        return self._cut(self.s, si)

    def replace_layer(self, si, window, s_new=None, s_stack=None) -> "RecurrentState":
        """Layer si's window put back (None: the kind has no conv), and its
        S (`s_new`, [B, H, P, N]) — or the whole stack where a kernel
        already updated it in place."""
        s = s_stack if s_stack is not None else self._put(self.s, si, s_new)
        conv = self.conv if window is None else self._put(self.conv, si, window)
        return RecurrentState(s, conv, self.slot, self.step)


def _moe_stats0(cfg: LlamaConfig):
    """The expert counters (ops/layers.moe_ffn): four, a fifth (every
    routed row) where the model holds a share of its experts, and two more
    at the end (tokens routed, tokens whose kept groups reach this chip)
    where the selection is group-limited."""
    if not cfg.n_experts:
        return None
    return jnp.zeros((4 + bool(cfg.experts_held) + 2 * cfg.grouped_routing,),
                     jnp.uint32)


def _v_placeholder(cfg: LlamaConfig, lead: tuple, dtype):
    """A latent cache has no v rows (the row is key and value): `v` is a
    placeholder of the cache's rank that nothing reads or writes."""
    return jnp.zeros((cfg.n_attn_layers, *lead, 1, 8, 128), dtype)


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
    state: RecurrentState | None = None  # where the model has state-space layers
    moe_stats: jax.Array | None = None  # u32[4] where the model has routed
    # experts: running sums of (token-expert rows, experts with a row,
    # layer-steps, longest group), added to by every forward (ops/layers.moe_ffn)

    def tree_flatten(self):
        return (self.k, self.v, self.state, self.moe_stats), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int, dtype=jnp.bfloat16, seq_len: int | None = None,
               state_dtype=jnp.float32, conv_dtype=jnp.bfloat16,
               state_step=None):
        """The layer axis counts the layers that hold KV rows
        (`cfg.n_attn_layers`: all of them, but for a hybrid stack)."""
        shape = (cfg.n_attn_layers, batch, cfg.cache_kv_heads,
                 seq_len or cfg.seq_len, cfg.cache_row)
        state = (RecurrentState.create(cfg, batch, state_dtype, conv_dtype,
                                       state_step)
                 if cfg.recurrent else None)
        v = (_v_placeholder(cfg, (batch,), dtype) if cfg.latent
             else jnp.zeros(shape, dtype))
        return cls(jnp.zeros(shape, dtype), v, state, _moe_stats0(cfg))

    @property
    def seq_len(self) -> int:
        return self.k.shape[3]

    def slot_view(self, slot) -> "KVCache":
        """ONE batch row's cache at B = 1: its rows cut out of the batch
        axis, its own recurrent state."""
        cut = lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)
        state = None if self.state is None else self.state.at_slot(slot)
        return KVCache(cut(self.k), cut(self.v), state, self.moe_stats)

    def merge_slot(self, sub: "KVCache", slot) -> "KVCache":
        put = lambda c, n: jax.lax.dynamic_update_slice_in_dim(c, n, slot, axis=1)
        state = None if sub.state is None else sub.state.at_slot(None)
        return KVCache(put(self.k, sub.k), put(self.v, sub.v), state,
                       sub.moe_stats)


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
    probability 0.0 — so paged attention is bit-exact vs dense).

    A model with WINDOWED attention layers holds a pool a kind: `k`/`v`/
    `tables` for the layers that see the whole context ([Lg, Pg+1, ...]) and
    `kw`/`vw`/`wtables` for the windowed ones ([Lw, Pw+1, ...]), each with
    its own allocator on the host (engine/batch.PagePool). Both tables are
    positional (block = row // page); a window block no query can see any
    more is handed back and its entry points at the window pool's trash
    page, which the clipped sweep never reads. No window layer: the three
    are None and everything is as above."""

    k: jax.Array
    v: jax.Array
    tables: jax.Array  # i32 [n_slots, max_blocks]
    state: RecurrentState | None = None  # per SLOT, beside the pool
    kw: jax.Array | None = None
    vw: jax.Array | None = None
    wtables: jax.Array | None = None
    moe_stats: jax.Array | None = None  # as KVCache.moe_stats

    def tree_flatten(self):
        return (self.k, self.v, self.tables, self.state, self.kw, self.vw,
                self.wtables, self.moe_stats), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def create(cls, cfg: LlamaConfig, n_slots: int, n_pages: int,
               page_size: int, dtype=jnp.bfloat16, max_blocks: int = 0,
               lanes: int = 0, state_dtype=jnp.float32,
               conv_dtype=jnp.bfloat16, state_step=None,
               window_pages: int = 0):
        """``lanes`` widens the row (minor) dim past head_size — the paged
        Pallas kernel needs whole 128-lane rows
        (ops/pallas/paged_attention.pool_lanes); 0 = head_size. The pool's
        layer axis counts the attention layers (`cfg.n_attn_layers`); with
        windowed layers, those that are not, and ``window_pages`` sizes the
        windowed layers' own pool."""
        lw = cfg.n_window_layers
        row = (cfg.cache_kv_heads, page_size, lanes or cfg.cache_row)
        shape = (cfg.n_attn_layers - lw, n_pages + 1, *row)
        tables = jnp.zeros((n_slots, max_blocks or 1), jnp.int32)
        state = (RecurrentState.create(cfg, n_slots, state_dtype, conv_dtype,
                                       state_step)
                 if cfg.recurrent else None)
        kw = vw = wtables = None
        if lw:
            kw = jnp.zeros((lw, window_pages + 1, *row), dtype)
            vw = jnp.zeros((lw, window_pages + 1, *row), dtype)
            # an entry nothing backs points at the window pool's trash page
            wtables = jnp.full((n_slots, max_blocks or 1), window_pages,
                               jnp.int32)
        v = (_v_placeholder(cfg, (1,), dtype) if cfg.latent
             else jnp.zeros(shape, dtype))
        return cls(jnp.zeros(shape, dtype), v, tables, state, kw, vw, wtables,
                   _moe_stats0(cfg))

    def slot_view(self, slot) -> "PagedKVCache":
        """The cache as ONE slot's B = 1 forward sees it: its block-table
        row over the global pool, its own recurrent state."""
        cut = lambda t: None if t is None else jax.lax.dynamic_slice_in_dim(
            t, slot, 1, axis=0)
        state = None if self.state is None else self.state.at_slot(slot)
        return PagedKVCache(self.k, self.v, cut(self.tables), state, self.kw,
                            self.vw, cut(self.wtables), self.moe_stats)

    def merge_slot(self, sub: "PagedKVCache", slot=None) -> "PagedKVCache":
        """Back from `slot_view`: the pools and state `sub` wrote, under
        every slot's tables."""
        state = None if sub.state is None else sub.state.at_slot(None)
        return PagedKVCache(sub.k, sub.v, self.tables, state, sub.kw, sub.vw,
                            self.wtables, sub.moe_stats)

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


def _qkv_heads(cfg: LlamaConfig, h, layers, ai, mm, heads: int, sfx: str = ""):
    """q [B, T, heads, hd], k and v [B, T, kv heads, hd] of layer `ai` of the
    `wq` / `wk` / `wv` stacks named by `sfx`, RMS-normed over the head where
    the header says so (before any rotation): what softmax attention and
    retention layers both start from."""
    b, t, _ = h.shape
    d, kvd = heads * cfg.head_size, cfg.kv_dim
    if "wqkv" + sfx in layers:  # fused launch (fuse_layer_weights)
        qkv = mm(h, layers["wqkv" + sfx], ai)
        q, k, v = qkv[..., :d], qkv[..., d : d + kvd], qkv[..., d + kvd :]
    else:
        q = mm(h, layers["wq" + sfx], ai)
        k = mm(h, layers["wk" + sfx], ai)
        v = mm(h, layers["wv" + sfx], ai)
    q = q.reshape(b, t, heads, cfg.head_size)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_size)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_size)
    if cfg.qk_norm:  # over the head, before the rotation
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, layers["q_norm" + sfx][ai], cfg.norm_epsilon)
            k = rms_norm(k, layers["k_norm" + sfx][ai], cfg.norm_epsilon)
    return q, k, v


def _attention_mixer(cfg: LlamaConfig, h, layers, ai, k_cache, v_cache, rope,
                     pos_base, attn_fn, active, mm, colmm, tables, ci=None,
                     window: int = 0):
    """Softmax attention over the cache. `ai` indexes the layer's weight
    stacks (the attention layers', or its own KIND's where the windowed
    layers' tensors are stacked apart as `*_win`) and `ci` the cache's layer
    axis (= `ai` unless the cache is a pool a kind; a hybrid model has fewer
    of both than it has layers). `window` > 0: the layer's queries see that
    many rows, and the layer has the windowed kind's head count. `rope` is
    the layer's own table's rows. Returns (out [B, T, D], k_cache, v_cache)."""
    b, t, _ = h.shape
    windowed = window > 0
    heads, sfx = cfg.heads_of(windowed), cfg.attn_suffix(windowed)
    d = heads * cfg.head_size  # heads x head size: the model's dim unless
    # the header gives the head size
    ci = ai if ci is None else ci
    win = {"window": window} if window else {}
    q, k, v = _qkv_heads(cfg, h, layers, ai, mm, heads, sfx)
    if rope is not None:  # RopeType.NONE: q and k as projected
        with jax.named_scope("rope_window" if windowed else "rope_global"):
            q = apply_rope(q, rope)
            k = apply_rope(k, rope)
    if cfg.softmax_scale_ratio != 1.0:
        # a configured score scale: every attention path bakes 1/sqrt(hd)
        # in, so q carries the ratio (a power of two where the scale is one:
        # exact in bf16)
        q = q * jnp.asarray(cfg.softmax_scale_ratio, q.dtype)
    if tables is None:
        k_cache = _cache_update(k_cache, k.transpose(0, 2, 1, 3), pos_base, active)
        v_cache = _cache_update(v_cache, v.transpose(0, 2, 1, 3), pos_base, active)
        att = attn_fn(q, k_cache, v_cache, pos_base, **win).reshape(b, t, d)
    elif getattr(attn_fn, "fused_kv_scatter", False):
        # paged flash-decode kernel: the new rows' scatter write is fused
        # into the attention launch (ops/pallas/paged_attention) — no
        # separate per-layer scatter dispatch, identical pool contents.
        # k_cache/v_cache are the WHOLE layer-stacked pools here (run_layers
        # carries them): the kernel indexes layer `ai` itself, like the
        # matmuls index the weight stacks
        att, k_cache, v_cache = attn_fn(
            q, k_cache, v_cache, tables, pos_base,
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), active, ci,
            **win)
        att = att.reshape(b, t, d)
    else:  # paged layout: scatter at block-table positions, same math
        k_cache = _paged_cache_update(k_cache, k.transpose(0, 2, 1, 3),
                                      tables, pos_base, active)
        v_cache = _paged_cache_update(v_cache, v.transpose(0, 2, 1, 3),
                                      tables, pos_base, active)
        att = attn_fn(q, k_cache, v_cache, tables, pos_base, **win).reshape(b, t, d)
    if cfg.attn_gate:
        # one gate a head, softplus in float32, read from the same normed
        # input as q
        with jax.named_scope("attn_gate"):
            gate = jax.nn.softplus(router_logits(h, layers["attn_gate" + sfx][ai]))
            att = (att.reshape(b, t, heads, cfg.head_size).astype(jnp.float32)
                   * gate[..., None]).astype(h.dtype).reshape(b, t, d)
    return colmm(att, layers["wo" + sfx], ai), k_cache, v_cache


def _ssm_mixer(cfg: LlamaConfig, h, layers, si, state: RecurrentState,
               pos_base, active, mm, colmm):
    """The Mamba-2 state-space mixer (ops/ssm.py has the equations). `si`
    indexes the state-space layers' weight stacks and the state's layer
    axis. A row at position 0 starts from ZERO state and a zero conv window,
    whatever its slot held: a sequence's start has no history, so a slot
    re-used by a new request cannot read the last one's state. Rows with
    active==False leave both bit-equal. Returns (out [B, T, D], state)."""
    b, t, _ = h.shape
    inner, n = cfg.ssm_inner, cfg.ssm_state
    heads, p, cd = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv_dim
    proj = mm(h, layers["in_proj"], si)  # z | x B C | dt (| zero pad)
    z, xbc = proj[..., :inner], proj[..., inner : inner + cd]
    dt_raw = proj[..., inner + cd : inner + cd + heads]
    fresh = jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32) == 0, (b,))
    window = state.window(si)
    xbc, new_window = ssm.causal_conv(xbc, window, layers["conv_w"][si],
                                      layers["conv_b"][si], fresh)
    x = xbc[..., :inner].reshape(b, t, heads, p)
    bmat, cmat = xbc[..., inner : inner + n], xbc[..., inner + n :]
    dt, log_a = ssm.decay_terms(dt_raw, layers["dt_bias"][si], layers["a_log"][si])
    act = jnp.ones((b,), bool) if active is None else active
    new_window = jnp.where(act[:, None, None], new_window, window)
    if t == 1 and state.slot is None and state.step is not None:
        # the decode step the engine resolved, on the layer-stacked state,
        # in place
        mode = jnp.where(act, jnp.where(fresh, 2, 1), 0)
        y, s_stack = state.step(state.s, si, x[:, 0], dt[:, 0],
                                jnp.exp(log_a[:, 0]), bmat[:, 0], cmat[:, 0],
                                mode)
        y = y[:, None]
        state = state.replace_layer(si, new_window, s_stack=s_stack)
    else:
        s_old = state.layer_state(si)
        s_in = jnp.where(fresh[:, None, None, None], 0.0,
                         s_old.astype(jnp.float32))
        if t == 1:
            y, s_new = ssm.ssm_step_ref(s_in, x[:, 0], dt[:, 0], log_a[:, 0],
                                        bmat[:, 0], cmat[:, 0])
            y = y[:, None]
        else:
            y, s_new = ssm.ssm_chunk_scan(s_in, x, dt, log_a, bmat, cmat,
                                          cfg.ssm_chunk)
        s_new = jnp.where(act[:, None, None, None], s_new.astype(s_old.dtype), s_old)
        state = state.replace_layer(si, new_window, s_new=s_new)
    y = y + layers["d"][si][:, None] * x
    y = ssm.gated_rms_norm(y.reshape(b, t, inner), z, layers["ssm_norm"][si],
                           cfg.norm_epsilon).astype(h.dtype)
    return colmm(y, layers["out_proj"], si), state


def _kda_mixer(cfg: LlamaConfig, h, layers, si, state: RecurrentState,
               pos_base, active, mm, colmm):
    """The gated delta-rule mixer (ops/delta.py has the equations). `si`
    indexes the delta-rule layers' weight stacks and the state's layer axis;
    a row at position 0 starts from ZERO state and a zero conv window, rows
    with active==False leave both bit-equal, as `_ssm_mixer`'s. Returns
    (out [B, T, D], state)."""
    b, t, _ = h.shape
    heads, dk, rank, inner = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.kda_inner
    proj = mm(h, layers["kda_proj"], si)  # q k v | fa | ga | beta (| zero pad)
    fa = proj[..., 3 * inner : 3 * inner + rank]
    ga = proj[..., 3 * inner + rank : 3 * inner + 2 * rank]
    beta = jax.nn.sigmoid(proj[..., 3 * inner + 2 * rank :
                               3 * inner + 2 * rank + heads].astype(jnp.float32))
    fresh = jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32) == 0, (b,))
    window = state.window(si)
    qkv, new_window = ssm.causal_conv(
        proj[..., : 3 * inner], window, layers["kda_conv_w"][si],
        jnp.zeros((), jnp.float32), fresh)
    split = lambda i: qkv[..., i * inner : (i + 1) * inner].reshape(b, t, heads, dk)
    q = delta.l2norm(split(0)) * (dk ** -0.5)
    k, v = delta.l2norm(split(1)), split(2)
    g = delta.decay(mm(fa, layers["kda_fb"], si), layers["kda_dt_bias"][si],
                    layers["kda_a_log"][si], heads)  # [B, T, H, K]
    act = jnp.ones((b,), bool) if active is None else active
    new_window = jnp.where(act[:, None, None], new_window, window)
    if t == 1 and state.slot is None and state.step is not None:
        # the decode step the engine resolved, on the layer-stacked state,
        # in place
        mode = jnp.where(act, jnp.where(fresh, 2, 1), 0)
        o, s_stack = state.step(state.s, si, q[:, 0], k[:, 0], v[:, 0],
                                jnp.exp(g[:, 0]), beta[:, 0], mode)
        o = o[:, None]
        state = state.replace_layer(si, new_window, s_stack=s_stack)
    else:
        s_old = state.layer_state(si)
        s_in = jnp.where(fresh[:, None, None, None], 0.0,
                         s_old.astype(jnp.float32))
        if t == 1:
            o, s_new = delta.kda_step_ref(s_in, q[:, 0], k[:, 0], v[:, 0],
                                          g[:, 0], beta[:, 0])
            o = o[:, None]
        else:
            # a prefill slice: the step scanned over its rows, under a name
            # of its own in the profile's op metadata
            with jax.named_scope("kda_slice"):
                o, s_new = delta.kda_scan(s_in, q, k, v, g, beta)
        s_new = jnp.where(act[:, None, None, None], s_new.astype(s_old.dtype), s_old)
        state = state.replace_layer(si, new_window, s_new=s_new)
    gate = mm(ga, layers["kda_gb"], si).reshape(b, t, heads, dk)
    y = delta.gated_head_norm(o, gate, layers["kda_norm"][si], cfg.norm_epsilon)
    return colmm(y.reshape(b, t, inner).astype(h.dtype), layers["kda_o"], si), state


def _retention_mixer(cfg: LlamaConfig, h, layers, si, state: RecurrentState,
                     pos_base, active, mm, colmm, rope=None):
    """The power-retention mixer (ops/power.py has the equations): the
    attention tensors' q, k and v, normed over the head and rotated (`rope`:
    the rows' table rows; a recurrent slot needs its row's position for
    nothing else), a gate a kv head, and a state that `cfg.q_per_kv` query
    heads share. `si` indexes the layers' weight stacks and the state's layer
    axis; a row at position 0 starts from ZERO state and rows with
    active==False leave it bit-equal, as `_ssm_mixer`'s. Returns
    (out [B, T, D], state)."""
    b, t, _ = h.shape
    q, k, v = _qkv_heads(cfg, h, layers, si, mm, cfg.n_heads)
    if rope is not None:
        with jax.named_scope("rope_retention"):
            q = apply_rope(q, rope)
            k = apply_rope(k, rope)
    with jax.named_scope("retention_gate"):
        # log of the decay, float32: log sigmoid(x W_g + b_g) [B, T, G]
        gamma = jax.nn.log_sigmoid(router_logits(h, layers["ret_gate"][si])
                                   + layers["ret_gate_bias"][si])
    fresh = jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32) == 0, (b,))
    act = jnp.ones((b,), bool) if active is None else active
    if t == 1 and state.slot is None and state.step is not None:
        # the decode step the engine resolved, on the layer-stacked state,
        # in place
        mode = jnp.where(act, jnp.where(fresh, 2, 1), 0)
        y, s_stack = state.step(state.s, si, q[:, 0], k[:, 0], v[:, 0],
                                jnp.exp(gamma[:, 0]), mode)
        y = y[:, None]
        state = state.replace_layer(si, None, s_stack=s_stack)
    else:
        s_old = state.layer_state(si)
        s_in = jnp.where(fresh[:, None, None, None], 0.0,
                         s_old.astype(jnp.float32))
        if t == 1:
            y, s_new = power.retention_step_ref(s_in, q[:, 0], k[:, 0], v[:, 0],
                                                gamma[:, 0])
            y = y[:, None]
        else:
            # a prefill slice: matrix products over its rows, under a name
            # of its own in the profile's op metadata
            with jax.named_scope("retention_slice"):
                y, s_new = power.retention_slice(s_in, q, k, v, gamma)
        s_new = jnp.where(act[:, None, None, None], s_new.astype(s_old.dtype), s_old)
        state = state.replace_layer(si, None, s_new=s_new)
    with jax.named_scope("retention_norm"):
        y = power.normalise(y, cfg.head_size).astype(h.dtype)
    return colmm(y.reshape(b, t, cfg.attn_dim), layers["wo"], si), state


def _mla_mixer(cfg: LlamaConfig, h, layers, ai, k_cache, v_cache, rope, pos_base,
               attn_fn, active, mm, colmm, tables, ci=None):
    """Latent attention in its ABSORBED form on every route: the cache row
    of a token is (c, k_pe) = (rmsnorm(W_kva h)[:r], W_kva h[r:]), one for
    all heads; a head's query meets it as (W_kvb,k^T q_nope, q_pe), the mix
    of the rows' latents comes back and W_kvb,v expands it to the head's
    value. One read of a row serves key and value of every head; the
    expanded form (k_nope, v = W_kvb c a head) is the benchmark reference's.

    `rope` (None: the shared dims ride unrotated) rotates q_pe a head and
    the ONE k_pe a token BEFORE the row is written, so the cache holds
    rotated rows and every route's sweep is the unrotated model's. With a
    q-side low rank (`cfg.q_lora_rank`) q = W_qb rmsnorm(W_qa h; g_q).
    `v_cache` is the latent cache's placeholder.
    Returns (out [B, T, D], k_cache, v_cache)."""
    b, t, _ = h.shape
    heads, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dp, dv = cfg.qk_nope_dim, cfg.qk_pe_dim, cfg.v_head_dim
    ci = ai if ci is None else ci
    hi = jax.lax.Precision.HIGHEST
    if cfg.q_lora_rank:
        with jax.named_scope("mla_q_rank"):
            cq = rms_norm(mm(h, layers["mla_qa"], ai), layers["mla_q_norm"][ai],
                          cfg.norm_epsilon)
        q = mm(cq, layers["mla_qb"], ai)
    else:
        q = mm(h, layers["mla_q"], ai)
    q = q.reshape(b, t, heads, dn + dp)
    kva = mm(h, layers["mla_kva"], ai)  # c | k_pe (| zero pad)
    c = rms_norm(kva[..., :r], layers["mla_kv_norm"][ai], cfg.norm_epsilon)
    k_pe = kva[..., r : r + dp]
    if rope is not None:
        with jax.named_scope("mla_rope"):
            k_pe = apply_rope(k_pe[:, :, None], rope)[:, :, 0]
    row = jnp.concatenate([c, k_pe], axis=-1)[:, None]  # [B, 1, T, W]
    wkvb = layers["mla_kvb"][ai]  # f32 [H, nope + v, r]
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bthn,hnr->bthr", q[..., :dn].astype(jnp.float32),
                           wkvb[:, :dn], precision=hi).astype(h.dtype)
    q_pe = q[..., dn:]
    if rope is not None:
        with jax.named_scope("mla_rope"):
            q_pe = apply_rope(q_pe, rope)
    q_abs = jnp.concatenate([q_lat, q_pe], axis=-1)
    scale = cfg.attn_scale or (dn + dp) ** -0.5
    if tables is None:
        k_cache = _cache_update(k_cache, row, pos_base, active)
        o_lat = latent_attention(q_abs, k_cache[:, 0], pos_base, scale, r)
    elif getattr(attn_fn, "fused_kv_scatter", False):
        # the paged kernel's latent sweep, on the whole layer-stacked pool
        o_lat, k_cache, v_cache = attn_fn(
            q_abs, k_cache, v_cache, tables, pos_base, row, None, active, ci,
            latent=r, scale=scale)
    else:
        k_cache = _paged_cache_update(k_cache, row, tables, pos_base, active)
        o_lat = latent_attention(q_abs, paged_view(k_cache, tables)[:, 0],
                                 pos_base, scale, r)
    with jax.named_scope("mla_expand"):
        o = jnp.einsum("bthr,hvr->bthv", o_lat.astype(jnp.float32), wkvb[:, dn:],
                       precision=hi)
    out = colmm(o.reshape(b, t, heads * dv).astype(h.dtype), layers["mla_o"], ai)
    return out, k_cache, v_cache


def _mlp(cfg: LlamaConfig, h, layers, li, mm, colmm, moe_impl, experts: bool,
         logits=None, stats=None):
    """The feed-forward block (reference "ff" segment, llm.cpp:314-385);
    sparse-MoE variant when the header carries N_EXPERTS (llm.hpp:17-18 —
    a key the reference parses but never executes): `logits` are the
    router's, computed by `_layer` where the header says the router reads.
    The expert stacks go in whole with the layer index, as the matmuls'
    weights do. Returns the block's output, and with `stats` (out, stats').
    `li` indexes the stacks of the layer's OWN feed-forward kind (`experts`:
    routed experts, else dense), and a shared expert, one SwiGLU at the
    shared width, is added to the routed sum once."""
    if experts:
        bias = layers["moe_bias"][li] if "moe_bias" in layers else None
        out = moe_ffn(
            cfg, h, None, layers["moe_w1"], layers["moe_w2"], layers["moe_w3"],
            impl=moe_impl, logits=logits, layer=li, stats=stats, bias=bias)
        if "shared_w1" not in layers:
            return out
        gate = activation(mm(h, layers["shared_w1"], li).astype(jnp.float32),
                          cfg.hidden_act).astype(h.dtype)
        shared = colmm(gate * mm(h, layers["shared_w3"], li),
                       layers["shared_w2"], li)
        if stats is None:
            return out + shared
        return out[0] + shared, out[1]
    if "w13" in layers:  # fused launch (fuse_layer_weights)
        gu = mm(h, layers["w13"], li)
        f = cfg.hidden_dim
        gate = activation(gu[..., :f].astype(jnp.float32), cfg.hidden_act).astype(h.dtype)
        return colmm(gate * gu[..., f:], layers["w2"], li)
    gate = activation(mm(h, layers["w1"], li).astype(jnp.float32), cfg.hidden_act).astype(h.dtype)
    up = mm(h, layers["w3"], li)
    return colmm(gate * up, layers["w2"], li)


def _layer(cfg: LlamaConfig, x, layers, li, mix, col_fn=None, mm=None,
           mm_in=None, moe_impl="auto", moe_stats=None, dense_ffn=False,
           fi=None):
    """One decoder layer: the ONE skeleton every architecture runs,

        x += r * mix(norm(x));  x += r * mlp(norm(x))

    with `mix(h, mm, colmm) -> (out, aux)` the layer's mixer (attention or
    state-space, chosen by the caller from the layer's kind; `aux` is
    whatever cache or state it updated) and r the residual multiplier (1
    unless the header says otherwise). `layers` is the full stacked params
    dict and `li` the traced layer index — quantized weights are NOT sliced
    here: the matmul dispatcher either DMA-indexes the stack (Pallas scalar
    prefetch) or slices lazily (XLA path). Slicing stacked weights before a
    pallas_call would make XLA materialize a full HBM copy of every weight,
    every layer, every token. Caches and states follow the same rule where
    a kernel takes them: the paged kernel route (`attn_fn.fused_kv_scatter`)
    and the state-space decode step are handed the whole stacked arrays and
    read and write their layer in place — a 70 MB slice cut out and put
    back per layer per step was 45% of a 7B decode step's device time
    (PERF.md section 6, PR 27).

    `mm_in` is the matmul for the INPUT-dim-sharded weights (wo/w2 — the
    reference's col slices with merge-add): under sharded-Pallas it psums
    partials inside shard_map; default is plain `mm` (GSPMD inserts the
    collective itself on the XLA path).

    `dense_ffn`: the layer's feed-forward block is dense though the model
    has experts; `fi` is the layer's index into its feed-forward kind's
    stacks where dense and expert layers are stacked apart (None = `li`).
    """
    fi = li if fi is None else fi
    mm = mm or matmul
    if col_fn is None:
        colmm = mm_in or mm  # `--sync q80` swaps in the Q80-exchange
        # shard_map instead (parallel/collectives.make_q80_col_matmul)
    else:
        def colmm(h, w, layer=None):
            return col_fn(h, _slice_layer(w, layer) if layer is not None else w)
    r = cfg.residual_multiplier
    scaled = (lambda y: y) if r == 1.0 else (lambda y: y * jnp.asarray(r, y.dtype))
    # --- mixer block (reference "att" segment, llm.cpp:198-312)
    h = rms_norm(x, layers["rms_att"][li], cfg.norm_epsilon)
    experts = "moe_gate" in layers and not dense_ffn
    logits = None
    if experts and cfg.router_pre_attention:
        # the router reads the attention block's normed input
        logits = router_logits(h, layers["moe_gate"][fi])
    out, aux = mix(h, mm, colmm)
    x = x + scaled(out)
    h = rms_norm(x, layers["rms_ffn"][li], cfg.norm_epsilon)
    if experts and logits is None:
        logits = router_logits(h, layers["moe_gate"][fi])
    y = _mlp(cfg, h, layers, fi, mm, colmm, moe_impl, experts, logits,
             moe_stats if experts else None)
    if moe_stats is not None:
        if experts:
            y, moe_stats = y
        return x + scaled(y), (aux, moe_stats)
    return x + scaled(y), aux


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
    for sfx in ("", "_win"):  # a stack a kind of attention layer
        if all(k + sfx in out for k in ("wq", "wk", "wv")):
            out["wqkv" + sfx] = cat(*(out.pop(k + sfx) for k in ("wq", "wk", "wv")))
    if all(k in out for k in ("w1", "w3")):
        out["w13"] = cat(out.pop("w1"), out.pop("w3"))
    return out


def layer_schedule(kinds: tuple) -> tuple[int, list[tuple[int, int, int]]]:
    """(period, runs) of a layer pattern: the shortest period the pattern
    repeats with, and within one period the runs of equal kinds as
    (kind, first offset in the period, length). A homogeneous stack is
    period 1 with one run of one layer; `m m m m m a m m m m` x 4 is period
    10 with runs (ssm, 0, 5), (attention, 5, 1), (ssm, 6, 4)."""
    n = len(kinds)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)))
    runs: list = []
    for off in range(period):
        if runs and runs[-1][0] == kinds[off]:
            runs[-1][2] += 1
        else:
            runs.append([kinds[off], off, 1])
    return period, [tuple(r) for r in runs]


def ragged_schedule(kinds: tuple):
    """A layer pattern as a PREFIX of runs and a pattern of run KINDS that
    repeats behind it with run lengths of its own in every period, or None
    where that holds no fewer layer bodies than `layer_schedule`'s whole
    periods. `k' k k m  k k k m  k k k m  k k m` has no whole period short of
    itself (8 runs, 8 bodies), but it is the prefix `k'` and then (k, m)
    four times with 2, 3, 3, 2 layers of k: three bodies. Returns (prefix,
    pattern, lengths): prefix a list of (kind, first layer, length), pattern
    the run kinds of a period, lengths an int array [periods, len(pattern)];
    among the splits with the fewest bodies, the shortest prefix."""
    import numpy as np

    rle: list = []
    for kind in kinds:
        if rle and rle[-1][0] == kind:
            rle[-1][1] += 1
        else:
            rle.append([kind, 1])
    best = None
    for p in range(len(rle)):
        rest = rle[p:]
        for m in range(1, len(rest) + 1):
            if len(rest) % m == 0 and all(
                    rest[i][0] == rest[i % m][0] for i in range(len(rest))):
                if best is None or p + m < best[0] + best[1]:
                    best = (p, m)
                break
    p, m = best
    if p + m >= len(layer_schedule(kinds)[1]):
        return None
    prefix, first = [], 0
    for kind, n in rle[:p]:
        prefix.append((kind, first, n))
        first += n
    lengths = np.asarray([n for _, n in rle[p:]], np.int32).reshape(-1, m)
    return prefix, [kind for kind, _ in rle[p:p + m]], lengths


def run_layers(
    cfg: LlamaConfig,
    layer_params: dict,  # stacked [L, ...] leaves (per kind: a mixer's
    # weights are stacked over the layers of ITS kind)
    x: jax.Array,  # [B, T, D]
    pos_base: jax.Array,  # scalar, or [B] per-row positions
    k_cache: jax.Array,  # [La, B, Hkv, S, hd], La = attention layers
    v_cache: jax.Array,
    rope,  # [T, head_size/2, 2] rope rows (or [B, T, ...] per-row); None =
    # no rotation; a pair (global layers' rows, windowed layers' rows) where
    # the kinds have a table each (`cfg.global_rope`)
    attn_fn=None,
    active: jax.Array | None = None,  # [B] bool: rows allowed to write cache
    unroll: int | bool = 1,
    col_fn=None,  # wo/w2 matmul override (Q80 quantized exchange)
    mm=None,  # quantized-matmul fn (x, w, layer) -> out; default ops.matmul
    mm_in=None,  # matmul for input-dim-sharded weights (see _layer)
    moe_impl: str = "auto",  # MoE compute scheme (ops.layers.moe_ffn)
    tables: jax.Array | None = None,  # i32 [B, max_blocks] block tables —
    # presence selects the paged cache layout (k/v are then page pools)
    state: "RecurrentState | None" = None,  # the state-space layers' state
    wpool: tuple | None = None,  # (kw, vw, wtables): the windowed layers'
    # own page pool and block tables (PagedKVCache); k/v/tables are then the
    # other attention layers'
    moe_stats: jax.Array | None = None,  # u32[4] expert counters to add to
) -> tuple:
    """Scan the decoder layers (any contiguous stack — the full model, or one
    pipeline stage's slice). Returns (x, k_cache, v_cache, state), and with
    `wpool` or `moe_stats` two more: the window pools (kw, vw) and the
    counters.

    A layer's kind is what `cfg.schedule_kinds` gives: softmax or latent
    attention, state-space or delta-rule, windowed or not, rotated or not,
    its feed-forward block experts or dense. A windowed layer is handed
    `window=cfg.window` (its attention masks, and the paged sweep clips its
    walk); a layer the header leaves unrotated gets no rope.

    The layers are scanned BY PERIOD of the layer pattern
    (`layer_schedule`): the scan's body holds each run of equal layers once
    (a run longer than one layer is an inner scan), so a 40-layer stack of
    period 10 compiles two state-space bodies and one attention body, and a
    homogeneous stack is the plain scan over layers it always was. The scan
    iterates over INDICES — the stacked weights stay closed-over and
    un-sliced, so the Pallas kernels can DMA-index them with zero copies
    (ops/pallas/q40_matmul.py docstring); each kind counts its own layers
    (`ai` into the attention stacks and the cache, `si` into the state-space
    stacks and the state). The caches ride in one of two ways, decided by
    what the call was given:

    * a page pool whose attention function is the fused paged kernel
      (`tables` and `attn_fn.fused_kv_scatter`): the whole stacked pools are
      CARRIED beside x, the kernel indexes the layer, and every layer's
      aliased call updates the one buffer in place — nothing pool-sized or
      slice-sized is cut out, put back or copied;
    * every other layout (dense caches, a pipeline stage's slice, the sp
      ring, the paged gather route): the per-layer slices are the scan's
      xs and ys, as XLA attention over a layer's slice wants them.

    The recurrent state is always carried whole and updated in place.

    A pattern with no whole period short of itself (a leading layer of a
    kind of its own, a last period cut short) is scanned as
    `ragged_schedule` splits it: the prefix's runs once, then a scan over
    periods of run KINDS in which a run whose length differs by period is a
    `fori_loop` of that (traced) length, each kind's body once; the layer
    and per-kind indices a run starts at are looked up by period. The caches
    ride in the carry there, whatever the route.

    `unroll`: passed to lax.scan — trades compile time for cross-layer
    scheduling freedom."""
    if attn_fn is None:
        if tables is None:
            attn_fn = gqa_attention
        else:
            from dllama_tpu.ops.layers import paged_gqa_attention

            attn_fn = paged_gqa_attention
    kinds = cfg.schedule_kinds or (int(LayerKind.ATTENTION),) * k_cache.shape[0]
    period, runs = layer_schedule(kinds)
    n_periods = len(kinds) // period
    ragged = ragged_schedule(kinds)
    # a layer that holds cache rows (softmax or latent attention), as
    # against one that holds recurrent state
    is_attn_kind = lambda kind: (kind & SCHEDULE_KIND_MASK) not in STATE_KINDS
    if cfg.layer_ffn:
        # dense and expert layers are stacked apart: a layer's index into
        # its own feed-forward kind's stacks, looked up by the traced layer
        ffn_ix = jnp.asarray([cfg.ffn_index(i) for i in range(cfg.n_layers)],
                             jnp.int32)
        ffn_of = lambda kind, li: dict(
            dense_ffn=bool(kind & SCHEDULE_DENSE_FFN), fi=ffn_ix[li])
    else:
        ffn_of = lambda kind, li: {}
    a_pp = sum(n for kind, _, n in runs if is_attn_kind(kind))
    s_pp = period - a_pp
    two_pools = wpool is not None  # a pool a kind: (kw, vw, wtables)
    # a layer's index among the cache-holding layers of its kind, windowed
    # or global, looked up by the traced layer where the period's arithmetic
    # does not give it: into the attention stacks where they are stacked
    # apart, and into a pool a kind under a ragged pattern
    kind_ix = (jnp.asarray([cfg.kind_index(i) for i in range(cfg.n_layers)],
                           jnp.int32)
               if cfg.window_heads or (two_pools and ragged is not None)
               else None)
    rope_g, rope_w = rope if isinstance(rope, tuple) else (rope, rope)
    w_pp = (sum(n for kind, _, n in runs if kind & SCHEDULE_WINDOWED)
            if two_pools else 0)
    kernel = tables is not None and getattr(attn_fn, "fused_kv_scatter", False)
    # the pools ride in the carry where the kernel indexes the layer itself,
    # and wherever there are two of them (a layer of the other routes then
    # cuts its slice out of the carried stack and puts it back: the CPU route)
    # ... and wherever the pattern is ragged (its runs are loops of a length
    # that is data: no slice of the cache can ride as a scan's xs)
    # ... and where no layer holds cache rows at all (the caches are empty)
    fused = kernel or two_pools or ragged is not None or not a_pp
    kwp, vwp, wtables = wpool if two_pools else (None, None, None)

    def one_layer(x, kc, vc, st, kw, vw, ms, li, ai, ci, si, kind):
        """Layer `li` of kind `kind`; kc/vc are the whole pools (fused) or
        this layer's slice; `ci` is the layer's index into ITS pool."""
        base = kind & SCHEDULE_KIND_MASK
        if not is_attn_kind(kind):
            mixer = (_kda_mixer if base == LayerKind.KDA
                     else _ssm_mixer if base == LayerKind.SSM
                     # the one recurrent kind that rotates (every layer by
                     # the model's one table)
                     else functools.partial(_retention_mixer, rope=rope_g))

            def mix(h, mm_, colmm):
                return mixer(cfg, h, layer_params, si, st, pos_base,
                             active, mm_, colmm)

            x, st = _layer(cfg, x, layer_params, li, mix, col_fn, mm, mm_in,
                           moe_impl, ms, **ffn_of(kind, li))
            if ms is not None:
                st, ms = st
            return x, kc, vc, st, kw, vw, ms

        windowed = bool(kind & SCHEDULE_WINDOWED)
        in_wpool = windowed and two_pools
        pk, pv = (kw, vw) if in_wpool else (kc, vc)
        tbl = wtables if in_wpool else tables
        lrope = (None if kind & SCHEDULE_UNROTATED
                 else rope_w if windowed else rope_g)
        if cfg.window_heads:
            ai = kind_ix[li]  # the stacks of the layer's own kind

        def mix(h, mm_, colmm):
            # the kernel indexes the layer in the carried stack; any other
            # route is handed the layer's slice (cut here where the stack is
            # carried, the scan's xs elsewhere)
            cut = fused and not kernel
            ks, vs = (pk[ci], pv[ci]) if cut else (pk, pv)
            if base == LayerKind.MLA:
                out, k2, v2 = _mla_mixer(
                    cfg, h, layer_params, ai, ks, vs, lrope, pos_base, attn_fn,
                    active, mm_, colmm, tbl, ci)
            else:
                out, k2, v2 = _attention_mixer(
                    cfg, h, layer_params, ai, ks, vs, lrope, pos_base, attn_fn,
                    active, mm_, colmm, tbl, ci, cfg.window if windowed else 0)
            if cut:
                k2 = jax.lax.dynamic_update_index_in_dim(pk, k2, ci, 0)
                v2 = jax.lax.dynamic_update_index_in_dim(pv, v2, ci, 0)
            return out, (k2, v2)

        x, aux = _layer(cfg, x, layer_params, li, mix, col_fn, mm, mm_in,
                        moe_impl, ms, **ffn_of(kind, li))
        if ms is not None:
            aux, ms = aux
        if in_wpool:
            kw, vw = aux
        else:
            kc, vc = aux
        return x, kc, vc, st, kw, vw, ms

    def period_fn(carry, xs):
        """One period: each run of equal layers once. Fused: the pools are
        in the carry. Else: `kx`/`vx` are this period's [a_pp, ...] slices
        (the scan's xs), rebuilt into its ys."""
        x, kp, vp, st, kw, vw, ms = carry
        pi, kx, vx = xs
        if not fused and a_pp == 1:  # the xs ARE the layer's slices
            kx, vx = kx[None], vx[None]
        k_out, v_out = [], []
        a_off = s_off = w_off = 0
        for kind, off, n in runs:
            is_attn = is_attn_kind(kind)
            li0 = pi * period + off
            ai0, si0 = pi * a_pp + a_off, pi * s_pp + s_off
            # the layer's index into its own pool: window layers count
            # among themselves where they have a pool of their own
            if two_pools and kind & SCHEDULE_WINDOWED:
                ci0 = pi * w_pp + w_off
            elif two_pools:
                ci0 = pi * (a_pp - w_pp) + (a_off - w_off)
            else:
                ci0 = None  # one pool: the attention layers' own count

            def run_fn(c, j_kv, kind=kind, li0=li0, ai0=ai0, si0=si0,
                       ci0=ci0, is_attn=is_attn):
                x, kp, vp, st, kw, vw, ms = c
                j, kc, vc = j_kv
                li, ai = li0 + j, ai0 + j
                ci = ai if ci0 is None else ci0 + j
                if fused or not is_attn:
                    return one_layer(x, kp, vp, st, kw, vw, ms, li, ai, ci,
                                     si0 + j, kind), None
                x, kc, vc, st, kw, vw, ms = one_layer(
                    x, kc, vc, st, kw, vw, ms, li, ai, ci, si0 + j, kind)
                return (x, kp, vp, st, kw, vw, ms), (kc, vc)

            sliced = is_attn and not fused
            kr = kx[a_off : a_off + n] if sliced else None
            vr = vx[a_off : a_off + n] if sliced else None
            c = (x, kp, vp, st, kw, vw, ms)
            if n == 1:
                c, ys = run_fn(c, (
                    0, kr[0] if sliced else None, vr[0] if sliced else None))
                if sliced:
                    ys = (ys[0][None], ys[1][None])
            else:
                c, ys = jax.lax.scan(
                    run_fn, c, (jnp.arange(n, dtype=jnp.int32), kr, vr))
            x, kp, vp, st, kw, vw, ms = c
            if sliced:
                k_out.append(ys[0])
                v_out.append(ys[1])
            if is_attn:
                a_off += n
                w_off += n if kind & SCHEDULE_WINDOWED else 0
            else:
                s_off += n
        ys = None
        if not fused and a_pp:
            ys = (jnp.concatenate(k_out) if len(k_out) > 1 else k_out[0],
                  jnp.concatenate(v_out) if len(v_out) > 1 else v_out[0])
            if a_pp == 1:
                ys = (ys[0][0], ys[1][0])
        return (x, kp, vp, st, kw, vw, ms), ys

    period_ids = jnp.arange(n_periods, dtype=jnp.int32)
    extra = lambda out: out + (((kwp, vwp) if two_pools else None, moe_stats)
                               if two_pools or moe_stats is not None else ())
    if ragged is not None:
        import numpy as np

        prefix, pattern, lengths = ragged
        attn_run = np.asarray([is_attn_kind(kind) for kind in pattern])

        def run_of(c, kind, n, li0, ai0, si0):
            """`n` layers of one kind from layer li0 on, the ai0-th of those
            with cache rows and the si0-th of those with state; `n` a Python
            int or, where a period's runs differ in length, traced: one body
            either way."""
            def body(j, c):
                ai = ai0 + j
                ci = kind_ix[li0 + j] if two_pools else ai
                return one_layer(*c, li0 + j, ai, ci, si0 + j, kind)

            if isinstance(n, int) and n == 1:
                return body(0, c)
            return jax.lax.fori_loop(0, n, body, c)

        c = (x, k_cache, v_cache, state, kwp, vwp, moe_stats)
        li = ai = si = 0
        for kind, first, n in prefix:
            c = run_of(c, kind, n, li, ai, si)
            li += n
            if is_attn_kind(kind):
                ai += n
            else:
                si += n
        # where each run of each period starts, by the three counts
        flat = lengths.reshape(-1)
        before = np.concatenate([[0], np.cumsum(flat)[:-1]])
        is_a = np.tile(attn_run, len(lengths))
        a_before = np.concatenate([[0], np.cumsum(flat * is_a)[:-1]])
        starts = jnp.asarray(np.stack(
            [li + before, ai + a_before, si + before - a_before],
            axis=-1).reshape(*lengths.shape, 3), jnp.int32)
        fixed = [int(col[0]) if (col == col[0]).all() else None
                 for col in lengths.T]
        lens = jnp.asarray(lengths)

        def ragged_period(c, pi):
            for r, kind in enumerate(pattern):
                n = lens[pi, r] if fixed[r] is None else fixed[r]
                li0, ai0, si0 = (starts[pi, r, i] for i in range(3))
                c = run_of(c, kind, n, li0, ai0, si0)
            return c, None

        (x, k_new, v_new, state, kwp, vwp, moe_stats), _ = jax.lax.scan(
            ragged_period, c, jnp.arange(len(lengths), dtype=jnp.int32),
            unroll=unroll)
        return extra((x, k_new, v_new, state))
    if fused:
        (x, k_new, v_new, state, kwp, vwp, moe_stats), _ = jax.lax.scan(
            period_fn, (x, k_cache, v_cache, state, kwp, vwp, moe_stats),
            (period_ids, None, None), unroll=unroll)
        return extra((x, k_new, v_new, state))
    by_period = lambda c: c if a_pp == 1 else c.reshape(n_periods, a_pp, *c.shape[1:])
    (x, _, _, state, _, _, moe_stats), (k_new, v_new) = jax.lax.scan(
        period_fn, (x, None, None, state, None, None, moe_stats),
        (period_ids, by_period(k_cache), by_period(v_cache)), unroll=unroll)
    return extra((x, k_new.reshape(k_cache.shape), v_new.reshape(v_cache.shape),
                  state))


def forward(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # i32 [B, T]
    pos_base: jax.Array,  # scalar i32
    cache: KVCache,
    rope_cache,  # [seq, head_size/2, 2]; a pair of tables where the global
    # layers have a rope of their own (ops/layers.build_rope_cache)
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
    table positions, gather/block-indexed attention; identical math). Either
    carries the state-space layers' RecurrentState where the model has any.

    The header's scalars apply here and in `_layer` (all 1 for a LLAMA
    file): h0 = embedding_multiplier * E[token]; logits / logits_scaling."""
    x = params["embedding"][tokens]  # [B, T, D]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    t = tokens.shape[1]
    pos_base = jnp.asarray(pos_base, jnp.int32)
    if cfg.rope_type == RopeType.NONE:
        rope = None
    elif pos_base.ndim == 1:
        idx = pos_base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [B, T]
        rope = jax.tree.map(
            lambda c: c[jnp.clip(idx, 0, c.shape[0] - 1)], rope_cache)
    else:
        rope = jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, pos_base, t, axis=0),
            rope_cache)
    paged = isinstance(cache, PagedKVCache)
    wpool = ((cache.kw, cache.vw, cache.wtables)
             if paged and cache.kw is not None else None)
    out = run_layers(
        cfg, params["layers"], x, pos_base, cache.k, cache.v, rope, attn_fn, active,
        unroll=unroll, col_fn=col_fn, mm=mm, mm_in=mm_in, moe_impl=moe_impl,
        tables=cache.tables if paged else None, state=cache.state,
        wpool=wpool, moe_stats=cache.moe_stats,
    )
    x, k_new, v_new, state = out[:4]
    more = {}
    if len(out) > 4:
        more["moe_stats"] = out[5]
        if wpool is not None:
            more.update(kw=out[4][0], vw=out[4][1])
    if last_only:
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"], cfg.norm_epsilon)
    logits = (mm or matmul)(x, params["wcls"]).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits, dataclasses.replace(cache, k=k_new, v=v_new, state=state,
                                       **more)


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
        "wq": qw((L,), cfg.dim, cfg.attn_dim),
        "wk": qw((L,), cfg.dim, cfg.kv_dim),
        "wv": qw((L,), cfg.dim, cfg.kv_dim),
        "wo": qw((L,), cfg.attn_dim, cfg.dim),
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
        "wq": stack(lambda: w(cfg.dim, cfg.attn_dim)),
        "wk": stack(lambda: w(cfg.dim, cfg.kv_dim)),
        "wv": stack(lambda: w(cfg.dim, cfg.kv_dim)),
        "wo": stack(lambda: w(cfg.attn_dim, cfg.dim)),
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

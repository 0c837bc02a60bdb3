"""Core model ops: RMSNorm, RoPE, GQA attention, activations.

jnp reference implementations — under jit XLA fuses these into the surrounding
matmuls; Pallas variants exist only where fusion isn't enough (see ops/pallas/).
Numerics follow the reference kernels (nn-cpu-ops.cpp): norms, softmax and
attention accumulate in f32 regardless of activation dtype.
"""

from __future__ import annotations

import functools
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
    if act == HiddenAct.RELU:
        return jax.nn.relu(x)
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


def yarn_freqs(spec, rot: int) -> np.ndarray:
    """YaRN's inverse frequencies over `rot` rotated dims, as the public
    `transformers` library's `yarn` rope type computes them: the plain
    f_i = theta^(-2i/rot) where a dim turns more than `beta_fast` times
    over the original context, f_i / factor where it turns fewer than
    `beta_slow` times, and a linear ramp over the frequency indices between
    the two correction dims d(b) = rot ln(orig_len / (2 pi b)) / (2 ln theta)
    (floor of d(beta_fast), ceil of d(beta_slow))."""
    half = rot // 2
    plain = 1.0 / (spec.theta ** (np.arange(half, dtype=np.float64) * 2.0 / rot))
    dim_of = lambda turns: (rot * math.log(spec.orig_len / (turns * 2 * math.pi))
                            / (2 * math.log(spec.theta)))
    low = max(math.floor(dim_of(spec.beta_fast)), 0)
    high = min(math.ceil(dim_of(spec.beta_slow)), rot - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    return plain / spec.factor * ramp + plain * (1.0 - ramp)


def rope_table(spec, head_size: int, seq_len: int) -> jax.Array:
    """[seq_len, rot/2, 2] (cos, sin) of a `RopeSpec`, rot = the leading
    `share` of the head that rotates (`apply_rope` passes the dims behind it
    through), both multiplied by the spec's attention factor."""
    rot = int(head_size * spec.share)
    if spec.type == RopeType.YARN:
        freqs = yarn_freqs(spec, rot)
    else:
        freqs = 1.0 / (spec.theta ** (np.arange(rot // 2, dtype=np.float64) * 2.0 / rot))
    angles = np.outer(np.arange(seq_len, dtype=np.float32), freqs.astype(np.float32))
    cache = np.stack([np.cos(angles), np.sin(angles)], axis=-1) * spec.attn_factor
    return jnp.asarray(cache, dtype=jnp.float32)


def build_rope_cache(cfg: LlamaConfig, seq_len: int | None = None):
    """Precomputed [seq_len, head_size/2, 2] (cos, sin) table, f32.

    The analog of the reference's per-node rope_cache buffer
    (nn-cpu-ops.cpp:1082-1102), computed for the *interleaved-pair* layout the
    `.m` format stores Q/K in (converter permutation, convert-hf.py:11-14).

    A model whose global layers have a rope of their own (`cfg.global_rope`)
    gets the pair (global table, this table): `models/llama.forward` cuts the
    rows of both and a layer is handed its kind's. A latent model's table
    spans its shared key dims (`cfg.rope_dims`) and is its only one
    (`cfg.rope_spec` where the header describes it).
    """
    seq_len = seq_len or cfg.seq_len
    if cfg.rope_spec is not None:
        return rope_table(cfg.rope_spec, cfg.rope_dims, seq_len)
    half = cfg.rope_dims // 2
    freqs = 1.0 / (cfg.rope_theta ** (np.arange(half, dtype=np.float64) * 2.0 / cfg.rope_dims))
    freqs = freqs.astype(np.float32)
    if cfg.rope_type == RopeType.LLAMA3_1 and cfg.rope_scaling_factor != 1.0:
        freqs = llama31_scale_freqs(freqs, cfg)
    t = np.arange(seq_len, dtype=np.float32)
    angles = np.outer(t, freqs)  # [S, half]
    cache = jnp.asarray(np.stack([np.cos(angles), np.sin(angles)], axis=-1),
                        dtype=jnp.float32)
    if cfg.global_rope is not None:
        return rope_table(cfg.global_rope, cfg.head_size, seq_len), cache
    return cache


def apply_rope(x: jax.Array, rope: jax.Array) -> jax.Array:
    """Rotate interleaved pairs: x[..., 2i], x[..., 2i+1] by angle pos*freq_i.

    x: [B, T, H, head_size]; rope: [T, head_size/2, 2] rows already gathered
    for the absolute positions of the T tokens — or [B, T, head_size/2, 2]
    when rows differ per sequence (continuous batching: per-slot positions).
    A table of fewer pairs than the head holds rotates the LEADING dims of
    the head; the dims behind them pass through.
    """
    b, t, h, hs = x.shape
    rot = 2 * rope.shape[-2]
    if rot < hs:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], rope), x[..., rot:]], axis=-1)
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


def router_logits(h: jax.Array, gate: jax.Array) -> jax.Array:
    """The router's [B, T, E] logits in float32, whatever h is stored as:
    float32 operands at the highest matmul precision (the TPU's default
    would round the router's weights to bfloat16 inside the MXU; E columns
    cost nothing), because a logit decides WHICH experts a row meets."""
    return jnp.einsum("btd,de->bte", h.astype(jnp.float32),
                      gate.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def keep_expert_groups(choose: jax.Array, groups: int, kept: int):
    """Group-limited selection (the DeepSeek-V3 family's): the E columns of
    `choose` [..., E] (score + selection bias, float32) lie in `groups`
    contiguous groups of equal size; a group's score is the sum of its two
    largest entries, and the `kept` groups with the largest score stay.
    Returns (`choose` with every other group's entries at -inf, so that a
    top k over it chooses among the kept groups alone; bool [..., groups],
    which groups were kept)."""
    by_group = choose.reshape(*choose.shape[:-1], groups, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, top_groups = jax.lax.top_k(group_score, kept)
    keep = jnp.any(top_groups[..., None] == jnp.arange(groups), axis=-2)
    limited = jnp.where(keep[..., None], by_group, -jnp.inf)
    return limited.reshape(choose.shape), keep


def expert_tile_rows(rows: int, e: int) -> int:
    """Rows a tile of the grouped expert kernel holds: a whole bf16 tile (16)
    while an expert sees a few rows (a decode step: the block-dot walk, one
    grid step a touched expert); once the mean group passes a tile (a prefill
    slice: the dequantising walk), the power of two from 32 up that holds the
    mean group and a third more, so that nearly every expert is ONE tile: a
    second tile of an expert dequantises its weight again (a 512-row slice's
    48 rows an expert in tiles of 64 read 245 / 212 us on SmallThinker's two
    projection shapes where tiles of 32 read 418 / 383 and tiles of 128
    309 / 276; `kbench.py expert`, my chip run, PR 39)."""
    if rows <= 16 * e:
        return 16
    return next(t for t in (32, 64, 128, 256) if 3 * t * e >= 4 * rows or t == 256)


def expert_groups(topi: jax.Array, e: int, tm: int):
    """The layout the grouped expert kernel reads, from the router's choices,
    by COUNTING over the N*k (token, choice) rows: one [N*k, E] one-hot
    compare gives an expert's rows (its column sums), a row's rank in its
    group (the rows above it in its own column, `_rank_in_group`: token
    order is kept), and through the experts' cumulative tile counts where
    the row stands. No sort, no scatter, no loop and no table looked up over the
    padded order. `topi` [N, k] expert ids; an id outside [0, E) (a share's
    sentinel) is a row of zeros in the one-hot and stands nowhere. The rows
    are put in expert order and each expert's group is padded to whole tiles
    of `tm` rows; T = min(E, N*k) + N*k // tm tiles always hold them. Returns

      pos   i32[N, k]  where (token, choice) stands in the padded order (0
                       for a choice that stands nowhere)
      tile_expert i32[T], tile_src i32[T]  for the kernel's index maps: the
                       expert a tile reads and the tile's own index, both
                       frozen at the last live tile for the dead ones behind
                       it, so a dead tile moves no bytes
      n_live i32       tiles that hold a row
      sizes i32[E]     rows an expert received"""
    n, k = topi.shape
    r = n * k
    t = min(e, r) + r // tm
    experts = jnp.arange(e, dtype=jnp.int32)
    hot = topi.reshape(r, 1).astype(jnp.int32) == experts  # [r, E]
    sizes = jnp.sum(hot.astype(jnp.int32), axis=0)
    tiles = (sizes + tm - 1) // tm
    # (E is small: a cumulative sum over it is a masked [E, E] sum)
    tile_end = jnp.sum(jnp.where(experts[:, None] <= experts, tiles[:, None], 0), axis=0)
    first_row = (tile_end - tiles) * tm  # where an expert's group starts
    pos = jnp.sum(jnp.where(hot, first_row + _rank_in_group(hot), 0), axis=1)
    n_live = tile_end[-1]
    tile_ids = jnp.minimum(jnp.arange(t, dtype=jnp.int32), jnp.maximum(n_live - 1, 0))
    # the expert of tile t: how many experts' tiles end at or before it (the
    # last expert's never do, for a live tile)
    tile_expert = jnp.sum((tile_end[:-1] <= tile_ids[:, None]).astype(jnp.int32), axis=1)
    return pos.reshape(n, k), tile_expert, tile_ids, n_live, sizes


#: rows a block of `_rank_in_group`'s triangular dot holds (a block's column
#: sums, at most this, are whole numbers bfloat16 holds exactly)
_RANK_BLOCK = 256


def _rank_in_group(hot: jax.Array) -> jax.Array:
    """i32[r, E]: for each row of the [r, E] one-hot, the rows above it in
    each column (the exclusive cumulative sum down the columns), through the
    MXU: a strictly lower triangular 0/1 matrix against the one-hot, past
    512 rows in blocks of `_RANK_BLOCK`, the blocks above a block counted by
    a second such dot. Exact: 0/1 and counts up to 256 in bfloat16, float32
    sums. (A `cumsum` is a `reduce-window` to XLA: 3 us at 192 rows and 100
    at 3,072 where this reads under 1 and 5; my chip run, PR 43.)"""
    r, e = hot.shape
    blk = r if r <= 2 * _RANK_BLOCK else _RANK_BLOCK  # (one block: one dot)
    below = lambda m: jnp.tril(jnp.ones((m, m), jnp.bfloat16), -1)
    c = jnp.pad(hot.astype(jnp.bfloat16), ((0, -r % blk), (0, 0))).reshape(-1, blk, e)
    before = jnp.einsum("ij,bje->bie", below(blk), c, preferred_element_type=jnp.float32)
    if c.shape[0] > 1:
        sums = jnp.sum(c.astype(jnp.float32), axis=1).astype(jnp.bfloat16)
        before = before + jnp.dot(below(c.shape[0]), sums,
                                  preferred_element_type=jnp.float32)[:, None]
    return before.reshape(-1, e)[:r].astype(jnp.int32)


def expert_rows(h: jax.Array, pos: jax.Array, held: jax.Array | None, rows: int,
                by_dot: bool) -> jax.Array:
    """h [N, D] laid out in the padded order of `expert_groups`: [rows, D]
    with token i's row at each of pos[i, :] (`held` bool[N, k]: the choices
    that stand somewhere). Every padded position is compared with the N*k
    real ones (positions along the lanes), never looked up in a table over
    the padded order. `by_dot`: the 0/1 matrix [N, rows] places the rows
    through the MXU (exact: one 1 a position; a pad row is zero); else the
    matrix gives each position its token and the rows are gathered (a pad
    row repeats token 0, never read back). Either way a position holds its
    own token's row and nothing of another's: the dot sums over all N
    tokens, and 0 x NaN is NaN, so a token whose row is not finite goes in
    as a row of zeros there (`moe_ffn` gives it back as NaN)."""
    n, k = pos.shape
    at = pos if held is None else jnp.where(held, pos, -1)
    # [N, rows], the padded positions along the lanes: does token i stand at p
    place = jnp.any(at[:, :, None] == jnp.arange(rows, dtype=jnp.int32), axis=1)
    if by_dot:
        return jnp.einsum("np,nd->pd", place.astype(h.dtype),
                          jnp.where(finite_rows(h)[:, None], h, 0),
                          preferred_element_type=h.dtype)
    token = jnp.arange(n, dtype=jnp.int32)[:, None]
    return h[jnp.sum(jnp.where(place, token, 0), axis=0)]


def finite_rows(h: jax.Array) -> jax.Array:
    """bool[N]: the rows of h [N, D] that hold no NaN and no infinity."""
    return jnp.all(jnp.isfinite(h), axis=-1)


def moe_ffn(
    cfg: LlamaConfig,
    h: jax.Array,  # [B, T, D] (already rms-normed)
    gate: jax.Array | None,  # router [D, E] f32 (None where `logits` is given)
    w1, w2, w3,  # expert stacks: [E, D, F], [E, F, D], [E, D, F] (QTensor or
    # dense); with `layer` the layer-stacked [L, E, ...] arrays themselves
    impl: str = "auto",  # 'auto' | 'grouped' | 'dispatch' | 'sort' | 'dense'
    capacity_factor: float = 2.0,
    logits: jax.Array | None = None,  # [B, T, E] f32 router logits computed
    # by the caller (which knows WHERE the router reads)
    layer=None,  # traced layer index into layer-stacked expert weights
    stats: jax.Array | None = None,  # u32[4] running counters, see below
    bias: jax.Array | None = None,  # f32 [E] selection bias (sigmoid router)
):
    """Sparse MoE FFN: softmax over the top-k router logits (= softmax over
    all, top k, renormalised), gated experts act(w1 x) * w3 x -> w2 with the
    header's activation, probability-weighted combine in float32.

    A SIGMOID router (`cfg.router_sigmoid`): scores s = sigmoid(logits), the
    top k of s + `bias` are chosen (the bias decides WHO is chosen and is
    left out of the weights), the weights are the chosen scores over their
    sum (+ 1e-20), and either router's weights are multiplied by
    `cfg.routed_scale`.

    ONE CHIP'S SHARE (`cfg.experts_held` > 0): the stacks hold experts
    [expert_offset, expert_offset + held) of the n_experts the router
    chooses among. Routing and renormalisation are over all of them; only
    the chosen experts that are held are computed and summed, and what the
    absent ones would add is left out (the other chips' part of the layer).
    The jnp route of a share is `dense`. `stats` then has a fifth counter:
    [0] counts the rows that landed on held experts, [4] every routed row.

    GROUP-LIMITED SELECTION (`cfg.n_expert_groups` > 1, sigmoid router):
    the top k is taken among the experts of the `cfg.expert_groups_kept`
    groups that `keep_expert_groups` keeps, in float32 like every choice;
    the weights are as above. `stats` then ends in two more counters: the
    tokens routed, and those whose kept groups include a group with an
    expert held here (a share that is one group sees only those tokens).

    The reference *parses* N_EXPERTS from the header and its converter emits
    expert tensors, but the runtime has no MoE graph (SURVEY.md §2.4 — EP row);
    this is the capability it never shipped.

    Compute schemes:
    * ``grouped`` (what `auto` resolves to where the Q40 Pallas kernels
      serve, engine/kernel_select.resolve_moe_impl): the rows are put in
      expert order by counting (`expert_groups`: a one-hot compare, sums and
      small dots; no sort, scatter or lookup over the padded order), laid
      out for the kernel (`expert_rows`), and ONE grouped Q40 kernel a projection
      (ops/pallas/q40_matmul.q40_expert_matmul) reads each touched expert's
      packed tile and scales from the stacked [L, E, ...] weights by
      scalar-prefetched (layer, expert, tile); experts with no row move no
      bytes and no dequantised copy of an expert is ever written to HBM.
      The way back is a gather of the N*k real rows of the result (a tile no
      row reached is never written: a select, never a product, drops it).
    * ``sort`` (the jnp default for T*B >= E): MegaBlocks-style grouped GEMM
      — sort the N*k (token, choice) rows by expert id (argsort + gathers, no
      scatters) and run ragged segment matmuls (``lax.ragged_dot``) over the
      DEQUANTISED expert stack. Exact like dense (no capacity drops).
    * ``dispatch``: GShard-style capacity-bucketed dispatch — each expert
      processes a fixed buffer of C = ~cf*k*N/E token rows (static shapes).
      Tokens over an expert's capacity lose that expert's contribution
      (standard switch-transformer semantics; cf=2 makes drops rare), and
      the ``.at[].add`` combine may serialize on TPU; never timed on a chip.
    * ``dense``: every expert runs on every token, combine weights zero the
      unrouted ones. Exact (no capacity drops) and gather-free — the
      correctness reference, and the cheaper choice for tiny batches where
      capacity C would equal N anyway.

    The jnp schemes dequantise the whole expert stack (`_dense_w`): they are
    the CPU route and the parity reference, not what a chip serves.

    With ``stats`` (u32[4]) the call returns ``(out, stats')``: the counters
    plus this call's token-expert rows, experts with a row, 1 (a layer-step)
    and the longest group — summed on the device, read by the engine with a
    launch's tokens (obs/instruments MOE_*)."""
    e, k = cfg.n_experts, cfg.n_active_experts
    b, t, d = h.shape
    n = b * t
    if impl == "auto":
        impl = "sort" if n >= e else "dense"
    if logits is None:
        logits = router_logits(h, gate)
    kept = None
    if cfg.router_sigmoid:
        score = jax.nn.sigmoid(logits.astype(jnp.float32))
        choose = score if bias is None else score + bias.astype(jnp.float32)
        if cfg.grouped_routing:
            choose, kept = keep_expert_groups(
                choose, cfg.n_expert_groups, cfg.expert_groups_kept)
        _, topi = jax.lax.top_k(choose, k)
        topv = jnp.take_along_axis(score, topi, axis=-1)
        probs = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    else:
        topv, topi = jax.lax.top_k(logits.astype(jnp.float32), k)
        probs = jax.nn.softmax(topv, axis=-1)  # [B, T, k]
    if cfg.routed_scale != 1.0:
        probs = probs * cfg.routed_scale
    mine = None
    if cfg.experts_held:
        # this chip's share: a choice outside it becomes the sentinel `e`,
        # which every scheme below drops, with weight 0
        e = cfg.experts_held
        local = topi - cfg.expert_offset
        mine = (local >= 0) & (local < e)
        topi = jnp.where(mine, local, e)
        probs = jnp.where(mine, probs, 0.0)
        if impl != "grouped":
            impl = "dense"
    if layer is not None and impl != "grouped":
        from dllama_tpu.ops.quant import slice_leaf

        w1, w2, w3 = (slice_leaf(w, layer) for w in (w1, w2, w3))

    def done(out, sizes=None):
        out = out.reshape(b, t, d).astype(h.dtype)
        if stats is None:
            return out
        if sizes is None:
            sizes = jnp.bincount(topi.reshape(-1), length=e)
        rows = jnp.asarray(n * k) if mine is None else jnp.count_nonzero(mine)
        return out, stats + jnp.stack(
            [rows, jnp.count_nonzero(sizes), jnp.asarray(1), sizes.max()]
            + ([] if mine is None else [jnp.asarray(n * k)])
            + group_counts()).astype(stats.dtype)

    def group_counts():
        """Tokens routed, and those whose kept groups include one with an
        expert held here (every token where all are held); [] where the
        selection is not group-limited."""
        if kept is None:
            return []
        per = cfg.n_experts // cfg.n_expert_groups
        first = cfg.expert_offset // per
        last = (cfg.expert_offset + cfg.n_held_experts - 1) // per
        here = jnp.any(kept[..., first:last + 1], axis=-1)
        return [jnp.asarray(n), jnp.count_nonzero(here)]

    if impl == "grouped":
        from dllama_tpu.ops.matmul import device_platform
        from dllama_tpu.ops.pallas.q40_matmul import q40_expert_matmul

        # (of a share's routed rows, the held experts' part is expected here)
        tm = expert_tile_rows(n * k if mine is None else n * k * e // cfg.n_experts, e)
        pos, tile_expert, tile_src, n_live, sizes = expert_groups(
            topi.reshape(n, k), e, tm)
        held = None if mine is None else mine.reshape(n, k)
        # a decode step's rows (one a sequence) are placed by the dot; a
        # slice's are gathered: the dot is no slower there (392 / 416 us a
        # layer-step at 256 rows), but the v5e compiler's memory space
        # assignment aborts on a program that holds BOTH a decode step's
        # and a slice's placement dot (every hybrid program of 2-256 slice
        # rows at Laguna's widths; my chip runs, PR 43; the cause is not
        # known: `experiments/warm_compile.py` is the check)
        by_dot = t == 1
        xs = expert_rows(h.reshape(n, d), pos, held, len(tile_src) * tm, by_dot)
        mm = functools.partial(
            q40_expert_matmul, layer=layer, tile_expert=tile_expert,
            tile_src=tile_src, n_live=n_live, tm=tm,
            interpret=device_platform() != "tpu")
        g = mm(xs, w1)
        up = mm(xs, w3)
        act = (activation(g, cfg.hidden_act) * up).astype(h.dtype)
        y = mm(act, w2)  # f32 [T*tm, D]
        yk = y[pos]
        if held is not None:
            # a choice this chip does not hold stands nowhere in the padded
            # order: a select, since a tile no row reached is never written
            yk = jnp.where(held[..., None], yk, 0.0)
        out = jnp.sum(yk * probs.reshape(n, k)[..., None], axis=1)
        if by_dot:
            # a sequence whose row went in as zeros (`expert_rows`) comes
            # out as one that met its experts would: not finite, and alone
            # in that (the engine's guard fails that request and no other)
            out = jnp.where(finite_rows(h.reshape(n, d))[:, None], out, jnp.nan)
        return done(out, sizes)

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
        return done(out)

    if impl == "dispatch":
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
        return done(out)

    weights = jnp.sum(
        jax.nn.one_hot(topi, e, dtype=probs.dtype) * probs[..., None], axis=-2
    )  # [B, T, E]
    g = jnp.einsum("btd,edf->btef", h, _dense_w(w1, h.dtype))
    up = jnp.einsum("btd,edf->btef", h, _dense_w(w3, h.dtype))
    act = activation(g.astype(jnp.float32), cfg.hidden_act).astype(h.dtype)
    y = jnp.einsum("btef,efd->bted", act * up, _dense_w(w2, h.dtype))
    out = jnp.einsum("bted,bte->btd", y.astype(jnp.float32), weights)
    return done(out)


def gqa_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_cache: jax.Array,  # [B, Hkv, S, hd]
    v_cache: jax.Array,  # [B, Hkv, S, hd]
    pos_base: jax.Array,  # i32 scalar, or [B] per-sequence positions
    window: int | None = None,  # rows a query sees, itself included
) -> jax.Array:
    """Causal GQA over the full KV cache (nn-cpu-ops.cpp:752-787 equivalent).
    With `window`, key j is visible to the query at row i iff
    i - window < j <= i.

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
        row = pos_base[:, None, None] + qoff[None]  # [B, t, s]
        mask = spans[None] <= row
        if window:
            mask = mask & (spans[None] > row - window)
        mask = mask[:, None, None]
    else:
        mask = spans <= pos_base + qoff
        if window:
            mask = mask & (spans > pos_base + qoff - window)
        mask = mask[None, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bthgd", probs, vf)
    return out.reshape(b, t, hq, hd).astype(q.dtype)


def latent_attention(
    q: jax.Array,  # [B, T, H, W]: a head's absorbed query, latent | shared dims
    rows: jax.Array,  # [B, S, W]: the cache rows, one a token for all heads
    pos_base: jax.Array,  # i32 scalar, or [B] per-sequence positions
    scale: float,
    rank: int,  # the leading `rank` dims of a row are also its value
) -> jax.Array:
    """Causal attention of every head over ONE shared row a token (latent
    attention in its absorbed form): scores q . row, output the softmax mix
    of the rows' first `rank` dims, f32 [B, T, H, rank]. The jnp route and
    the parity reference of ops/pallas/paged_attention's latent sweep."""
    b, t, _, _ = q.shape
    s = rows.shape[1]
    rf = rows.astype(jnp.float32)
    scores = jnp.einsum("bthw,bsw->bhts", q.astype(jnp.float32), rf) * scale
    pos = jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32), (b,))
    row = pos[:, None, None] + jnp.arange(t, dtype=jnp.int32)[None, :, None]
    mask = jnp.arange(s, dtype=jnp.int32)[None, None, :] <= row  # [B, T, S]
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhts,bsr->bthr", probs, rf[..., :rank])


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
    window: int | None = None,
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
                         paged_view(v_pool, tables), pos_base, window)

"""Plain reference of the decoder `benchmark/layouts/laguna.py` lays out:
attention whose head count, rope and window go by the layer's kind, QK-norm,
a gate a head, a leading dense layer and sigmoid-routed experts with a
shared expert. Pre-norm residual blocks, h = x + Attn_kind(rmsnorm(x; g_a));
y = h + FFN(rmsnorm(h; g_f)); logits = rmsnorm(x_L; g) @ W_head.

Attention, on n = rmsnorm(x; g_a), H = the kind's query heads (global or
windowed), 8 kv heads at the published sizes, head size hd:

    q = n W_q as [H, hd], k = n W_k, v = n W_v as [kv, hd]
    q = rmsnorm_hd(q; g_q), k = rmsnorm_hd(k; g_k)     per head, the gains
                                                       shared by the heads
    rotation of interleaved pairs (2i, 2i+1), by kind:
      windowed: all hd dims, f_i = theta^(-2i/hd)
      global:   the leading `share` of the head (rot dims; the rest pass
                through), YaRN: plain f_i = theta^(-2i/rot), interpolated
                f_i / factor, blended by a linear ramp over the frequency
                indices between low = floor d(beta_fast) and high =
                ceil d(beta_slow), d(b) = rot ln(orig / (2 pi b)) / (2 ln theta);
                cos and sin times the attention factor
    scores q k^T / sqrt(hd), key j visible to row i iff j <= i (and
    j > i - window on a windowed layer), softmax, times v: a as [H, hd]
    gate = softplus(n W_g) as [H], float32; a_h = gate_h * a_h
    Attn = concat(a) W_o

Feed-forward: a dense layer is (silu(b W1) * b W3) W2 at the dense width.
An expert layer: s = sigmoid(b W_r) over ALL the experts routed among; the
top k of s + bias are chosen; weight_i = s_i / (sum of the chosen s + 1e-20)
* routed_scaling_factor (the bias is not in the weights, the weight is on the
expert's OUTPUT); out = sum_i weight_i expert_i(b) + shared(b), each a SwiGLU.
THE SHARE: the file holds experts [offset, offset + held); routing and the
weights are over all of them, and only the chosen experts that are held are
summed: what the absent ones would add is left out, here as in the program.

All in float32 under `jax.default_matmul_precision("highest")`, no kernels,
no cache, and no import from the program: the weights are the bytes
`benchmark/files.py` wrote, found through the layout and dequantised here.
The feed-forward block and the Q40 reader are imported from
`benchmark/reference/kimi_linear.py` (the same equations on the same header
names: `_combine_weights`, `_swiglu`, `ffn_block`); attention is this file's.

Departures from the published description (the configuration's `assumed`
lists each with its ground), one line each here: QK-norm is an RMS norm over
the head BEFORE the rotation with one gain vector a layer (`_attention`); the
gate is per head, softplus, read from the attention block's normed input
(`_attention`); the router is sigmoid / top k of score + bias / renormalised
/ scaled (kimi_linear's `_combine_weights`); the feed-forward blocks are SwiGLU with
SiLU (its `_swiglu`); pairs rotate interleaved, this repo's `.m` pairing (`_rotate`).
Departures from a textbook forward pass, none in the arithmetic: the loop is
layer-outer and sequence-inner (a layer is dequantised once); attention runs
in blocks of query rows (`lax.map`) so that 4,096-row prompts fit; every held
expert is applied to every row of every sequence with combine weight 0 off
its rows (no shape depends on the routing).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.layouts import laguna as layout
# the feed-forward half is `kimi-linear-48b-a3b`'s, to the line: a dense
# SwiGLU layer, the sigmoid router over all the experts routed among, the
# held share, the shared expert (`ffn_block`), and the byte readers
from benchmark.reference.kimi_linear import (  # noqa: F401
    _f32, _head, _q40, _rms_norm, ffn_block)

PRECISION = "highest"
Q_ROWS = 512  # query rows an attention block holds


def rope_freqs(rope: dict, rot: int) -> np.ndarray:
    """The inverse frequencies of `rot` rotated dims: plain (type 0), or
    YaRN (type 4) as the public `transformers` library's `yarn` rope type
    computes them."""
    half = rot // 2
    plain = 1.0 / (rope["theta"] ** (np.arange(half, dtype=np.float64) * 2.0 / rot))
    if rope["type"] != layout.ROPE_YARN:
        return plain
    d = lambda turns: (rot * math.log(rope["orig_len"] / (turns * 2 * math.pi))
                       / (2 * math.log(rope["theta"])))
    low = max(math.floor(d(rope["beta_fast"])), 0)
    high = min(math.ceil(d(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
    return plain / rope["factor"] * ramp + plain * (1 - ramp)


def _rotate(x, freqs, scale: float):
    """x [T, H, hd]: rotate the pairs (2i, 2i+1) of the leading
    2 * len(freqs) dims by position * freqs[i], cos and sin times `scale`;
    the dims behind them pass through."""
    t, h, hd = x.shape
    rot = 2 * len(freqs)
    ang = jnp.asarray(np.outer(np.arange(t, dtype=np.float32),
                               np.asarray(freqs, np.float32)), jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    xp = x[..., :rot].reshape(t, h, rot // 2, 2)
    x0, x1 = xp[..., 0], xp[..., 1]
    turned = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                       axis=-1).reshape(t, h, rot)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _attention(n_heads: int, n_kv_heads: int, head: int, eps: float,
               window: int, freqs: tuple, rope_scale: float, x, w):
    """The attention block on one whole sequence x [T, D] -> x + out;
    `window` 0 = every earlier key."""
    t = x.shape[0]
    n = _rms_norm(x, w["rms_att"], eps)
    q = _rms_norm((n @ w["wq"].T).reshape(t, n_heads, head), w["q_norm"], eps)
    k = _rms_norm((n @ w["wk"].T).reshape(t, n_kv_heads, head), w["k_norm"], eps)
    v = (n @ w["wv"].T).reshape(t, n_kv_heads, head)
    q, k = _rotate(q, freqs, rope_scale), _rotate(k, freqs, rope_scale)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    key = jnp.arange(t)[None, :]
    n_blocks = -(-t // Q_ROWS)
    q = jnp.pad(q, ((0, n_blocks * Q_ROWS - t), (0, 0), (0, 0)))

    def block(args):
        """Q_ROWS query rows against every key; a row of the padding past
        the sequence's end repeats the last row's mask and is cut below."""
        qb, q0 = args
        qi = jnp.minimum(q0 + jnp.arange(Q_ROWS), t - 1)[:, None]
        seen = key <= qi
        if window:
            seen = seen & (key > qi - window)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(head)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    att = jax.lax.map(block, (q.reshape(n_blocks, Q_ROWS, n_heads, head),
                              jnp.arange(n_blocks) * Q_ROWS))
    att = att.reshape(n_blocks * Q_ROWS, n_heads, head)[:t]
    gate = jax.nn.softplus(n @ w["attn_gate"].T)  # [T, H]
    return x + (att * gate[..., None]).reshape(t, n_heads * head) @ w["wo"].T


def attention_block(s: dict, views: dict, li: int, xs: list) -> list:
    """Layer li's attention block on each whole sequence x [T, D] of `xs`
    -> x + out (the layer is dequantised once)."""
    p, sfx = f"layers.{li}.", "_win" if s["windowed"][li] else ""
    w = {n: _q40(views[p + n + sfx]) for n in ("wq", "wk", "wv", "wo")}
    w.update({n: _f32(views[p + n + sfx])
              for n in ("q_norm", "k_norm", "attn_gate")})
    w["rms_att"] = _f32(views[p + "rms_att"])
    if s["windowed"][li]:
        rope, window, scale = ({"type": layout.ROPE_PLAIN, "theta": s["rope_theta"]},
                               s["window"], 1.0)
        rot = s["head_size"]
    else:
        rope, window, scale = s["g_rope"], 0, s["g_rope"]["attn_factor"]
        rot = int(s["head_size"] * rope["share"])
    freqs = tuple(float(f) for f in rope_freqs(rope, rot))
    return [_attention(s["heads"][li], s["n_kv_heads"], s["head_size"],
                       s["norm_epsilon"], window, freqs, float(scale), x, w)
            for x in xs]


def logits_at(model_path: str, sequences: list, positions: list) -> list:
    """For each token sequence (1-d int array), the float32 logits
    [len(positions[i]), vocab] at the positions asked for, from one full
    causal forward pass over the whole sequence."""
    s, views = layout.tensor_views(model_path)
    emb = np.asarray(views["embedding"][0]).view(np.float32).reshape(
        views["embedding"][1])
    eps = s["norm_epsilon"]
    with jax.default_matmul_precision(PRECISION):
        xs = [jnp.asarray(emb[np.asarray(seq, np.int64)]) for seq in sequences]
        for li in range(s["n_layers"]):
            xs = attention_block(s, views, li, xs)
            # the feed-forward block acts on each row alone: the sequences'
            # rows go through it end to end, one program for all the lengths
            h = ffn_block(s, views, li, jnp.concatenate(xs))
            xs = jnp.split(h, np.cumsum([len(x) for x in xs])[:-1])
            del h
        gain = _f32(views["final_norm"])
        wcls = _q40(views["wcls"])
        out = [np.asarray(_head(eps, x[np.asarray(pos, np.int64)], gain, wcls))
               for x, pos in zip(xs, positions)]
    return out

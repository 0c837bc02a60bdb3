"""Plain reference of the window-and-global, routed-expert decoder that
`benchmark/layouts/smallthinker.py` lays out. Layer l on the residual x:

    a   = rmsnorm(x; g_att)
    r   = a @ W_r                      64 router logits: the router reads the
                                       attention block's normed input
    q, k, v = a @ Wq, a @ Wk, a @ Wv   heads x head need not be the model's dim
    if the layer rotates: q, k = rope(q, k) on interleaved pairs (2i, 2i+1)
    s   = q k^T / sqrt(head), causal; a windowed layer: key j is visible to
          query i iff i - window < j <= i
    h   = x + softmax(s) v @ Wo
    b   = rmsnorm(h; g_ffn)
    E   = top-k(r); p = softmax(r[E])  (= softmax over all, top k, renormalised)
    out = h + sum_{e in E} p_e W2_e(relu(W1_e b) * W3_e b)

and logits = rmsnorm(x_L; g) @ W_head. All in float32 under
`jax.default_matmul_precision("highest")`, no kernels, no cache, and no
import from the program: the weights are the bytes
`benchmark/files.py` wrote, found through the layout and dequantised here
(f16 scale x (nibble - 8)).

Departures from a textbook forward pass, none in the arithmetic: the loop is
layer-outer and sequence-inner, so a layer is dequantised once for all the
sequences; attention runs in blocks of query rows (`lax.map` over one
block's program), so 7,000 x 7,000 scores of 28 heads are never alive at
once; an expert is dequantised once a layer and applied to every row of
every sequence (they are concatenated for the expert block, which acts on
each row alone) with the combine weight 0 where the row was not routed to
it: the same sum, with no shape that depends on the routing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.layouts import smallthinker as layout

PRECISION = "highest"
Q_ROWS = 512  # query rows an attention block holds


@functools.partial(jax.jit, static_argnums=(1, 2))
def _dequant_q40(raw, n_out: int, k_in: int):
    """uint8 [n_out * k_in/32 * 18] as on disk -> f32 [n_out, k_in]."""
    rec = raw.reshape(n_out, k_in // files.Q_BLOCK, files.Q40_BLOCK_BYTES)
    scale = jax.lax.bitcast_convert_type(rec[..., :2], jnp.float16)
    packed = rec[..., 2:]
    lo = (packed & 0x0F).astype(jnp.int32) - 8
    hi = (packed >> 4).astype(jnp.int32) - 8
    codes = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    w = codes * scale.astype(jnp.float32)[..., None]
    return w.reshape(n_out, k_in)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta: float):
    """x [T, H, hd]: rotate pairs (2i, 2i+1) by position * theta^(-2i/hd)."""
    t, h, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(hd // 2, dtype=np.float64) * 2.0 / hd))
    ang = jnp.asarray(np.outer(np.arange(t, dtype=np.float64), freqs),
                      jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xp = x.reshape(t, h, hd // 2, 2)
    x0, x1 = xp[..., 0], xp[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(t, h, hd)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _attention(n_heads: int, n_kv_heads: int, head: int, theta: float,
               eps: float, rotates: bool, window: int, x, w):
    """The attention block and the router's logits on one whole sequence
    x [T, D] -> (h [T, D], r [T, E]); `window` 0 = every earlier key."""
    t = x.shape[0]
    a = _rms_norm(x, w["rms_att"], eps)
    r = a @ w["moe_gate"].T
    q = (a @ w["wq"].T).reshape(t, n_heads, head)
    k = (a @ w["wk"].T).reshape(t, n_kv_heads, head)
    v = (a @ w["wv"].T).reshape(t, n_kv_heads, head)
    if rotates:
        q, k = _rope(q, theta), _rope(k, theta)
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

    # one block's program whatever the length: an unrolled loop over 14
    # blocks took the chip's compiler 20 s a sequence and kind of layer
    att = jax.lax.map(block, (q.reshape(n_blocks, Q_ROWS, n_heads, head),
                              jnp.arange(n_blocks) * Q_ROWS))
    att = att.reshape(n_blocks * Q_ROWS, n_heads * head)[:t]
    return x + att @ w["wo"].T, r


@functools.partial(jax.jit, static_argnums=(0, 1))
def _combine_weights(top_k: int, eps: float, h, r, gain):
    """(b [T, D], p [T, E]): the experts' normed input, and each expert's
    combine weight for each row, 0 where the row is not routed to it."""
    b = _rms_norm(h, gain, eps)
    topv, topi = jax.lax.top_k(r, top_k)
    p = jax.nn.softmax(topv, axis=-1)
    dense = jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], topi].set(p)
    return b, dense


@jax.jit
def _expert(b, p_e, w1, w2, w3):
    """One expert on every row, weighted: p_e [T] is 0 off its rows."""
    y = (jax.nn.relu(b @ w1.T) * (b @ w3.T)) @ w2.T
    return y * p_e[:, None]


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps: float, x, gain, wcls):
    return _rms_norm(x, gain, eps) @ wcls.T


def _f32(view):
    raw, shape, _ = view
    return jnp.asarray(np.asarray(raw).view(np.float32).reshape(shape))


def _q40(view, index=None):
    """The whole matrix, or expert `index` of an [E, out, in] stack."""
    raw, shape, _ = view
    n_out, k_in = shape[-2:]
    if index is not None:
        per = n_out * k_in // files.Q_BLOCK * files.Q40_BLOCK_BYTES
        raw = raw[index * per:(index + 1) * per]
    return _dequant_q40(jnp.asarray(np.asarray(raw)), n_out, k_in)


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
            p = f"layers.{li}."
            w = {n: _q40(views[p + n]) for n in ("wq", "wk", "wv", "wo")}
            for n in ("moe_gate", "rms_att"):
                w[n] = _f32(views[p + n])
            window = s["window"] if s["windowed"][li] else 0
            hr = [_attention(s["n_heads"], s["n_kv_heads"], s["head_size"],
                             s["rope_theta"], eps, bool(s["rotates"][li]),
                             window, x, w) for x in xs]
            del w
            gain = _f32(views[p + "rms_ffn"])
            # the expert block acts on each row alone: the sequences' rows
            # go through it end to end, one program for all the lengths
            h = jnp.concatenate([h for h, _ in hr])
            b, dense = _combine_weights(s["n_active_experts"], eps, h,
                                        jnp.concatenate([r for _, r in hr]),
                                        gain)
            del hr
            for e in range(s["n_experts"]):
                w1, w2, w3 = (_q40(views[p + n], e)
                              for n in ("moe_w1", "moe_w2", "moe_w3"))
                h = h + _expert(b, dense[:, e], w1, w2, w3)
            xs = jnp.split(h, np.cumsum([len(x) for x in xs])[:-1])
            del h, b, dense
        gain = _f32(views["final_norm"])
        wcls = _q40(views["wcls"])
        out = [np.asarray(_head(eps, x[np.asarray(pos, np.int64)], gain, wcls))
               for x, pos in zip(xs, positions)]
    return out

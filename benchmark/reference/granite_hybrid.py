"""Plain reference of the hybrid state-space / attention decoder the `.m`
layout `benchmark/layouts/granite_hybrid.py` describes (the shape of
`granitemoehybrid`, huggingface.co/ibm-granite/granite-4.0-h-micro).

With `h` the residual stream, `r` the residual multiplier, `eps` the norm's:

  h0 = embedding_multiplier * E[token]
  each layer:  h = h + r * Mix(RMSNorm(h))
               h = h + r * W2(silu(W1 u) * (W3 u)),  u = RMSNorm(h)
  logits = (E_q RMSNorm(h)) / logits_scaling,  E_q the tied head (the Q40 of E)

  Mix = attention: q, k, v projections without bias and WITHOUT any position
    rotation; causal softmax(attention_multiplier * q k^T) v over grouped kv
    heads; W_o.
  Mix = Mamba-2:  [z | xBC | dt] = W_in u   (inner | inner + 2 state | heads)
    xBC_t = silu(b + sum_{j<K} w_j * xBC_{t-(K-1)+j})   per channel, causal,
            zeros before the sequence's start
    x (heads x head), B (state), C (state) = split(xBC); one group of B, C
            shared by all heads
    dt_t = softplus(dt_t + dt_bias)        per head, no clamp
    a_t  = exp(-exp(A_log) * dt_t)         per head
    S_t  = a_t S_{t-1} + dt_t * x_t (outer) B_t     S in R^{heads x head x state}
    y_t  = S_t C_t + D * x_t
    y    = RMSNorm(y * silu(z)) * w_norm   over all inner channels: gate
           first, then norm, one group
    W_out y

All in float32 under `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching, and no import from the program: the weights are the
bytes `benchmark/files.py` wrote, found through the layout and dequantised
here (f16 scale x (nibble - 8)). The recurrence is computed AS WRITTEN, one
token after another (`lax.scan` over tokens), not in the chunked form the
program's prefill uses: the two derivations check each other.

Departures from the published forward pass: none in the arithmetic. The
loop is layer-outer, sequence-inner, so each layer is dequantised once for
all the sequences asked for; every sequence is still computed alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.layouts import granite_hybrid as layout

PRECISION = "highest"
_MAMBA = layout.KINDS.index("mamba")


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


def _attention(x, w, n_heads, n_kv_heads, head_size, scale):
    """x [T, D] normed -> [T, D]: causal attention, no rotation."""
    t = x.shape[0]
    q = (x @ w["wq"].T).reshape(t, n_heads, head_size)
    k = (x @ w["wk"].T).reshape(t, n_kv_heads, head_size)
    v = (x @ w["wv"].T).reshape(t, n_kv_heads, head_size)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return att.reshape(t, n_heads * head_size) @ w["wo"].T


def _mamba(x, w, heads, head, state, taps, eps):
    """x [T, D] normed -> [T, D]: the Mamba-2 mixer, token by token."""
    t = x.shape[0]
    inner = heads * head
    proj = x @ w["in_proj"].T
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * state],
                  proj[:, 2 * inner + 2 * state:])
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = w["conv_b"] + sum(w["conv_w"][:, j] * padded[j:j + t] for j in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(t, heads, head)
    bs, cs = xbc[:, inner:inner + state], xbc[:, inner + state:]
    dts = jax.nn.softplus(dt + w["dt_bias"])  # [T, heads]
    decay = jnp.exp(-jnp.exp(w["a_log"]) * dts)

    def step(s, row):
        x_t, b_t, c_t, dt_t, a_t = row
        s = (a_t[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, c_t) + w["d"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, head, state), jnp.float32),
                        (xs, bs, cs, dts, decay))
    y = _rms_norm(y.reshape(t, inner) * jax.nn.silu(z), w["ssm_norm"], eps)
    return y @ w["out_proj"].T


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(kind: int, sizes: tuple, x, w):
    """One decoder layer on one whole sequence x [T, D]; w holds f32
    matrices stored [out, in] and the small f32 tensors."""
    (n_heads, n_kv_heads, head_size, scale, r, eps, heads, head, state,
     taps) = sizes
    h = _rms_norm(x, w["rms_att"], eps)
    if kind == _MAMBA:
        x = x + r * _mamba(h, w, heads, head, state, taps, eps)
    else:
        x = x + r * _attention(h, w, n_heads, n_kv_heads, head_size, scale)
    h = _rms_norm(x, w["rms_ffn"], eps)
    return x + r * ((jax.nn.silu(h @ w["w1"].T) * (h @ w["w3"].T)) @ w["w2"].T)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(eps: float, div: float, x, gain, wcls):
    return (_rms_norm(x, gain, eps) @ wcls.T) / div


def _f32(view):
    raw, shape, _ = view
    return jnp.asarray(np.asarray(raw).view(np.float32).reshape(shape))


def _q40(view):
    raw, (n_out, k_in), _ = view
    return _dequant_q40(jnp.asarray(np.asarray(raw)), n_out, k_in)


_Q40 = {_MAMBA: ("in_proj", "out_proj", "w1", "w2", "w3"),
        1 - _MAMBA: ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
_F32 = {_MAMBA: ("conv_w", "conv_b", "dt_bias", "a_log", "d", "ssm_norm",
                 "rms_att", "rms_ffn"),
        1 - _MAMBA: ("rms_att", "rms_ffn")}


def logits_at(model_path: str, sequences: list, positions: list) -> list:
    """For each token sequence (1-d int array), the float32 logits
    [len(positions[i]), vocab] at the positions asked for, from one full
    causal forward pass over the whole sequence."""
    s, views = layout.tensor_views(model_path)
    emb = np.asarray(views["embedding"][0]).view(np.float32).reshape(
        views["embedding"][1])
    sizes = (s["n_heads"], s["n_kv_heads"], s["head_size"], s["attn_scale"],
             s["residual_multiplier"], s["norm_epsilon"], s["ssm_heads"],
             s["ssm_head_dim"], s["ssm_state"], s["ssm_conv"])
    with jax.default_matmul_precision(PRECISION):
        xs = [jnp.asarray(emb[np.asarray(seq, np.int64)]) * s["embedding_multiplier"]
              for seq in sequences]
        for li, kind in enumerate(s["kinds"]):
            w = {n: _q40(views[f"layers.{li}.{n}"]) for n in _Q40[kind]}
            w.update({n: _f32(views[f"layers.{li}.{n}"]) for n in _F32[kind]})
            xs = [_layer(kind, sizes, x, w) for x in xs]
            del w
        gain = _f32(views["final_norm"])
        wcls = _q40(views["wcls"])
        out = [np.asarray(_head(s["norm_epsilon"], s["logits_scaling"],
                                x[np.asarray(pos, np.int64)], gain, wcls))
               for x, pos in zip(xs, positions)]
    return out

"""Plain reference of the LLAMA-arch decoder the `.m` format describes.

RMSNorm -> q/k/v -> RoPE on interleaved pairs (the `.m` layout of q and k)
-> causal softmax attention with grouped kv heads -> wo, residual -> RMSNorm
-> SwiGLU (silu(w1 x) * w3 x) -> w2, residual; final RMSNorm; head. All in
float32 under `jax.default_matmul_precision("highest")`, no kernels, no
cache, no batching, and no import from the program: the weights are the
bytes `benchmark/files.py` wrote, found through the layout
(`benchmark/layouts/llama.py`) and dequantised here (f16 scale x (nibble -
8)). One layer's float32 weights are live at a time (0.8-0.9 GB at 7B), and
the head is computed only at the positions asked for.

Departures from a textbook forward pass: none in the arithmetic. The loop
is layer-outer, sequence-inner, so that each layer is dequantised once for
all the sequences asked for; every sequence is still computed alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.layouts import llama as layout

PRECISION = "highest"


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


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _layer(n_heads: int, n_kv_heads: int, head_size: int, theta: float,
           eps: float, x, w):
    """One decoder layer on one whole sequence x [T, D]; w holds f32
    matrices stored [out, in] and the two norm gains."""
    t = x.shape[0]
    h = _rms_norm(x, w["rms_att"], eps)
    q = _rope((h @ w["wq"].T).reshape(t, n_heads, head_size), theta)
    k = _rope((h @ w["wk"].T).reshape(t, n_kv_heads, head_size), theta)
    v = (h @ w["wv"].T).reshape(t, n_kv_heads, head_size)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(head_size)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(t, n_heads * head_size) @ w["wo"].T
    h = _rms_norm(x, w["rms_ffn"], eps)
    return x + (jax.nn.silu(h @ w["w1"].T) * (h @ w["w3"].T)) @ w["w2"].T


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps: float, x, gain, wcls):
    return _rms_norm(x, gain, eps) @ wcls.T


def _f32(view):
    raw, shape, _ = view
    return jnp.asarray(np.asarray(raw).view(np.float32).reshape(shape))


def _q40(view):
    raw, (n_out, k_in), _ = view
    return _dequant_q40(jnp.asarray(np.asarray(raw)), n_out, k_in)


def logits_at(model_path: str, sequences: list, positions: list) -> list:
    """For each token sequence (1-d int array), the float32 logits
    [len(positions[i]), vocab] at the positions asked for, from one full
    causal forward pass over the whole sequence."""
    s, views = layout.tensor_views(model_path)
    emb = np.asarray(views["embedding"][0]).view(np.float32).reshape(
        views["embedding"][1])
    with jax.default_matmul_precision(PRECISION):
        xs = [jnp.asarray(emb[np.asarray(seq, np.int64)]) for seq in sequences]
        for li in range(s["n_layers"]):
            w = {n: _q40(views[f"layers.{li}.{n}"])
                 for n in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
            w["rms_att"] = _f32(views[f"layers.{li}.rms_att"])
            w["rms_ffn"] = _f32(views[f"layers.{li}.rms_ffn"])
            xs = [_layer(s["n_heads"], s["n_kv_heads"], s["head_size"],
                         s["rope_theta"], s["norm_epsilon"], x, w) for x in xs]
            del w
        gain = _f32(views["final_norm"])
        wcls = _q40(views["wcls"])
        out = [np.asarray(_head(s["norm_epsilon"],
                                x[np.asarray(pos, np.int64)], gain, wcls))
               for x, pos in zip(xs, positions)]
    return out

"""Plain reference of the decoder `benchmark/layouts/brumby.py` lays out:
every mixer POWER RETENTION of degree 2 with a gate (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239), computed here in
its ATTENTION form: no state, no expansion of the keys, no recurrence. The
program serves the recurrent form; the two agree only if its expansion, its
decay, its normaliser and its grouping of heads are right.

Pre-norm residual blocks, h = x + Ret(rmsnorm(x; g_a)); y = h +
FFN(rmsnorm(h; g_f)); logits = rmsnorm(x_L; g) @ W_head. Retention, on n =
rmsnorm(x; g_a), H query heads, G kv heads (query head j reads kv head
j // (H / G)), head size hd:

    q = n W_q as [H, hd], k = n W_k, v = n W_v as [G, hd]
    q = rmsnorm_hd(q; g_q), k = rmsnorm_hd(k; g_k)     per head, the gains
                                                       shared by the heads
    q, k rotated: interleaved pairs (2i, 2i+1) by position * theta^(-2i/hd)
    gamma = log sigmoid(n W_g + b_g) as [G], float32   the log decay of a row
    a[t, s] = exp(sum of gamma over rows (s, t]) * (q_t . k_s / sqrt(hd))^2
              for s <= t, else 0                        every weight >= 0
    y_t = sum_s a[t, s] v_s / (sum_s a[t, s] + 1e-6)
    Ret = concat(y) W_o                                no gate, no norm on y

Feed-forward: (silu(b W1) * b W3) W2.

All in float32 under `jax.default_matmul_precision("highest")`, no kernels,
no cache, and no import from the program: the weights are the bytes
`benchmark/files.py` wrote, found through the layout and dequantised here
(the readers, the norm, the rotation and the head are
`benchmark/reference/llama.py`'s, to the line).

Departures from the published description (the configuration's `assumed`
lists each with its ground): the degree is 2; the weights are normalised by
their sum + eps; the gate is one a kv head with a bias, read from the
mixer's normed input; q and k rotate over the whole head; no output gate.
Departures from a textbook forward pass, none in the arithmetic: the loop is
layer-outer and sequence-inner (a layer is dequantised once); the weights
run in blocks of query rows (`lax.map`) so that 4,800-row sequences fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.layouts import brumby as layout
from benchmark.reference.llama import (  # noqa: F401
    PRECISION, _f32, _head, _q40, _rms_norm, _rope)

EPS = 1e-6
Q_ROWS = 512  # query rows a block of weights holds


def _retention(q, k, v, gamma):
    """q [T, H, hd], k, v [T, G, hd], gamma [T, G] -> y [T, H, hd]."""
    t, h, hd = q.shape
    g = k.shape[1]
    run = jnp.cumsum(gamma, axis=0)  # [T, G]: sum of gamma over rows [0, t]
    pad = -t % Q_ROWS
    rows = jnp.arange(t + pad).reshape(-1, Q_ROWS)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_ROWS, g, h // g, hd)
    rb = jnp.pad(run, ((0, pad), (0, 0))).reshape(-1, Q_ROWS, g)

    def block(args):
        qi, ri, ti = args  # [Q, G, J, hd], [Q, G], [Q]
        score = jnp.einsum("qgjd,sgd->gjqs", qi, k) / np.sqrt(hd)
        seen = (jnp.arange(t)[None, :] <= ti[:, None])[None]  # [1, Q, S]
        since = jnp.where(seen, ri.T[:, :, None] - run.T[:, None, :], -jnp.inf)
        a = score * score * jnp.exp(since)[:, None]  # [G, J, Q, S]
        y = jnp.einsum("gjqs,sgd->qgjd", a, v)
        return y / (jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None] + EPS)

    y = jax.lax.map(block, (qb, rb, rows))
    return y.reshape(-1, h, hd)[:t]


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _layer(n_heads: int, n_kv_heads: int, head_size: int, theta: float,
           eps: float, x, w):
    """One decoder layer on one whole sequence x [T, D]; w holds f32
    matrices stored [out, in], the norm gains and the gate's bias."""
    t = x.shape[0]
    n = _rms_norm(x, w["rms_att"], eps)
    heads = lambda m, count: (n @ m.T).reshape(t, count, head_size)
    q = _rope(_rms_norm(heads(w["wq"], n_heads), w["q_norm"], eps), theta)
    k = _rope(_rms_norm(heads(w["wk"], n_kv_heads), w["k_norm"], eps), theta)
    v = heads(w["wv"], n_kv_heads)
    gamma = jax.nn.log_sigmoid(n @ w["ret_gate"].T + w["ret_gate_bias"])
    y = _retention(q, k, v, gamma)
    x = x + y.reshape(t, n_heads * head_size) @ w["wo"].T
    b = _rms_norm(x, w["rms_ffn"], eps)
    return x + (jax.nn.silu(b @ w["w1"].T) * (b @ w["w3"].T)) @ w["w2"].T


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
            p = f"layers.{li}."
            w = {n: _q40(views[p + n])
                 for n in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
            w.update({n: _f32(views[p + n]) for n in (
                "q_norm", "k_norm", "ret_gate", "ret_gate_bias", "rms_att",
                "rms_ffn")})
            xs = [_layer(s["n_heads"], s["n_kv_heads"], s["head_size"],
                         s["rope_theta"], s["norm_epsilon"], x, w) for x in xs]
            del w
        gain = _f32(views["final_norm"])
        wcls = _q40(views["wcls"])
        out = [np.asarray(_head(s["norm_epsilon"],
                                x[np.asarray(pos, np.int64)], gain, wcls))
               for x, pos in zip(xs, positions)]
    return out

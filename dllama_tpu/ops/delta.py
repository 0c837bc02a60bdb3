"""Gated delta-rule linear attention with a decay per key channel (the "KDA"
mixer of the Kimi-Linear family; the rule itself is Yang et al. 2024, "Gated
Delta Networks", arXiv:2412.06464, with the scalar gate made a vector over
the key dims): the step, its scanned slice form, and the small ops around it.

Per head, with a state S in R^{K x V} (key x value), all float32:

    S' = diag(exp(g_t)) S_{t-1}                  g_t <= 0, one a key channel
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     the delta rule: what the
                                                 state already returns for
                                                 k_t is taken out first
    o_t = S_t^T q_t

q and k are L2-normalised a head (q also scaled 1/sqrt(K)), beta = sigmoid
is the write strength, one a head. The state is a running product-and-sum
over the whole context: like the state-space layers' it stays float32.

A prefill slice is the SCAN of the step over its rows (exact; the per-row
vectors are computed for the whole slice first, only the state's recurrence
is sequential). The chunked form with its triangular solve, and a kernel
for it, are not here: ROADMAP.md Reach keeps them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def l2norm(x):
    """x / |x| over the last axis, float32 (|x|^2 + 1e-6 under the root)."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS)


def decay(f_raw, dt_bias, a_log, heads: int):
    """g f32 [..., H, K] = -exp(A_log[head]) * softplus(f + dt_bias): the log
    of the per-channel decay. f_raw [..., H*K], dt_bias [H*K], a_log [H]."""
    f = jax.nn.softplus(f_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    f = f.reshape(*f.shape[:-1], heads, -1)
    return -jnp.exp(a_log.astype(jnp.float32))[:, None] * f


def kda_step_ref(s, q, k, v, g, beta):
    """One step, plain jnp. s f32 [B, H, K, V]; q, k, g [B, H, K]; v
    [B, H, V]; beta [B, H] -> (o [B, H, V], S_new)."""
    s = jnp.exp(g)[..., None] * s
    u = v - jnp.sum(s * k[..., None], axis=-2)  # v - S'^T k
    s = s + (beta[..., None] * k)[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def kda_scan(s, q, k, v, g, beta):
    """The step scanned over T rows from the incoming state. s [B, H, K, V];
    q, k, g [B, T, H, K]; v [B, T, H, V]; beta [B, T, H] ->
    (o [B, T, H, V], S_out)."""
    def body(s, row):
        o, s = kda_step_ref(s, *row)
        return s, o

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(body, s, rows)
    return jnp.moveaxis(o, 0, 1), s


def gated_head_norm(o, gate, weight, eps: float):
    """rmsnorm over each head's values (gain `weight` [V], shared by the
    heads) times sigmoid(gate). o, gate [..., H, V] -> f32 [..., H, V]."""
    of = o.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps)
    return of * inv * weight.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))

"""State-space (Mamba-2 / SSD) mixer ops: the causal depthwise conv over a
carried window, the selective scan in its chunked form (prefill slices) and
its one-step form (decode), and the gated RMSNorm.

The recurrence, per head h with state S in R^{P x N} (Dao & Gu 2024,
"Transformers are SSMs", section 6; arXiv:2405.21060):

    dt_t = softplus(dt_raw_t + dt_bias)          a_t = exp(-exp(A_log) dt_t)
    S_t  = a_t S_{t-1} + dt_t x_t (outer) B_t    y_t = S_t C_t + D x_t

B and C are shared by all heads (one group). Everything here is float32:
the state is a running sum over the whole context.

The chunked form computes a block of T rows at once from the incoming
state: with l_t = sum_{s<=t} log a_s,

    y_t = sum_{s<=t} (C_t . B_s) exp(l_t - l_s) dt_s x_s  +  exp(l_t) S_in C_t
    S_out = exp(l_T) S_in + sum_s exp(l_T - l_s) dt_s x_s (outer) B_s

two matmuls and a masked [T, T] decay matrix a head instead of T dependent
steps. The step-by-step recurrence is the benchmark reference's derivation
(benchmark/reference); the two check each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(xbc, window, weight, bias, fresh=None):
    """Depthwise causal conv over the sequence with a carried window.

    xbc [B, T, C] (any float dtype), window [B, K-1, C] (the K-1 rows before
    this slice), weight f32 [C, K] (tap j multiplies the row K-1-j back),
    bias f32 [C]; `fresh` [B] bool: rows whose slice starts a sequence (the
    window is then zeros whatever it holds).
    Returns (silu(conv) f32 [B, T, C], the new window [B, K-1, C])."""
    k = weight.shape[-1]
    if fresh is not None:
        window = jnp.where(fresh[:, None, None], jnp.zeros_like(window), window)
    full = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)  # [B, T+K-1, C]
    t = xbc.shape[1]
    ff = full.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + ff[:, j:j + t] * weight[:, j].astype(jnp.float32)
    return jax.nn.silu(out), full[:, t:].astype(window.dtype)


def decay_terms(dt_raw, dt_bias, a_log):
    """(dt, log a) f32 [..., H] from the projected step and the layer's
    per-head parameters. No clamp on dt (the published limits are (0, inf))."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return dt, -jnp.exp(a_log.astype(jnp.float32)) * dt


def ssm_step_ref(s, x, dt, log_a, bmat, cmat):
    """One step, plain jnp. s f32 [B, H, P, N]; x [B, H, P]; dt, log_a
    [B, H]; bmat, cmat [B, N] -> (y [B, H, P] = S_new C, S_new)."""
    s = (jnp.exp(log_a)[..., None, None] * s
         + (dt[..., None] * x)[..., None] * bmat[:, None, None, :])
    return jnp.einsum("bhpn,bn->bhp", s, cmat), s


def _ssm_block(s, x, dt, log_a, bmat, cmat):
    """One block of the chunked form. s [B, H, P, N]; x [B, T, H, P]; dt,
    log_a [B, T, H]; bmat, cmat [B, T, N] -> (y [B, T, H, P], S_out)."""
    hi = jax.lax.Precision.HIGHEST
    t = x.shape[1]
    cum = jnp.cumsum(log_a, axis=1)  # l_t, inclusive [B, T, H]
    diff = cum[:, :, None, :] - cum[:, None, :, :]  # l_t - l_s [B, T, S, H]
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))  # 0 above the diagonal
    g = jnp.einsum("btn,bsn->bts", cmat, bmat, precision=hi)
    xdt = x * dt[..., None]  # [B, T, H, P]
    y = jnp.einsum("btsh,bshp->bthp", g[..., None] * decay, xdt, precision=hi)
    y = y + jnp.einsum("btn,bhpn->bthp", cmat, s, precision=hi) * jnp.exp(cum)[..., None]
    tail = jnp.exp(cum[:, -1:, :] - cum)  # exp(l_T - l_s) [B, T, H]
    s = (jnp.exp(cum[:, -1])[..., None, None] * s
         + jnp.einsum("bshp,bsn->bhpn", xdt * tail[..., None], bmat, precision=hi))
    return y, s


def ssm_chunk_scan(s, x, dt, log_a, bmat, cmat, chunk: int):
    """The chunked selective scan over T rows from the incoming state: one
    block when T <= chunk (a serving prefill slice is at most one chunk),
    else a scan over whole blocks of `chunk` rows and one block for what is
    left."""
    t = x.shape[1]
    if t <= chunk:
        return _ssm_block(s, x, dt, log_a, bmat, cmat)
    n, args = t // chunk, (x, dt, log_a, bmat, cmat)
    split = lambda v: jnp.moveaxis(
        v[:, :n * chunk].reshape(v.shape[0], n, chunk, *v.shape[2:]), 1, 0)

    def body(s, blk):
        y, s = _ssm_block(s, *blk)
        return s, y

    s, ys = jax.lax.scan(body, s, tuple(split(v) for v in args))
    y = jnp.moveaxis(ys, 0, 1).reshape(x.shape[0], n * chunk, *x.shape[2:])
    if t % chunk:
        y_tail, s = _ssm_block(s, *(v[:, n * chunk:] for v in args))
        y = jnp.concatenate([y, y_tail], axis=1)
    return y, s


def gated_rms_norm(y, z, weight, eps: float):
    """RMSNorm(y * silu(z)) * weight over all channels (gate first, then
    norm, one group). y, z [..., C] -> f32 [..., C]."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    inv = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g * inv * weight.astype(jnp.float32)

"""Plain reference of the delta-rule / latent-attention decoder over
sigmoid-routed experts that `benchmark/layouts/kimi_linear.py` lays out.
Pre-norm residual blocks, x += mixer(rmsnorm(x; g_att)); x +=
ffn(rmsnorm(x; g_ffn)); logits = rmsnorm(x_L; g) @ W_head.

KDA mixer (32 heads of key and value size 128 at the published sizes), on
a = rmsnorm(x; g_att), with [q k v fa ga b] = a @ W_proj:

    q, k, v = silu(conv(q)), silu(conv(k)), silu(conv(v))   causal depthwise
              conv, `taps` taps, no bias: out_t = sum_j w_j in_{t-(taps-1)+j}
    q = l2norm(q) / sqrt(K), k = l2norm(k)    per head; l2norm(x) =
              x / sqrt(sum x^2 + 1e-6)
    g = -exp(A_log[head]) * softplus(fa @ W_fb + dt_bias)   per key channel
    beta = sigmoid(b)                                       per head
    S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S^T q_t                         S in R^{K x V}, float32, from zero
    out = (rmsnorm_head(o; g_o) * sigmoid(ga @ W_gb)) @ W_o

MLA mixer, unrotated, EXPANDED form: q = a @ W_q as heads x (nope + pe);
(c, k_pe) = a @ W_kva, c = rmsnorm(c; g_kv); (k_nope, v) = c @ W_kvb as
heads x (nope + v); k = (k_nope, k_pe) with k_pe shared by the heads; scores
q . k / sqrt(nope + pe), causal softmax, the mix of v through W_o. (The
program computes the absorbed form; the two must agree.)

Feed-forward: a dense layer is (silu(b W1) * b W3) W2 at the dense width.
An expert layer: s = sigmoid(b W_r) over ALL the experts routed among;
the top k of s + bias are chosen; weight_i = s_i / (sum of the chosen s +
1e-20) * routed_scaling_factor (the bias is not in the weights); out =
sum_i weight_i expert_i(b) + shared(b). THE SHARE: the file holds experts
[offset, offset + held); routing and the weights are over all of them, and
only the chosen experts that are held are summed: what the absent ones
would add is left out, here as in the program, and the partial result goes
on to the next layer.

All in float32 under `jax.default_matmul_precision("highest")`, no kernels,
no cache, and no import from the program: the weights are the bytes
`benchmark/files.py` wrote, found through the layout and dequantised here.

Departures from a textbook forward pass, none in the arithmetic: the loop is
layer-outer and sequence-inner (a layer is dequantised once); the
recurrence runs token by token as a `lax.scan` (one program a sequence
length); attention runs in blocks of query rows (`lax.map`); every held
expert is applied to every row of every sequence with combine weight 0 off
its rows (no shape depends on the routing). The l2norm's 1e-6 and the
decay's softplus form follow the family's public implementation (the
configuration's `assumed` lists them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.layouts import kimi_linear as layout

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
    return (codes * scale.astype(jnp.float32)[..., None]).reshape(n_out, k_in)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _kda(heads: int, dk: int, rank: int, eps: float, x, w):
    """The KDA block on one whole sequence x [T, D] -> x + out."""
    t = x.shape[0]
    inner = heads * dk
    a = _rms_norm(x, w["rms_att"], eps)
    proj = a @ w["kda_proj"].T
    qkv = proj[:, :3 * inner]
    fa = proj[:, 3 * inner:3 * inner + rank]
    ga = proj[:, 3 * inner + rank:3 * inner + 2 * rank]
    beta = jax.nn.sigmoid(proj[:, 3 * inner + 2 * rank:])  # [T, H]
    taps = w["kda_conv_w"].shape[1]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * w["kda_conv_w"][:, j] for j in range(taps))
    qkv = jax.nn.silu(conv)
    head = lambda i: qkv[:, i * inner:(i + 1) * inner].reshape(t, heads, dk)
    q = _l2norm(head(0)) / np.sqrt(dk)
    k, v = _l2norm(head(1)), head(2)
    g = (-jnp.exp(w["kda_a_log"])[:, None]
         * jax.nn.softplus(fa @ w["kda_fb"].T + w["kda_dt_bias"]).reshape(
             t, heads, dk))

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[:, :, None] * s
        u = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dk), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(ga @ w["kda_gb"].T).reshape(t, heads, dk)
    y = _rms_norm(o, w["kda_norm"], eps) * gate
    return x + y.reshape(t, inner) @ w["kda_o"].T


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _mla(heads: int, rank: int, dn: int, dp: int, dv: int, eps: float, x, w):
    """The latent attention block, expanded, on one whole sequence."""
    t = x.shape[0]
    a = _rms_norm(x, w["rms_att"], eps)
    q = (a @ w["mla_q"].T).reshape(t, heads, dn + dp)
    kva = a @ w["mla_kva"].T
    c = _rms_norm(kva[:, :rank], w["mla_kv_norm"], eps)
    k_pe = kva[:, rank:]
    kv = (c @ w["mla_kvb"].T).reshape(t, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, None, :], (t, heads, dp))], axis=-1)
    v = kv[..., dn:]
    key = jnp.arange(t)[None, :]
    n_blocks = -(-t // Q_ROWS)
    q = jnp.pad(q, ((0, n_blocks * Q_ROWS - t), (0, 0), (0, 0)))

    def block(args):
        qb, q0 = args
        qi = jnp.minimum(q0 + jnp.arange(Q_ROWS), t - 1)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(dn + dp)
        s = jnp.where((key <= qi)[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    att = jax.lax.map(block, (q.reshape(n_blocks, Q_ROWS, heads, dn + dp),
                              jnp.arange(n_blocks) * Q_ROWS))
    att = att.reshape(n_blocks * Q_ROWS, heads * dv)[:t]
    return x + att @ w["mla_o"].T


@jax.jit
def _swiglu(b, w1, w2, w3):
    return (jax.nn.silu(b @ w1.T) * (b @ w3.T)) @ w2.T


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _combine_weights(top_k: int, scale: float, eps: float, h, gain, gate, bias):
    """(b [T, D], w [T, E]): the feed-forward block's normed input, and each
    routed-among expert's combine weight for each row, 0 where not chosen."""
    b = _rms_norm(h, gain, eps)
    s = jax.nn.sigmoid(b @ gate.T)
    _, topi = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, topi, axis=-1)
    wgt = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    dense = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], topi].set(wgt)
    return b, dense


@jax.jit
def _expert(b, w_e, w1, w2, w3):
    """One expert on every row, weighted: w_e [T] is 0 off its rows."""
    return _swiglu(b, w1, w2, w3) * w_e[:, None]


@functools.partial(jax.jit, static_argnums=(0,))
def _normed(eps: float, x, gain):
    return _rms_norm(x, gain, eps)


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


_KDA_Q40 = ("kda_proj", "kda_fb", "kda_gb", "kda_o")
_KDA_F32 = ("kda_conv_w", "kda_dt_bias", "kda_a_log", "kda_norm", "rms_att")
_MLA_Q40 = ("mla_q", "mla_kva", "mla_kvb", "mla_o")
_MLA_F32 = ("mla_kv_norm", "rms_att")


def ffn_block(s: dict, views: dict, li: int, h, share=None, shared=True):
    """Layer li's feed-forward block on rows h [T, D] -> h + out. `share`
    (offset, held), a range within what the file holds, overrides the file's
    own share (the test that the shares add up); `shared` False leaves the
    shared expert out."""
    p = f"layers.{li}."
    eps = s["norm_epsilon"]
    gain = _f32(views[p + "rms_ffn"])
    if s["dense_ffn"][li]:
        w1, w2, w3 = (_q40(views[p + n]) for n in ("w1", "w2", "w3"))
        return h + _swiglu(_normed(eps, h, gain), w1, w2, w3)
    b, dense = _combine_weights(
        s["n_active_experts"], s["routed_scale"], eps, h, gain,
        _f32(views[p + "moe_gate"]), _f32(views[p + "moe_bias"]))
    offset, held = share or (s["expert_offset"], s["held"])
    for e in range(offset, offset + held):  # e counts among ALL the experts
        w1, w2, w3 = (_q40(views[p + n], e - s["expert_offset"])
                      for n in ("moe_w1", "moe_w2", "moe_w3"))
        h = h + _expert(b, dense[:, e], w1, w2, w3)
    if shared and s["n_shared"]:
        w1, w2, w3 = (_q40(views[p + n])
                      for n in ("shared_w1", "shared_w2", "shared_w3"))
        h = h + _swiglu(b, w1, w2, w3)
    return h


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
            if s["kinds"][li] == layout.KIND_KDA:
                w = {n: _q40(views[p + n]) for n in _KDA_Q40}
                w.update({n: _f32(views[p + n]) for n in _KDA_F32})
                xs = [_kda(s["kda_heads"], s["kda_head_dim"], s["kda_rank"],
                           eps, x, w) for x in xs]
            else:
                w = {n: _q40(views[p + n]) for n in _MLA_Q40}
                w.update({n: _f32(views[p + n]) for n in _MLA_F32})
                xs = [_mla(s["n_heads"], s["kv_rank"], s["nope_dim"],
                           s["pe_dim"], s["v_dim"], eps, x, w) for x in xs]
            del w
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

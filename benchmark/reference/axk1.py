"""Plain reference of the rotated-latent-attention decoder over
group-limited sigmoid-routed experts that `benchmark/layouts/axk1.py` lays
out (the DeepSeek-V3 family's block as A.X-K1 publishes it). Pre-norm
residual blocks, x += mixer(rmsnorm(x; g_att)); x += ffn(rmsnorm(x;
g_ffn)); logits = rmsnorm(x_L; g) @ W_head.

MLA mixer, EXPANDED form, on a = rmsnorm(x; g_att), t a token's position:

    c_q = rmsnorm(a W_qa; g_q);  q = c_q W_qb as heads x (nope + pe)
    (c, k_raw) = a W_kva;  c = rmsnorm(c; g_kv)
    k_pe = rope(k_raw, t), one for all heads;  q_pe = rope(q_pe, t) a head
    (k_nope, v) = c W_kvb as heads x (nope + v);  k = (k_nope, k_pe)
    scores s q . k, causal softmax, the mix of v through W_o

`rope` rotates the pairs (x_2i, x_2i+1) by t f_i. Plain: f_i =
theta^(-2i/pe), s = (nope + pe)^-1/2. With the header's YaRN table (keys
170-177): d(b) = pe ln(orig / (2 pi b)) / (2 ln theta), low = floor
d(beta_fast), high = ceil d(beta_slow) (clamped to [0, pe - 1]), ramp_i =
clip((i - low) / (high - low), 0, 1), f_i = theta^(-2i/pe) (1 - ramp_i) +
theta^(-2i/pe) / factor ramp_i; cos and sin times the header's attention
factor; s is the header's score scale (key 102), which the layout computed
as (nope + pe)^-1/2 mscale(factor, mscale_all_dim)^2. (The program computes
the absorbed form over rotated cache rows; the two must agree.)

Feed-forward: a dense layer is (silu(b W1) * b W3) W2 at the dense width.
An expert layer: s = sigmoid(b W_r) over ALL the experts routed among, in
`n_groups` contiguous groups of equal size; a group's score is the sum of
its two largest s + bias; the `groups_kept` groups with the largest score
are kept; the top k of s + bias among the kept groups' experts are chosen;
weight_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor (the
bias is not in the weights); out = sum_i weight_i expert_i(b) + shared(b).
THE SHARE: the file holds experts [offset, offset + held); routing and the
weights are over all of them, and only the chosen experts that are held are
summed: what the absent ones would add is left out, here as in the
program, and the partial result goes on to the next layer.

All in float32 under `jax.default_matmul_precision("highest")`, no kernels,
no cache, and no import from the program: the weights are the bytes
`benchmark/files.py` wrote, found through the layout and dequantised here,
a layer at a time.

Departures from a textbook forward pass, none in the arithmetic: the loop
is layer-outer and sequence-inner (a layer is dequantised once); attention
runs in blocks of query rows (`lax.map`) and the dense feed-forward layer in
blocks of rows; every held expert is applied to
every row of every sequence with combine weight 0 off its rows (no shape
depends on the routing). The rope's pairing (adjacent dims) is the
program's: the published config does not state one, and with random W_qb
and W_kva a fixed permutation of the shared dims is the same model (the
configuration's `assumed`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.layouts import axk1 as layout

PRECISION = "highest"
Q_ROWS = 256  # query rows an attention block holds
FFN_ROWS = 4096  # rows a block of the dense feed-forward layer holds


def _one_at_a_time(fn):
    """The jitted `fn`, waited for before its caller dispatches the next
    program. JAX dispatches asynchronously and the runtime reserves a
    program's result and temporaries when it is queued: the loops below
    queue four sequences' attention blocks and 24 experts' feed-forward
    blocks back to back, and on the chip the reference then held 7.85 GB at
    its highest beside the program's resident weights (3.55 GB one at a
    time: the whole of the cell's `memory_peak_bytes` above what serving
    holds; PERF.md section 6, PR 44). The values are the same."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        return jax.block_until_ready(fn(*args, **kwargs))
    return run


@_one_at_a_time
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


def rope_freqs(s: dict) -> np.ndarray:
    """The pe/2 inverse frequencies of the header's rope, float64."""
    pe, theta, rope = s["pe_dim"], s["rope_theta"], s["rope"]
    i = np.arange(pe // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / pe)
    if rope is None:
        return plain
    d = lambda turns: (pe * math.log(rope["orig_len"] / (turns * 2 * math.pi))
                       / (2 * math.log(theta)))
    low = max(math.floor(d(rope["beta_fast"])), 0)
    high = min(math.ceil(d(rope["beta_slow"])), pe - 1)
    ramp = np.clip((i - low) / ((high if high != low else high + 0.001) - low),
                   0.0, 1.0)
    return plain * (1.0 - ramp) + plain / rope["factor"] * ramp


def rope_rows(s: dict, t: int):
    """(cos, sin) f32 [t, pe/2] for positions 0..t-1."""
    angles = np.outer(np.arange(t, dtype=np.float32),
                      rope_freqs(s).astype(np.float32))
    factor = 1.0 if s["rope"] is None else s["rope"]["attn_factor"]
    return (jnp.asarray(np.cos(angles) * factor, jnp.float32),
            jnp.asarray(np.sin(angles) * factor, jnp.float32))


def _rotate(x, cos, sin):
    """x [t, ..., pe] by pairs of adjacent dims; cos, sin [t, pe/2]."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, sn = cos.reshape(shape), sin.reshape(shape)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * c - x1 * sn, x0 * sn + x1 * c], axis=-1).reshape(x.shape)


@_one_at_a_time
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _mla(heads: int, rank: int, dn: int, dp: int, dv: int, eps: float,
         scale: float, x, w, cos, sin):
    """The latent attention block, expanded, on one whole sequence."""
    t = x.shape[0]
    a = _rms_norm(x, w["rms_att"], eps)
    c_q = _rms_norm(a @ w["mla_qa"].T, w["mla_q_norm"], eps)
    q = (c_q @ w["mla_qb"].T).reshape(t, heads, dn + dp)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)], axis=-1)
    kva = a @ w["mla_kva"].T
    c = _rms_norm(kva[:, :rank], w["mla_kv_norm"], eps)
    k_pe = _rotate(kva[:, rank:], cos, sin)
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
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        sc = jnp.where((key <= qi)[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    att = jax.lax.map(block, (q.reshape(n_blocks, Q_ROWS, heads, dn + dp),
                              jnp.arange(n_blocks) * Q_ROWS))
    att = att.reshape(n_blocks * Q_ROWS, heads * dv)[:t]
    return x + att @ w["mla_o"].T


@_one_at_a_time
@jax.jit
def _swiglu(b, w1, w2, w3):
    return (jax.nn.silu(b @ w1.T) * (b @ w3.T)) @ w2.T


def choose_experts(scores, bias, groups: int, kept: int, top_k: int):
    """s [T, E] sigmoid scores -> the indices [T, top_k] of the chosen
    experts: the top k of s + bias among the `kept` groups (of `groups`
    contiguous equal ones) whose two largest s + bias sum highest."""
    choose = scores + bias
    if groups > 1:
        by_group = choose.reshape(choose.shape[0], groups, -1)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, top_groups = jax.lax.top_k(group_score, kept)
        keep = jnp.zeros((choose.shape[0], groups), bool).at[
            jnp.arange(choose.shape[0])[:, None], top_groups].set(True)
        choose = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(choose.shape)
    return jax.lax.top_k(choose, top_k)[1]


@_one_at_a_time
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _combine_weights(top_k: int, groups: int, kept: int, scale: float,
                     eps: float, h, gain, gate, bias):
    """(b [T, D], w [T, E]): the feed-forward block's normed input, and each
    routed-among expert's combine weight for each row, 0 where not chosen."""
    b = _rms_norm(h, gain, eps)
    s = jax.nn.sigmoid(b @ gate.T)
    topi = choose_experts(s, bias, groups, kept, top_k)
    chosen = jnp.take_along_axis(s, topi, axis=-1)
    wgt = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    dense = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], topi].set(wgt)
    return b, dense


@_one_at_a_time
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


_MLA_Q40 = ("mla_qa", "mla_qb", "mla_kva", "mla_kvb", "mla_o")
_MLA_F32 = ("mla_q_norm", "mla_kv_norm", "rms_att")


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
        # in blocks of rows: the dense width's three [rows, 18,432] float32
        # intermediates over all the check's rows at once would be 3.7 GB
        return jnp.concatenate([
            c + _swiglu(_normed(eps, c, gain), w1, w2, w3)
            for c in (h[i:i + FFN_ROWS] for i in range(0, h.shape[0], FFN_ROWS))])
    b, dense = _combine_weights(
        s["n_active_experts"], s["n_groups"], s["groups_kept"],
        s["routed_scale"], eps, h, gain,
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
    scale = s["attn_scale"] or (s["nope_dim"] + s["pe_dim"]) ** -0.5
    with jax.default_matmul_precision(PRECISION):
        xs = [jnp.asarray(emb[np.asarray(seq, np.int64)]) for seq in sequences]
        ropes = [rope_rows(s, len(x)) for x in xs]
        for li in range(s["n_layers"]):
            p = f"layers.{li}."
            w = {n: _q40(views[p + n]) for n in _MLA_Q40}
            w.update({n: _f32(views[p + n]) for n in _MLA_F32})
            xs = [_mla(s["n_heads"], s["kv_rank"], s["nope_dim"], s["pe_dim"],
                       s["v_dim"], eps, scale, x, w, *rope)
                  for x, rope in zip(xs, ropes)]
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

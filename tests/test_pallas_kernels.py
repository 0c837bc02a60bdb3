"""Pallas kernel equivalence tests (interpret mode on CPU).

The analog of the reference's nn-cpu-ops-test.cpp: every fused kernel is
checked against the pure-jnp reference implementation with calibrated
tolerances (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.ops.quant import QTensor
from dllama_tpu.ops.pallas.q40_matmul import q40_matmul, supported


@pytest.mark.parametrize(
    "m,k,n",
    [
        (1, 256, 256),  # decode GEMV shape (row-padded to 8 inside)
        (8, 512, 384),
        (16, 1024, 512),
        (128, 256, 1280),  # prefill chunk
        (3, 512, 256),  # odd batch -> pad path
    ],
)
def test_q40_matmul_matches_dequant_dot(rng, m, k, n):
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = QTensor.quantize(rng.standard_normal((k, n)).astype(np.float32) * 0.1)
    assert supported(x.shape, w)
    got = q40_matmul(x, w, interpret=True)
    want = jnp.dot(x, w.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2)


def test_q40_matmul_batched_lead_dims(rng):
    x = jnp.asarray(rng.standard_normal((2, 4, 256)), jnp.bfloat16)
    w = QTensor.quantize(rng.standard_normal((256, 256)).astype(np.float32) * 0.1)
    got = q40_matmul(x, w, interpret=True)
    assert got.shape == (2, 4, 256)
    assert got.dtype == jnp.bfloat16
    want = jnp.dot(x, w.dequantize(jnp.bfloat16), preferred_element_type=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=8e-2, rtol=8e-2
    )


def test_q40_matmul_exact_on_roundtrip_values(rng):
    """Inputs already on the Q40 grid -> kernel must be exact vs dequant-dot
    (same accumulation dtype), like the reference's epsilon-0 identity cases."""
    w0 = rng.standard_normal((128, 256)).astype(np.float32)
    w = QTensor.quantize(w0)
    x = jnp.eye(128, dtype=jnp.float32)
    got = q40_matmul(x, w, interpret=True)
    want = w.dequantize(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0, rtol=0)


# ---------------------------------------------------------------- flash attn


@pytest.mark.parametrize(
    "b,t,hq,hkv,hd,s,pos",
    [
        (1, 1, 8, 4, 64, 256, 0),  # decode at start
        (1, 1, 8, 4, 64, 256, 200),  # decode deep in the cache
        (1, 16, 8, 8, 64, 128, 0),  # MHA prefill chunk
        (2, 64, 8, 2, 128, 256, 64),  # GQA batched prefill mid-sequence
        (1, 3, 4, 4, 64, 128, 5),  # odd T -> row-pad path
        (1, 1, 8, 4, 64, 1024, 3),  # decode in a long cache: most kv tiles pruned
    ],
)
def test_flash_attention_matches_jnp(rng, b, t, hq, hkv, hd, s, pos):
    from dllama_tpu.ops.layers import gqa_attention
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

    q = jnp.asarray(rng.standard_normal((b, t, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    got = flash_gqa_attention(q, k, v, jnp.int32(pos), interpret=True)
    want = gqa_attention(q, k, v, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,pos", [
    (1, 0), (1, 511), (1, 512), (1, 800), (1, 1023), (1, 1500), (1, 2047),
    # prefill chunks: horizon = pos + t picks the covering view, incl. a
    # chunk that ENDS exactly on / just past a bucket boundary
    (16, 0), (16, 496), (16, 497), (64, 960), (64, 1980),
])
def test_flash_attention_bucketed_matches_unbucketed(rng, t, pos):
    """s_buckets dispatches to a power-of-two cache view covering
    max(pos)+t; output must be identical to the full-S grid at every
    position, especially ON the bucket boundaries (horizon 512 rides the
    512 view, horizon 513 the 1024 one)."""
    from dllama_tpu.ops.pallas.flash_attention import _s_buckets, flash_gqa_attention

    assert _s_buckets(2048) == (512, 1024, 2048)
    assert _s_buckets(512) == ()  # nothing to bucket

    q = jnp.asarray(rng.standard_normal((1, t, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4, 2048, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 4, 2048, 64)), jnp.float32)
    want = flash_gqa_attention(q, k, v, jnp.int32(pos), interpret=True)
    got = flash_gqa_attention(q, k, v, jnp.int32(pos), interpret=True,
                              s_buckets=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0, rtol=0)


def test_flash_attention_bf16_io(rng):
    from dllama_tpu.ops.layers import gqa_attention
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

    q = jnp.asarray(rng.standard_normal((1, 8, 8, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 4, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 4, 128, 64)), jnp.bfloat16)
    got = flash_gqa_attention(q, k, v, jnp.int32(32), interpret=True)
    assert got.dtype == jnp.bfloat16
    want = gqa_attention(q, k, v, jnp.int32(32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_attention_in_model_forward(rng):
    """Full forward with the Pallas attn_fn vs the jnp default — end-to-end
    parity, the analog of swapping kernels under the reference executor."""
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import KVCache, forward, random_params
    from dllama_tpu.ops.layers import build_rope_cache
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention
    from functools import partial

    cfg = LlamaConfig(dim=128, hidden_dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
                      vocab_size=256, seq_len=64)
    params = random_params(cfg, seed=1, dtype=jnp.float32, quantize=False)
    rope = build_rope_cache(cfg)
    toks = jnp.asarray(rng.integers(0, 256, (1, 8)), jnp.int32)

    cache0 = KVCache.create(cfg, 1, jnp.float32)
    ref_logits, _ = forward(cfg, params, toks, jnp.int32(0), cache0, rope)
    cache1 = KVCache.create(cfg, 1, jnp.float32)
    got_logits, _ = forward(
        cfg, params, toks, jnp.int32(0), cache1, rope,
        attn_fn=partial(flash_gqa_attention, interpret=True),
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits), atol=1e-4, rtol=1e-4
    )


# ------------------------------------------------------------------ rms norm


@pytest.mark.parametrize("shape", [(1, 1, 256), (2, 16, 512), (5, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_pallas_matches_jnp(rng, shape, dtype):
    from dllama_tpu.ops.layers import rms_norm as rms_ref
    from dllama_tpu.ops.pallas.rms_norm import rms_norm as rms_pallas

    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]) * 0.5 + 1.0, jnp.float32)
    got = rms_pallas(x, w, 1e-5, interpret=True)
    want = rms_ref(x, w, 1e-5)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("tier", ["_blockdot_call", "_deq_call"])
def test_q40_tiers_agree(rng, tier):
    """Both tiers' jitted calls compute the same product at a decode shape
    (bf16 in, the block-dot kernel's only activation type; 16 rows, a whole
    bf16 tile, as the dispatcher pads them)."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    x = jnp.asarray(rng.standard_normal((3, 512)), jnp.bfloat16)
    w = QTensor.quantize(rng.standard_normal((512, 384)).astype(np.float32) * 0.1)
    want = jnp.dot(x.astype(jnp.float32), w.dequantize(jnp.float32))
    got = getattr(qmod, tier)(
        jnp.zeros((1,), jnp.int32), jnp.pad(x, ((0, 13), (0, 0))), w.packed[None],
        jax.lax.bitcast_convert_type(w.scales, jnp.uint16)[None], interpret=True)[:3]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=8e-2, rtol=8e-2)


# The cells' decode shapes scaled down but keeping what their tiling hangs
# on: k = 43 x 256 (DeepSeek's w2, taken whole), n = 43 x 128 (its w1/w3:
# no wide tile divides it), a head whose n is a multiple of 512 and of
# nothing wider, and a square stacked weight over two k tiles.
_CELL_SHAPES = {
    "w2: k = 43 x 256": (11008, 256, 2),
    "w1: n = 43 x 128": (512, 5504, 2),
    "head: n = 3 x 512": (256, 1536, 1),
    "wq: two k tiles": (8192, 128, 3),
}


@pytest.fixture(scope="module")
def cell_weights():
    """name -> (stacked QTensor with f16 scales down to subnormals, its
    float32 dequantisation a layer)."""
    rng = np.random.default_rng(7)
    out = {}
    for name, (k, n, layers) in _CELL_SHAPES.items():
        packed = rng.integers(0, 256, (layers, k // 2, n), dtype=np.uint8)
        scales = (rng.random((layers, k // 32, n), np.float32) * 0.02 + 1e-3).astype(np.float16)
        scales[:, ::3, ::5] = np.float16(3e-6)  # subnormal f16 (< 6.1e-5)
        scales[:, 1::7, 1::3] *= np.float16(-1)
        w = QTensor(jnp.asarray(packed), jnp.asarray(scales))
        out[name] = (w, [QTensor(w.packed[i], w.scales[i]).dequantize(jnp.float32)
                         for i in range(layers)])
    return out


@pytest.mark.parametrize("m", [1, 8, 12, 16])
@pytest.mark.parametrize("name", list(_CELL_SHAPES))
def test_blockdot_matches_dequant_dot_at_cell_shapes(cell_weights, name, m):
    """The m <= 16 kernel against the XLA dequantise-then-dot in float32,
    the layer a TRACED index into the stacked arrays. The kernel carries its
    codes as 16 + q and takes 24 x the block sums off again: that cancels in
    float32 (1e-5 of the largest value), it is not bit for bit."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    w, dense = cell_weights[name]
    k, _, layers = _CELL_SHAPES[name]
    x = jnp.asarray(np.random.default_rng(m).standard_normal((m, k)), jnp.bfloat16)
    pad = jnp.pad(x, ((0, 16 - m), (0, 0)))  # the kernel takes 16 rows
    scales = jax.lax.bitcast_convert_type(w.scales, jnp.uint16)
    call = jax.jit(lambda layer: qmod._blockdot_call(
        layer.reshape(1), pad, w.packed, scales, interpret=True))
    # a subnormal f16 scale (every third block of every fifth column) may
    # read as 0 where the backend flushes float32 subnormals (`_scales_f32`);
    # its weights are under 8 x 3e-6 each
    flushed = np.zeros(w.shape[-1], bool)
    flushed[::5] = True
    slack = 8 * 3e-6 * np.abs(np.asarray(x, np.float32)).sum(axis=1, keepdims=True)
    for li in {0, layers - 1}:
        got = np.asarray(call(jnp.int32(li)))[:m]
        want = np.asarray(jnp.dot(x.astype(jnp.float32), dense[li],
                                  precision="highest"))
        err, top = np.abs(got - want), np.abs(want).max()
        assert err[:, ~flushed].max() <= 1e-5 * top
        assert (err[:, flushed] <= 1e-5 * top + slack).all()
        # and through the public entry, in the activation dtype
        out = q40_matmul(x, w, jnp.int32(li), interpret=True)
        assert out.dtype == jnp.bfloat16 and out.shape == want.shape
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   atol=1e-2 * top, rtol=0)


def test_position_is_the_order_the_words_unpack_to(rng):
    """`_position` says where an input dim of a 128-row group lands among the
    rows `_unpack_words` leaves (the kernel moves x there through the MXU):
    the unpacked codes, read back through it, must be the plain codes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    k, n = 256, 128
    packed = jnp.asarray(rng.integers(0, 256, (k // 2, n), dtype=np.uint8))

    def kern(p_ref, o_ref):
        parts = qmod._unpack_words(pltpu.bitcast(p_ref[:], jnp.uint32))  # 4 x [64, n]
        o_ref[:] = jnp.concatenate(
            [t[32 * g:32 * g + 32] for g in range(2) for t in parts],
            axis=0).astype(jnp.float32)

    codes = np.asarray(pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=True)(packed)) - 24.0
    dense = np.asarray(QTensor(packed, jnp.ones((k // 32, n), jnp.float16)
                               ).dequantize(jnp.float32))
    src = np.arange(k)
    where = 128 * (src // 128) + np.asarray(qmod._position(src % 128))
    assert sorted(where) == list(src)  # a permutation within each group
    np.testing.assert_array_equal(codes[where], dense)


# Granite's four stacked shapes (PERF.md section 4) cut to test size with the
# divisibility their tiling hangs on, and what else the tier must take: a k
# of whole 128-dim groups that is no whole 256-row step, DeepSeek's 43 x 256.
_DEQ_SHAPES = {
    "in_proj: n = 67 x 128": (256, 8576, 2),
    "out_proj: k = 2 n": (1024, 512, 2),
    "w1: n = 4 k": (256, 1024, 3),
    "w2: k = 4 n": (2048, 512, 2),
    "k = 3 x 128": (384, 256, 1),
    "k = 43 x 256": (11008, 128, 2),
}


@pytest.fixture(scope="module")
def deq_weights():
    """name -> (stacked QTensor, a layer's float32 dequantisation and the same
    rounded to bf16 a weight, which is what the tier feeds its dot for a bf16
    x)."""
    rng = np.random.default_rng(11)
    out = {}
    for name, (k, n, layers) in _DEQ_SHAPES.items():
        packed = rng.integers(0, 256, (layers, k // 2, n), dtype=np.uint8)
        scales = (rng.random((layers, k // 32, n), np.float32) * 0.02 + 1e-3).astype(np.float16)
        scales[:, 1::7, 1::3] *= np.float16(-1)
        w = QTensor(jnp.asarray(packed), jnp.asarray(scales))
        dense = [QTensor(w.packed[i], w.scales[i]).dequantize(jnp.float32)
                 for i in range(layers)]
        out[name] = (w, dense, [d.astype(jnp.bfloat16).astype(jnp.float32) for d in dense])
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("m", [24, 48, 64, 256])
@pytest.mark.parametrize("name", list(_DEQ_SHAPES))
def test_deq_matches_dequant_dot_at_cell_shapes(deq_weights, name, m, dtype):
    """The m > 16 tier against the XLA dequantise-then-dot in float32, the
    layer a TRACED index into the stacked arrays. For a bf16 x it rounds each
    dequantised weight (q - 8) * s to bf16 once, as the byte-wise body it
    replaced did (1e-5 of the largest value from that product: the order of
    the float32 sums; 4e-3 from the unrounded one); a float32 x meets the
    float32 weights."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    if m == 256 and _DEQ_SHAPES[name][0] * _DEQ_SHAPES[name][1] > 1 << 20:
        m = 128  # the interpreter's minutes, not another path
    w, dense, rounded = deq_weights[name]
    k, _, layers = _DEQ_SHAPES[name]
    x = jnp.asarray(np.random.default_rng(m).standard_normal((m, k)), dtype)
    pad = jnp.pad(x, ((0, -m % 16), (0, 0)))  # whole tiles of rows, as the dispatcher pads
    scales = jax.lax.bitcast_convert_type(w.scales, jnp.uint16)
    # (the weights as arguments, as a step program holds them: closed over,
    # XLA's CPU constant folder takes the interpreter's byte-to-word bitcast
    # apart and reads k = 384 wrong)
    call = jax.jit(lambda layer, packed, scales: qmod._deq_call(
        layer.reshape(1), pad, packed, scales, interpret=True))
    for li in {0, layers - 1}:
        got = np.asarray(call(jnp.int32(li), w.packed, scales))[:m]
        exact = np.asarray(jnp.dot(x.astype(jnp.float32), dense[li], precision="highest"))
        top = np.abs(exact).max()
        if dtype == jnp.bfloat16:
            same = np.asarray(jnp.dot(x.astype(jnp.float32), rounded[li], precision="highest"))
            assert np.abs(got - same).max() <= 1e-5 * top
            assert np.abs(got - exact).max() <= 4e-3 * top
        else:
            assert np.abs(got - exact).max() <= 1e-5 * top
    # and through the public entry, in the activation dtype
    out = q40_matmul(x, w, jnp.int32(layers - 1), interpret=True)
    assert out.dtype == dtype and out.shape == exact.shape
    np.testing.assert_allclose(np.asarray(out, np.float32), exact, atol=1e-2 * top, rtol=0)


def test_deq_lays_x_out_again_for_every_m_tile(rng):
    """Past 512 rows the batch is cut into m tiles; x is laid out at each
    tile's first grid step (its rows are other rows), over two n tiles and
    two k tiles here."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    m, k, n = 1024, 512, 256
    w = QTensor.quantize(rng.standard_normal((k, n)).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    assert qmod._deq_tiles(m, k, n, 4)[0] == 512
    got = qmod._deq_call(jnp.zeros((1,), jnp.int32), x, w.packed[None],
                         jax.lax.bitcast_convert_type(w.scales, jnp.uint16)[None],
                         interpret=True, tk=256, tn=128)
    want = jnp.dot(x, w.dequantize(jnp.float32), precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_deq_position_is_the_order_the_words_dequantise_to(rng):
    """`_deq_position` says where an input dim of a 128-dim group lands among
    the rows `_dequant_words` leaves (the kernel moves x there through the
    MXU): the dequantised rows, read back through it, are the plain
    dequantisation, bit for bit in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    k, n = 256, 128
    packed = jnp.asarray(rng.integers(0, 256, (k // 2, n), dtype=np.uint8))
    scales = jnp.asarray((rng.random((k // 32, n), np.float32) * 0.02 + 1e-3).astype(np.float16))

    def kern(p_ref, s_ref, o_ref):
        o_ref[:] = qmod._dequant_words(pltpu.bitcast(p_ref[:], jnp.uint32), s_ref[:],
                                       jnp.float32)

    # a block's scale under each of its four word rows, as the kernel stores them
    sb = jnp.repeat(scales.astype(jnp.float32) * qmod._TOP_NIBBLE, 4, axis=0)
    rows = np.asarray(pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=True)(packed, sb))
    dense = np.asarray(QTensor(packed, scales).dequantize(jnp.float32))
    src = np.arange(k)
    where = 128 * (src // 128) + np.asarray(qmod._deq_position(src % 128))
    assert sorted(where) == list(src)  # a permutation within each group
    np.testing.assert_array_equal(rows[where], dense)


# every Q40 (k, n) the two cells serve at m <= 16 (PERF.md section 4)
_SERVED = {
    "deepseek wq..wo": (4096, 4096), "deepseek w1/w3": (4096, 11008),
    "deepseek w2": (11008, 4096), "deepseek head": (4096, 102400),
    "granite in_proj": (2048, 8576), "granite out_proj": (4096, 2048),
    "granite w1/w3": (2048, 8192), "granite w2": (8192, 2048),
    "granite wq/wo": (2048, 2048), "granite wk/wv": (2048, 512),
    "granite head": (2048, 100352),
}


@pytest.mark.parametrize("name", list(_SERVED))
def test_blockdot_tiles_keep_their_floor(name):
    """Every served shape's tile divides its weight, walks k by whole loop
    steps, takes its scales as whole 16-row u16 tiles and fits the VMEM
    budget with its second buffer; and wherever the weight's divisors allow
    it at all, a grid step moves 256 KB of packed bytes or more and the
    call is more than one step (one step overlaps no copy with any work)."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    k, n = _SERVED[name]
    tk, tn = qmod._blockdot_tiles(k, n)
    assert k % tk == 0 and n % tn == 0 and tn % 128 == 0 and tk % qmod._SUB_K == 0
    assert (tk // 32) % 16 == 0 or tk == k
    assert tk == k or tk % 4096 == 0  # whole chunks of block sums
    assert 2 * (tk * tn // 2 + tk // 32 * tn * 2) <= qmod._VMEM_BUDGET
    lanes, rows = qmod._inner(tk, tn)
    assert tn % lanes == 0 and rows % qmod._SUB_K == 0 and 0 < rows <= tk
    sound = lambda tk, tn: (tk * tn // 2 >= qmod._STEP_FLOOR
                            and (k // tk) * (n // tn) >= 2)
    could = any(sound(a, b) for a in [k] + list(range(4096, k, 4096))
                for b in range(128, n + 1, 128) if k % a == 0 and n % b == 0)
    assert sound(tk, tn) or not could
    if name.startswith("deepseek") or name == "granite head":
        assert sound(tk, tn)  # the shapes the cells' decode steps run


# the dense (k, n) the accepted cells send `_blockdot_call` -> (tk, tn, lanes,
# rows a pass) and the sha256 of the call's traced jaxpr (addresses blanked)
# at 16 rows over a 2-layer stack, as PR 38's tree gives them under this
# suite's jax configuration (tests/conftest.py): a change to
# the grouped expert kernel leaves the dense call the same program
_DENSE_CALLS = {
    (4096, 4096): ((4096, 512, 512, 4096), "1daf61a21fb574cb"),
    (4096, 11008): ((4096, 256, 256, 4096), "dd899f8f50b1e93b"),
    (11008, 4096): ((11008, 512, 512, 4096), "6543fffc52220216"),
    (4096, 102400): ((4096, 2560, 512, 4096), "63461a58865053a1"),
    (2560, 3584): ((2560, 512, 512, 2560), "40574844b4642254"),
    (3584, 2560): ((3584, 512, 512, 3584), "bae0fdba2b1fde2f"),
    (2560, 151936): ((2560, 128, 128, 2560), "44966ff708936a48"),
    (2048, 100352): ((2048, 3584, 512, 2048), "35c05390c4b03e00"),
}


@pytest.mark.parametrize("k,n", list(_DENSE_CALLS))
def test_dense_blockdot_call_is_the_program_it_was(k, n):
    """DeepSeek's, Granite's and SmallThinker's dense shapes run the tiles,
    the inner loop and the traced kernel they ran before the expert kernel
    got a walk of its own (PR 39): their cells are the controls."""
    import hashlib
    import re

    from dllama_tpu.ops.pallas import q40_matmul as qmod

    tiles, digest = _DENSE_CALLS[k, n]
    tk, tn = qmod._blockdot_tiles(k, n)
    assert (tk, tn) + qmod._inner(tk, tn) == tiles
    S = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(lambda *a: qmod._blockdot_call(*a))(
        S((1,), jnp.int32), S((16, k), jnp.bfloat16), S((2, k // 2, n), jnp.uint8),
        S((2, k // 32, n), jnp.uint16)))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---------------------------------------------------- the grouped expert kernel
# the expert projections of the two expert cells (PERF.md section 4) -> the
# columns tile a grid step holds and the lanes a pass of its loop covers
_EXPERT_SHAPES = {
    "smallthinker w1/w3": ((2560, 768), (768, 768)),
    "smallthinker w2": ((768, 2560), (2560, 2560)),
    "kimi w1/w3": ((2304, 1024), (1024, 1024)),
    "kimi w2": ((1024, 2304), (2304, 2304)),
}


def _expert_stack(rng, experts, k, n, layers=2):
    packed = rng.integers(0, 256, (layers, experts, k // 2, n), dtype=np.uint8)
    scales = (rng.random((layers, experts, k // 32, n), np.float32) * 0.02
              + 1e-3).astype(np.float16)
    scales[:, :, 1::7, 1::3] *= np.float16(-1)
    return jnp.asarray(packed), jnp.asarray(scales)


def _expert_want(x, packed, scales, li, e, tm=16):
    """A tile against its expert in float32; a tile taller than 16 rows runs
    the dequantising body, which rounds each weight (q - 8) * s to bf16 once
    (as `_deq_call` does for every other matmul of a slice)."""
    w = QTensor(packed[li, e], scales[li, e]).dequantize(jnp.float32)
    if tm > 16:
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return np.asarray(jnp.dot(x.astype(jnp.float32), w, precision="highest"))


def _run_expert_call(rng, sizes, tm, k, n, li=1, held=None, **kw):
    """`sizes[e]` rows routed to expert e (one choice a token; `held` keeps
    the first experts and turns the others' rows into the sentinel) through
    `expert_groups` and `_expert_call` on layer `li`; returns what the
    asserts below read."""
    from dllama_tpu.ops.layers import expert_groups, expert_rows
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    e = held or len(sizes)
    topi = np.repeat(np.arange(len(sizes)), sizes)
    topi = jnp.asarray(np.where(topi < e, topi, e)[rng.permutation(len(topi)), None],
                       jnp.int32)
    packed, scales = _expert_stack(rng, e, k, n)
    pos, tile_expert, tile_src, n_live, got_sizes = expert_groups(topi, e, tm)
    assert got_sizes.tolist() == list(sizes[:e])
    h = jnp.asarray(rng.standard_normal((topi.shape[0], k)), jnp.bfloat16)
    xs = expert_rows(h, pos, topi < e, len(tile_src) * tm, by_dot=tm == 16)
    live = int(n_live)
    # what stands behind the last live tile is never read: poison it
    xs = xs.at[live * tm:].set(jnp.nan)
    tile_expert = tile_expert.at[live:].set(10 ** 6)
    out = np.asarray(qmod._expert_call(
        jnp.full((1,), li, jnp.int32), tile_expert, tile_src, n_live.reshape(1), xs,
        packed, jax.lax.bitcast_convert_type(scales, jnp.uint16), tm=tm,
        interpret=True, **kw))
    assert out.shape == (xs.shape[0], n) and out.dtype == np.float32
    for t in range(live):
        rows = slice(t * tm, (t + 1) * tm)
        want = _expert_want(xs[rows], packed, scales, li, int(tile_expert[t]), tm)
        assert np.abs(out[rows] - want).max() <= 1e-5 * np.abs(want).max(), t
    # ... and never written (an interpreted kernel's untouched result is NaN)
    assert np.isnan(out[live * tm:]).all()
    return live, len(tile_expert), np.asarray(pos), out, h, packed, scales, topi


@pytest.mark.parametrize("fill", ["tm 16: 1, 3 and 16 rows, an expert with none",
                                  "tm 32: a full tile, a part one, two and three of one expert"])
@pytest.mark.parametrize("name", list(_EXPERT_SHAPES))
def test_expert_call_matches_dequant_dot_at_cell_shapes(name, fill):
    """The grouped kernel at the two expert cells' four projection shapes
    against the float32 dequantise-then-dot of each live tile's expert
    (1e-5 of the largest value: the codes' offset cancels in float32; at 32
    rows a tile the dequantising walk, against the weights it rounds), the
    layer a traced index; the tiles behind the last live one are neither
    read nor written."""
    (k, n), inner = _EXPERT_SHAPES[name]
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    assert qmod._expert_inner(k, n) == inner
    assert qmod._expert_deq_tn(k, n) == n  # a tall tile: one pass an expert
    rng = np.random.default_rng(len(name) + len(fill))
    if fill.startswith("tm 16"):
        live, tiles, *_ = _run_expert_call(rng, (1, 0, 3, 16), 16, k, n)
        assert (live, tiles) == (3, 4 + 1)
    else:
        live, tiles, *_ = _run_expert_call(rng, (32, 5, 40, 70), 32, k, n)
        assert (live, tiles) == (1 + 1 + 2 + 3, 4 + 147 // 32)


@pytest.mark.parametrize("case", ["no tile live", "one chip's share",
                                  "columns tiles and lane passes", "every row found again"])
def test_expert_call_walks_only_the_live_tiles(case):
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    rng = np.random.default_rng(11)
    if case == "no tile live":  # every row is another chip's: nothing runs
        live, tiles, _, out, *_ = _run_expert_call(rng, (0, 0, 5, 9), 16, 256, 256, held=2)
        assert live == 0 and np.isnan(out).all()
    elif case == "one chip's share":  # the sentinel rows stand nowhere
        live, tiles, pos, out, h, packed, scales, topi = _run_expert_call(
            rng, (2, 17, 5, 9), 16, 512, 256, held=2)
        assert (live, tiles) == (1 + 2, 2 + 33 // 16)
    elif case == "columns tiles and lane passes":
        # an expert past a grid step's bytes is walked by columns tiles, a
        # tile past a pass's weights by lanes: the sweep's overrides say how
        _run_expert_call(rng, (3, 20, 1), 16, 512, 768, tn=256, lanes=128)
        _run_expert_call(rng, (3, 20, 1), 16, 512, 768, tn=768, lanes=384)
        assert qmod._expert_inner(5120, 1536) == (1536, 384)  # DeepSeek-V2's
        assert qmod._expert_inner(1536, 5120) == (5120, 1280)
        assert qmod._expert_inner(4096, 14336) == (2048, 512)  # 4 MB a step
        _run_expert_call(rng, (40, 3, 70), 32, 512, 768, tn=256)  # tall tiles too
        assert qmod._expert_deq_tn(5120, 1536) == 384
    else:  # each (token, choice) reads its own row of its own expert
        live, tiles, pos, out, h, packed, scales, topi = _run_expert_call(
            rng, (4, 0, 19, 2), 16, 256, 384)
        for token in (0, 7, 24):
            want = _expert_want(h[token:token + 1], packed, scales, 1, int(topi[token, 0]))
            assert np.abs(out[pos[token, 0]] - want[0]).max() <= 1e-5 * np.abs(want).max()


# every Q40 (m, k, n) the three cells send the m > 16 tier: Granite's 48
# slots, prompt slices of 32-512 rows in every cell, the check's prefill
_DEQ_SERVED = [(m, k, n) for m, shapes in (
    (48, ["granite in_proj", "granite out_proj", "granite w1/w3", "granite w2",
          "granite wq/wo", "granite wk/wv", "granite head"]),
    (64, ["granite in_proj", "granite w2", "deepseek w1/w3", "deepseek w2"]),
    (128, ["deepseek wq..wo", "deepseek w1/w3", "deepseek w2"]),
    (512, ["deepseek w2", "granite w1/w3"]),
) for k, n in (_SERVED[name] for name in shapes)] + [
    (512, 2560, 3584), (512, 3584, 2560), (256, 2560, 512)]  # SmallThinker's attention


@pytest.mark.parametrize("m,k,n", _DEQ_SERVED)
def test_deq_tiles_keep_their_rules(m, k, n):
    """Every served shape's tile divides its weight into whole 128-lane
    columns and whole 256-row steps (or takes k whole), a pass is whole
    steps of the tile within the pass cap, the step's buffers and the pass's
    planes fit the budget; and wherever the weight's divisors allow it at
    all, a grid step moves 256 KB of packed bytes or more and the call is
    more than one step."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    tm, tk, tn, rows = qmod._deq_tiles(m, k, n)
    assert tm == m  # one m tile up to 512 rows: the weight streams once
    assert k % tk == 0 and n % tn == 0 and tn % 128 == 0
    assert tk == k or tk % qmod._SUB_K == 0
    assert tk % rows == 0 and (rows == tk or rows % qmod._SUB_K == 0)
    assert rows == qmod._deq_pass(tk, tn) and rows * tn <= qmod._DEQ_PASS_WEIGHTS
    nb = tk // 32
    assert (2 * (tk * tn // 2 + nb * tn * 2 + tm * tn * 4) + nb * tn * 4
            + 10 * rows * tn) <= qmod._DEQ_VMEM
    sound = lambda tk, tn: (tk * tn // 2 >= qmod._STEP_FLOOR
                            and (k // tk) * (n // tn) >= 2)
    could = any(sound(a, b) and qmod._deq_pass(a, b)
                for a in [k] + list(range(256, k, 256))
                for b in range(128, n + 1, 128) if k % a == 0 and n % b == 0)
    assert sound(tk, tn) or not could
    if m == 48 and k * n >= 1 << 23:
        assert sound(tk, tn)  # the claimed cell's large shapes


def test_deq_tiles_take_the_whole_width_where_a_pass_holds_it():
    """A tile of the result costs a grid step (its zeroing and write-back):
    on Granite's 16 M-weight shapes the chooser takes the whole width and a
    pass's worth of rows, not the whole depth in narrow columns (1.2-1.4 us
    a call on the chip, PERF.md section 6, PR 37); in_proj's n = 67 x 128
    has no other tile than whole or 128 lanes."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    for k, n in ((2048, 8576), (2048, 8192), (8192, 2048), (4096, 2048)):
        _, tk, tn, rows = qmod._deq_tiles(48, k, n)
        assert tn == n and rows == tk and tk * tn <= qmod._DEQ_PASS_WEIGHTS
        assert 2 * tk * tn > qmod._DEQ_PASS_WEIGHTS or tk == k  # as deep as a pass allows


def test_deq_pass_is_whole_steps_within_the_cap():
    """`_deq_pass`: the whole tile where it is within the cap; else the most
    whole 256-row steps that divide it; 0 where one step is already over."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    cap = qmod._DEQ_PASS_WEIGHTS
    assert qmod._deq_pass(384, 256) == 384  # a k of 3 x 128: one pass, no steps
    assert qmod._deq_pass(2048, 1024) == 2048 and 2048 * 1024 <= cap
    assert qmod._deq_pass(2048, 2048) == 1024
    assert qmod._deq_pass(11008, 512) == 256 * 1  # 43 x 256: only 256 and 11008 divide
    assert qmod._deq_pass(1024, 7168) == 256
    assert qmod._deq_pass(512, 100352) == 0 and 256 * 100352 > cap


@pytest.mark.parametrize("m,k,itemsize,want", [
    (48, 8192, 2, 48), (512, 11008, 2, 512),  # the cells' largest: one tile
    (1024, 4096, 2, 512), (768, 4096, 2, 256),  # past 512 rows: the largest divisor
    (512, 28672, 2, 128), (512, 11008, 4, 256),  # x and its copy would not fit
])
def test_deq_m_tile_is_the_batch_unless_x_does_not_fit(m, k, itemsize, want):
    """One m tile streams and dequantises the whole weight once (PR 29 read
    3 x 16 rows at three times the time of 48); x stays in VMEM for the call,
    so a tile is cut only where x's block, its second buffer and the laid-out
    copy would pass their share."""
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    tm = qmod._deq_tiles(m, k, 4096, itemsize)[0]
    assert tm == want and m % tm == 0
    assert 3 * tm * k * itemsize <= qmod._DEQ_X_BYTES


class TestDispatchKnobs:
    """Contracts for the measurement-session knobs: prefill GEMM routing
    (ops.matmul.XLA_PREFILL_MIN_M), the block-dot tile overrides, and what
    the block-dot kernel leaves to the dequantising tier."""

    def test_xla_prefill_routing_threshold(self, monkeypatch):
        """Pins that the threshold actually ROUTES (not merely that both
        paths agree numerically): the fused kernel is stubbed to raise, so a
        prefill-shaped (t>1) call with m>=threshold must bypass it while
        decode-shaped calls — t==1 at ANY slot count, and 2-D calls — must
        hit it (ADVICE r3: flattened-m routing would starve batched decode)."""
        from dllama_tpu.ops import matmul as mm
        from dllama_tpu.ops.pallas import q40_matmul as qm

        w = QTensor.quantize((np.random.default_rng(0).standard_normal((256, 256)) * 0.05).astype(np.float32))
        x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 64, 256)), jnp.bfloat16)
        ref = np.asarray(mm.matmul(x, w, backend="xla"), np.float32)
        monkeypatch.setattr(mm, "XLA_PREFILL_MIN_M", 32)

        def boom(*a, **k):
            raise AssertionError("fused kernel must not run at prefill m >= threshold")

        monkeypatch.setattr(qm, "q40_matmul", boom)
        got = np.asarray(mm.matmul(x, w, backend="pallas"), np.float32)  # routed
        np.testing.assert_allclose(got, ref, atol=3e-2, rtol=3e-2)
        # decode-shaped calls must invoke the fused kernel even when the
        # flattened row count crosses the threshold (64 slots x t=1), and
        # for plain 2-D calls (no seq axis)
        for shape in ((64, 1, 256), (8, 256)):
            xd = jnp.asarray(
                np.random.default_rng(2).standard_normal(shape), jnp.bfloat16
            )
            with pytest.raises(AssertionError, match="fused kernel"):
                mm.matmul(xd, w, backend="pallas")

    @pytest.mark.parametrize("tiles", [dict(tk=4096, tn=128), dict(tn=256),
                                       dict(lanes=128), dict(rows=256)])
    def test_blockdot_tile_override_matches_default(self, tiles):
        """The chip sweep's overrides are static arguments of the jitted call
        (no module knob): another tiling is the same product."""
        from dllama_tpu.ops.pallas import q40_matmul as qm

        w = QTensor.quantize((np.random.default_rng(3).standard_normal((8192, 256)) * 0.05).astype(np.float32))
        x = jnp.asarray(np.random.default_rng(4).standard_normal((16, 8192)), jnp.bfloat16)
        args = (jnp.zeros((1,), jnp.int32), x, w.packed[None],
                jax.lax.bitcast_convert_type(w.scales, jnp.uint16)[None])
        want = np.asarray(qm._blockdot_call(*args, interpret=True, tk=8192, tn=128))
        got = np.asarray(qm._blockdot_call(*args, interpret=True, **tiles))
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)  # f32 sums, reordered

    @pytest.mark.parametrize("x_dtype,k", [(jnp.float32, 512), (jnp.bfloat16, 384)])
    def test_what_the_blockdot_kernel_cannot_take_goes_to_deq(self, monkeypatch, x_dtype, k):
        """float32 activations and a k that is not whole loop steps of 256
        rows take the dequantising tier, whatever m is."""
        from dllama_tpu.ops.pallas import q40_matmul as qm

        def boom(*a, **kw):
            raise AssertionError("block-dot kernel must not run")

        monkeypatch.setattr(qm, "_blockdot_call", boom)
        w = QTensor.quantize((np.random.default_rng(5).standard_normal((k, 256)) * 0.05).astype(np.float32))
        x = jnp.asarray(np.random.default_rng(6).standard_normal((8, k)), x_dtype)
        got = np.asarray(qm.q40_matmul(x, w, interpret=True), np.float32)
        want = np.asarray(x, np.float32) @ np.asarray(w.dequantize(jnp.float32))
        np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------- q80 matmul


@pytest.mark.parametrize("m", [1, 8, 64])
def test_q80_matmul_matches_dequant_dot(rng, m):
    """Fused Q80 kernels (blockdot m<=16, deq m>16) vs the XLA dequant dot."""
    from dllama_tpu.ops.pallas.q80_matmul import q80_matmul, supported
    from dllama_tpu.ops.quant import Q8Tensor, quantize_q80_np

    k, n = 128, 256
    w = (rng.standard_normal((n, k)) * 0.1).astype(np.float32)
    codes, scales = quantize_q80_np(w.reshape(-1))
    qt = Q8Tensor.from_file_layout(codes, scales, n, k)
    assert supported((m, k), qt)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    got = q80_matmul(x, qt, interpret=True)
    want = jnp.dot(x, qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_q80_matmul_stacked_layer_index(rng):
    from dllama_tpu.ops.pallas.q80_matmul import q80_matmul
    from dllama_tpu.ops.quant import Q8Tensor, quantize_q80_np

    k, n, L = 128, 128, 3
    layers = []
    for _ in range(L):
        w = (rng.standard_normal((n, k)) * 0.1).astype(np.float32)
        codes, scales = quantize_q80_np(w.reshape(-1))
        layers.append(Q8Tensor.from_file_layout(codes, scales, n, k))
    st = Q8Tensor(jnp.stack([l.codes for l in layers]),
                  jnp.stack([l.scales for l in layers]))
    x = jnp.asarray(rng.standard_normal((8, k)), jnp.float32)
    for li in range(L):
        got = q80_matmul(x, st, jnp.int32(li), interpret=True)
        want = jnp.dot(x, layers[li].dequantize(jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-3, rtol=1e-3)


def test_flash_attention_bucketed_vector_pos(rng):
    """Bucketed dispatch under PER-ROW positions (batched decode): the
    horizon is max(pos) + t, so the batch rides the view covering its
    deepest slot and every row stays exact."""
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

    q = jnp.asarray(rng.standard_normal((2, 1, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 4, 2048, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 4, 2048, 64)), jnp.float32)
    for pos in ([3, 300], [500, 511], [100, 1900]):
        pv = jnp.asarray(pos, jnp.int32)
        want = flash_gqa_attention(q, k, v, pv, interpret=True)
        got = flash_gqa_attention(q, k, v, pv, interpret=True, s_buckets=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=0, rtol=0)


def test_q80_matmul_bf16_and_odd_rows(rng):
    """q80 kernels: bf16 activations keep exactness of int8 codes, and odd
    row counts take the pad path."""
    from dllama_tpu.ops.pallas.q80_matmul import q80_matmul
    from dllama_tpu.ops.quant import Q8Tensor

    k, n = 256, 128
    w = Q8Tensor.quantize((rng.standard_normal((k, n)) * 0.1).astype(np.float32))
    for m, dt in ((3, jnp.float32), (8, jnp.bfloat16), (2, jnp.bfloat16)):
        x = jnp.asarray(rng.standard_normal((m, k)), dt)
        got = q80_matmul(x, w, interpret=True)
        want = jnp.dot(x, w.dequantize(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=5e-2, rtol=5e-2)
        assert got.dtype == dt and got.shape == (m, n)

"""Ring / sequence-parallel attention correctness on the 8-device CPU mesh.

The capability the reference lacks outright (SURVEY.md §5.7): KV sequence
sharding. Every test compares against the single-device full-softmax
reference with tight tolerances (exact math, only reduction-order noise)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dllama_tpu.engine.engine import InferenceEngine
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.ops.layers import gqa_attention
from dllama_tpu.parallel.mesh import MeshConfig, make_mesh
from dllama_tpu.parallel.ring_attention import ring_attention, sp_cache_attention
from dllama_tpu.parallel.sharding import LlamaShardings


def full_causal_reference(q, k, v):
    """Plain causal GQA softmax in f64-ish f32, query i attends keys <= i."""
    b, t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d).astype(np.float32)
    s = np.einsum("bthgd,bhsd->bhgts", qg, k.astype(np.float32)) / math.sqrt(d)
    mask = np.tril(np.ones((t, t), bool))
    s = np.where(mask[None, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bhgts,bhsd->bhgtd", p, v.astype(np.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, d)


@pytest.mark.parametrize("sp,hq,hkv", [(8, 4, 4), (4, 8, 2), (2, 4, 2)])
def test_ring_attention_matches_full_causal(rng, sp, hq, hkv):
    b, t, d = 2, 64, 16
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    want = full_causal_reference(q, k, v)

    mesh = make_mesh(MeshConfig(sp=sp))
    got = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp", None, None), P(None, None, "sp", None), P(None, None, "sp", None)),
            out_specs=P(None, "sp", None, None),
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-4)


def test_ring_attention_non_causal(rng):
    b, t, hq, hkv, d = 1, 32, 4, 2, 8
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d)
    s = np.einsum("bthgd,bhsd->bhgts", qg, k) / math.sqrt(d)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhgts,bhsd->bhgtd", p, v).transpose(0, 3, 1, 2, 4).reshape(b, t, hq, d)

    mesh = make_mesh(MeshConfig(sp=4))
    got = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=False),
            mesh=mesh,
            in_specs=(P(None, "sp", None, None), P(None, None, "sp", None), P(None, None, "sp", None)),
            out_specs=P(None, "sp", None, None),
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("t,pos", [(1, 17), (4, 8), (8, 0)])
def test_sp_cache_attention_matches_gqa(rng, t, pos):
    """LSE-merge sharded-cache attention == full-cache gqa_attention for
    decode (t=1) and chunked prefill (t>1) at arbitrary positions."""
    b, hq, hkv, d, s = 2, 8, 4, 16, 32
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    want = np.asarray(gqa_attention(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.int32(pos)))

    mesh = make_mesh(MeshConfig(sp=4, tp=2))
    got = jax.jit(
        jax.shard_map(
            lambda q, kc, vc, p: sp_cache_attention(q, kc, vc, p, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, None, "tp", None), P(None, "tp", "sp", None), P(None, "tp", "sp", None), P()),
            out_specs=P(None, None, "tp", None),
        )
    )(q, kc, vc, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-4)


def test_engine_sp_shard_map_end_to_end():
    """Engine with sp>1 now routes attention through the shard_map LSE path;
    must equal the single-device engine bit-for-tolerance."""
    cfg = LlamaConfig(
        dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4, vocab_size=128, seq_len=64
    )
    params = random_params(cfg, seed=3, dtype=jnp.float32, quantize=False)
    prompt = np.array([[5, 9, 2, 7, 1, 3]], dtype=np.int32)

    ref = InferenceEngine(cfg, params, cache_dtype=jnp.float32)
    ref_logits = np.asarray(ref.prefill(prompt))
    ref_l2 = np.asarray(ref.decode_step(np.array([[11]])))

    mesh = make_mesh(MeshConfig(sp=4, tp=2))
    sh = LlamaShardings(mesh, cfg)
    eng = InferenceEngine(cfg, params, cache_dtype=jnp.float32, shardings=sh)
    assert sh.attn_fn(1) is not None
    got = np.asarray(eng.prefill(prompt))
    np.testing.assert_allclose(got, ref_logits, atol=2e-4, rtol=1e-3)
    got_l2 = np.asarray(eng.decode_step(np.array([[11]])))
    np.testing.assert_allclose(got_l2, ref_l2, atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("t,pos", [(8, 0), (8, 8), (16, 16)])
def test_ring_cache_attention_matches_gqa(rng, t, pos):
    """Sequence-sharded-query prefill over the rotating cache == full-cache
    gqa_attention at arbitrary chunk positions (VERDICT r1 #6)."""
    from dllama_tpu.parallel.ring_attention import ring_cache_attention

    b, hq, hkv, d, s = 2, 8, 4, 16, 32
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    want = np.asarray(gqa_attention(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.int32(pos)))

    mesh = make_mesh(MeshConfig(sp=4, tp=2))
    got = jax.jit(
        jax.shard_map(
            lambda q, kc, vc, p: ring_cache_attention(q, kc, vc, p, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp", "tp", None), P(None, "tp", "sp", None), P(None, "tp", "sp", None), P()),
            out_specs=P(None, "sp", "tp", None),
        )
    )(q, kc, vc, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("sp,tp", [(2, 1), (4, 2), (8, 1)])
def test_engine_sp_ring_prefill_long_prompt(sp, tp):
    """e2e: a prompt longer than one sp shard's cache slice prefills through
    the ring path (chunk width divisible by sp -> ring_cache_attention) and
    matches single-device logits; decode then runs the LSE-merge path."""
    cfg = LlamaConfig(
        dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4, vocab_size=128, seq_len=64
    )
    params = random_params(cfg, seed=3, dtype=jnp.float32, quantize=False)
    rng = np.random.default_rng(11)
    # seq_len/sp <= 32 for sp>=2; prompt of 40 spans multiple shard slices
    prompt = rng.integers(1, cfg.vocab_size, size=(1, 40)).astype(np.int32)

    ref = InferenceEngine(cfg, params, cache_dtype=jnp.float32)
    ref_logits = np.asarray(ref.prefill(prompt))
    ref_l2 = np.asarray(ref.decode_step(np.array([[11]])))

    mesh = make_mesh(MeshConfig(sp=sp, tp=tp))
    sh = LlamaShardings(mesh, cfg)
    eng = InferenceEngine(cfg, params, cache_dtype=jnp.float32, shardings=sh)
    got = np.asarray(eng.prefill(prompt))
    np.testing.assert_allclose(got, ref_logits, atol=2e-4, rtol=1e-3)
    got_l2 = np.asarray(eng.decode_step(np.array([[11]])))
    np.testing.assert_allclose(got_l2, ref_l2, atol=2e-4, rtol=1e-3)

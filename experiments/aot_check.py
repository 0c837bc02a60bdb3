"""The case table of the offline compile check — no TPU attached.

libtpu is installed locally, and a PJRT topology description lets XLA:TPU
compile a lowered module for a chip that is described, not attached
(`jax.experimental.topologies.get_topology_desc`). This module builds the
cases: every Pallas kernel (the Q40 kernels and the paged sweeps at the
benchmark cells' own shapes too), the shard_map'd tensor-parallel paths, the
whole InferenceEngine step, the whole BatchEngine serving programs (paged
decode chunk, hybrid step, prefill chunk, spec-verify chunk) at Llama-3.2-1B
width, and the step programs of the five served architectures at their
published widths. v5e is the one chip there is to run what gets accepted,
so it is the one target.

`tests/test_chip_compile.py` compiles every one of them (`pytest
tests/test_chip_compile.py -k '<case>'` is the command line): a case that
compiles was turned into machine code for the chip by Mosaic/XLA:TPU —
interpret mode cannot show a misaligned slice, a VMEM overflow or a program
that does not fit HBM; this can. Nothing runs, so it says nothing about
results or times: `python chip_smoke.py` on the chip does, and
`experiments/warm_compile.py` offers a cell's programs to the chip's own
compiler. Every case is something the program can be told to run, and a
refusal fails the test.

Code that asks the platform (kernels=auto, interpret=) sees the CPU here, so
whoever compiles these cases points `ops.matmul.device_platform` at "tpu"
first (the test's fixture; `experiments/pool_copies.py`'s main()).
"""

import dataclasses
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# topology-AOT needs no TPU attached, and off GCP the instance-metadata
# probe stalls through 30 failing fetches before libtpu gives up — skip it
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
# libtpu lets one process at a time load it (/tmp/libtpu_lockfile) — right
# for a process that drives a chip, wrong for compile-only use: a pytest
# worker holds it for its lifetime once it has described the topology, and
# the next worker (or experiments/pool_copies.py) must still start
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dllama_tpu.ops.pallas import q40_matmul as qmod
from dllama_tpu.ops.quant import Q_BLOCK, QTensor

S = jax.ShapeDtypeStruct


TARGET = "v5e:2x2"


def topology():
    """The described (not attached) chip target. Unresolvable is FATAL — a
    gate that silently compiled for nothing would pass green while
    validating nothing."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(TARGET, platform="tpu")
    except Exception as e:
        raise SystemExit(
            f"FATAL: topology {TARGET} unavailable ({repr(e)[:160]}) — the "
            "compile check cannot run; do not treat this as a pass")


#: Llama-3.2-1B: dim 2048, hidden 8192, 16 layers,
#: 32/8 heads — head_size 64, NOT 128 — vocab 128256. The width chip_smoke.py
#: serves, so the width every case below is sized to unless it says 8b.
DIM, HIDDEN, N_LAYERS, HQ, HKV, HD, VOCAB = 2048, 8192, 16, 32, 8, 64, 128256
HD_8B = 128  # Llama-3.1-8B / OLMoE head size: whole 128-lane rows
SLOTS, SEQ, SPEC_K = 8, 2048, 4  # chip_smoke.py's serve flags


#: every case all_cases() builds, by name and in its order: what
#: tests/test_chip_compile.py parametrises over (building the cases needs the
#: described topology and two engines, so the names stand here, and a test
#: holds them equal to what is built)
CASES = (
    "q40 decode m=8 w1(2048x8192)",
    "q40 decode m=8 w2(8192x2048)",
    "q40 decode m=8 wcls(2048x128256)",
    "q40 prefill m=256 w1(2048x8192)",
    "q40 prefill m=256 w2(8192x2048)",
    "q40 prefill m=256 wcls(2048x128256)",
    "q40 decode m=8 wk(2048x512)",
    "q40 spec-verify m=40 w1",
    "q40 decode m=8 wcls8b(4096x128256)",
    "q40 prefill m=256 wcls8b(4096x128256)",
    "q40 decode m=16 deepseek wq(4096x4096)",
    "q40 decode m=16 deepseek w1(4096x11008)",
    "q40 decode m=16 deepseek w2(11008x4096)",
    "q40 decode m=16 deepseek head(4096x102400)",
    "q40 decode m=8 granite head(2048x100352)",
    "q40 decode m=8 granite in_proj(2048x8576)",
    "q40 m=48 granite in_proj(2048x8576)",
    "q40 m=48 granite out_proj(4096x2048)",
    "q40 m=48 granite w1(2048x8192)",
    "q40 m=48 granite w2(8192x2048)",
    "q40 m=48 granite head(2048x100352)",
    "q40 m=512 smallthinker wq(2560x3584)",
    "q40 m=128 deepseek w2(11008x4096)",
    "q80 decode m=8 w1(2048x8192)",
    "q80 prefill m=256 w1(2048x8192)",
    "q80 decode m=8 wcls8b(4096x128256)",
    "flash decode t=1 S=2048 hd=64",
    "flash prefill t=256 S=2048 hd=64",
    "flash decode t=1 S=1024 hd=128",
    "flash prefill t=256 S=1024 hd=128",
    "flash decode t=1 S=8192 hd=128",
    "flash decode f8 KV cache hd=128",
    "flash decode bucketed S=8192 hd=128",
    "paged decode t=1 p=128 hd=64 fused scatter",
    "paged spec verify t=5 p=128 hd=64 fused scatter",
    "paged decode t=1 p=16 hd=64 fused scatter",
    "paged decode t=1 p=8 hd=64 fused scatter",
    "paged decode t=1 p=24 (odd page) hd=64 fused scatter",
    "paged prefill t=256 p=128 hd=64 (XLA pre-scatter)",
    "paged decode t=1 p=128 hd=64 read-only sweep",
    "paged decode t=1 p=128 hd=128 fused scatter",
    "paged decode t=1 p=128 hd=64 layer-indexed stack",
    "paged prefill t=256 p=128 hd=64 layer-indexed stack (XLA pre-scatter)",
    "paged spec verify t=9 p=128 hd=128 fused scatter",
    "paged decode t=1 p=128 b=12 Hkv=32 hd=128 layer-indexed stack "
    "(deepseek7b.decode_closed)",
    "paged decode t=1 p=128 b=48 Hkv=8 hd=64 layer-indexed stack "
    "(granite4h.reason_closed)",
    "paged latent decode t=1 p=128 b=32 64 heads x 576 layer-indexed stack "
    "(axk1.long_reason_closed)",
    "paged latent slice t=512 p=128 64 heads x 576 layer-indexed stack "
    "(axk1.long_reason_closed, XLA pre-scatter)",
    "paged latent decode t=1 p=128 b=48 32 heads x 576 layer-indexed stack "
    "(kimilinear.reason_closed)",
    "moe sort (8 experts, 64 tokens)",
    "moe dispatch (8 experts, 64 tokens)",
    "moe dense (8 experts, 64 tokens)",
    "tp=4 shard_map mm out-shard (w1)",
    "tp=4 shard_map mm in-shard+psum (w2)",
    "tp=4 shard_map head-sharded flash",
    "FULL 1b tp=4 engine step t=256",
    "FULL 1b tp=4 engine step t=1",
    "FULL 1b decode step (scan+flash+blockdot)",
    "FULL 1b speculative decode (k=8 while_loop)",
    "serve 1b paged decode chunk n=4",
    "serve 1b hybrid step p=64 n=4",
    "serve 1b paged prefill chunk m=256",
    "serve 1b paged prefill chunk m=1",
    "serve 1b spec-verify chunk K=4 m=4",
    "serve 1b paged penalized decode chunk n=4",
    "serve olmoe-width 64-slot paged decode chunk n=4",
)


def cases():
    """Kernel-level (name, fn, abstract args) tuples at the widths above."""
    L = 2
    layer = S((1,), jnp.int32)
    out = []

    def q40_case(name, m, k, n, layers=L):
        packed, scales = (S((layers, k // 2, n), jnp.uint8),
                          S((layers, k // Q_BLOCK, n), jnp.uint16))
        out.append((name, lambda l, x, p, s: qmod.q40_matmul(x, QTensor(p, s), l),
                    (layer, S((m, k), jnp.bfloat16), packed, scales)))

    # UNSTACKED 2-D weights with f16 scales and no layer index: byte-for-byte
    # the operands a loaded .m file's wcls runs with
    def flat_case(name, m, k, n):
        out.append((name, lambda x, p, s: qmod.q40_matmul(x, QTensor(p, s)),
                    (S((m, k), jnp.bfloat16), S((k // 2, n), jnp.uint8),
                     S((k // Q_BLOCK, n), jnp.float16))))

    # decode rows = serving slots, prefill rows = the 256-token chunk cap;
    # the dispatcher takes blockdot (m <= 16) / deq (m > 16)
    for m in (SLOTS, 256):
        tier = "decode" if m <= 16 else "prefill"
        q40_case(f"q40 {tier} m={m} w1({DIM}x{HIDDEN})", m, DIM, HIDDEN)
        q40_case(f"q40 {tier} m={m} w2({HIDDEN}x{DIM})", m, HIDDEN, DIM)
        flat_case(f"q40 {tier} m={m} wcls({DIM}x{VOCAB})", m, DIM, VOCAB)
    q40_case(f"q40 decode m={SLOTS} wk({DIM}x{HKV * HD})", SLOTS, DIM, HKV * HD)
    q40_case(f"q40 spec-verify m={SLOTS * (SPEC_K + 1)} w1",
             SLOTS * (SPEC_K + 1), DIM, HIDDEN)
    flat_case("q40 decode m=8 wcls8b(4096x128256)", 8, 4096, 128256)
    flat_case("q40 prefill m=256 wcls8b(4096x128256)", 256, 4096, 128256)
    # the m <= 16 kernel at the benchmark cells' own decode shapes (PERF.md
    # section 4): each takes another tile from `_blockdot_tiles`
    for tag, m, k, n, layers in (
            ("deepseek wq", 16, 4096, 4096, 30), ("deepseek w1", 16, 4096, 11008, 30),
            ("deepseek w2", 16, 11008, 4096, 30), ("deepseek head", 16, 4096, 102400, 1),
            ("granite head", 8, 2048, 100352, 1), ("granite in_proj", 8, 2048, 8576, 40)):
        q40_case(f"q40 decode m={m} {tag}({k}x{n})", m, k, n, layers=layers)
    # the dequantising tier (m > 16) at the cells' own shapes: Granite's 48
    # slots, its head, a SmallThinker slice, a DeepSeek slice over k = 43 x 256
    for tag, m, k, n, layers in (
            ("granite in_proj", 48, 2048, 8576, 40), ("granite out_proj", 48, 4096, 2048, 40),
            ("granite w1", 48, 2048, 8192, 40), ("granite w2", 48, 8192, 2048, 40),
            ("granite head", 48, 2048, 100352, 1), ("smallthinker wq", 512, 2560, 3584, 24),
            ("deepseek w2", 128, 11008, 4096, 30)):
        q40_case(f"q40 m={m} {tag}({k}x{n})", m, k, n, layers=layers)

    # q80 fused matmuls (packed int8 weights, the Q80-file fast path): the
    # same decode/prefill split as q40, production on unsharded engines
    from dllama_tpu.ops.pallas.q80_matmul import q80_matmul
    from dllama_tpu.ops.quant import Q8Tensor

    q8w = Q8Tensor(S((L, DIM, HIDDEN), jnp.int8), S((L, DIM // Q_BLOCK, HIDDEN), jnp.uint16))
    for q8m in (8, 256):
        out.append((f"q80 {'decode' if q8m <= 16 else 'prefill'} m={q8m} w1({DIM}x{HIDDEN})",
                    lambda x, l, c, s: q80_matmul(x, Q8Tensor(c, s), l),
                    (S((q8m, DIM), jnp.bfloat16), S((), jnp.int32),
                     q8w.codes, q8w.scales)))
    out.append(("q80 decode m=8 wcls8b(4096x128256)",
                lambda x, c, s: q80_matmul(x, Q8Tensor(c, s)),
                (S((8, 4096), jnp.bfloat16), S((4096, 128256), jnp.int8),
                 S((4096 // Q_BLOCK, 128256), jnp.float16))))

    # flash attention over the dense cache: decode (t=1, group=4 folded+pad)
    # and prefill shapes, at the 1b head size and at whole-lane heads
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

    def flash(name, t, s_len, hd, kv_dtype=jnp.bfloat16, **kw):
        kv = S((1, HKV, s_len, hd), kv_dtype)
        out.append((name,
                    lambda q, k, v: flash_gqa_attention(q, k, v, jnp.int32(7), **kw),
                    (S((1, t, HQ, hd), jnp.bfloat16), kv, kv)))

    flash(f"flash decode t=1 S={SEQ} hd={HD}", 1, SEQ, HD)
    flash(f"flash prefill t=256 S={SEQ} hd={HD}", 256, SEQ, HD)
    flash("flash decode t=1 S=1024 hd=128", 1, 1024, HD_8B)
    flash("flash prefill t=256 S=1024 hd=128", 256, 1024, HD_8B)
    flash("flash decode t=1 S=8192 hd=128", 1, 8192, HD_8B)
    # f8 (e4m3) KV cache variant (--cache-dtype f8): half the cache DMA
    flash("flash decode f8 KV cache hd=128", 1, 1024, HD_8B, jnp.float8_e4m3fn)
    # bucketed grid (DLLAMA_FLASH_BUCKETS): lax.switch over pow-2 cache
    # views — every branch is its own pallas_call instance, so Mosaic must
    # accept all of them plus the switch wrapping
    flash("flash decode bucketed S=8192 hd=128", 1, 8192, HD_8B, s_buckets=True)

    # general paged flash-decode kernel (ops/pallas/paged_attention): the
    # paged-by-default serving route — scalar-prefetched block tables, one
    # grid step per (slot, block of kv heads, q tile), pages DMA'd as whole
    # head blocks through a ring that does not drain between grid steps, the
    # new KV rows blended into the sweep's landed copy of their page. The
    # pool is as the engine allocates it on this route: rows pool_lanes(hd)
    # wide (Mosaic refuses to DMA-walk a 64-lane pool). Production at the
    # shipped default page size AND at the small/odd sizes the capability
    # check admits; t=K+1 is the batched spec-verify shape, t=256 exercises
    # the XLA pre-scatter prefill path of the same wrapper; "layer-indexed
    # stack" is the call as the decoder's layer scan makes it (the whole
    # [L, P, ...] pool and the layer as data). The last two are the decode
    # calls of the benchmark's two cells at their own shapes (PERF.md
    # section 4).
    from dllama_tpu.ops.pallas.paged_attention import paged_decode_attention, pool_lanes

    def paged(name, page, t=1, b=SLOTS, hd=HD, read_only=False, stacked=False,
              hq=HQ, hkv=HKV, pages=None):
        nb = SEQ // page
        pools = S(((2,) * stacked) + ((pages or b * nb) + 1, hkv, page,
                                      pool_lanes(hd)), jnp.bfloat16)
        args = [S((b, t, hq, hd), jnp.bfloat16), pools, pools,
                S((b, nb), jnp.int32), S((b,), jnp.int32)]
        if not read_only:
            args += [S((b, hkv, t, hd), jnp.bfloat16),
                     S((b, hkv, t, hd), jnp.bfloat16), S((b,), jnp.bool_)]
        fn = lambda *a: paged_decode_attention(*a, interpret=False)
        if stacked:  # the layer-stacked pool, the layer as data (PR 27)
            args.append(S((), jnp.int32))
            fn = lambda *a: paged_decode_attention(*a[:-1], layer=a[-1],
                                                   interpret=False)
        out.append((name, fn, tuple(args)))

    paged(f"paged decode t=1 p=128 hd={HD} fused scatter", 128)
    paged(f"paged spec verify t={SPEC_K + 1} p=128 hd={HD} fused scatter", 128, t=SPEC_K + 1)
    paged(f"paged decode t=1 p=16 hd={HD} fused scatter", 16)
    paged(f"paged decode t=1 p=8 hd={HD} fused scatter", 8)
    paged(f"paged decode t=1 p=24 (odd page) hd={HD} fused scatter", 24, b=4)
    paged(f"paged prefill t=256 p=128 hd={HD} (XLA pre-scatter)", 128, t=256, b=1)
    paged(f"paged decode t=1 p=128 hd={HD} read-only sweep", 128, read_only=True)
    paged("paged decode t=1 p=128 hd=128 fused scatter", 128, hd=HD_8B)
    paged(f"paged decode t=1 p=128 hd={HD} layer-indexed stack", 128, stacked=True)
    paged(f"paged prefill t=256 p=128 hd={HD} layer-indexed stack (XLA pre-scatter)",
          128, t=256, b=1, stacked=True)
    paged("paged spec verify t=9 p=128 hd=128 fused scatter", 128, t=9, hd=HD_8B)
    paged("paged decode t=1 p=128 b=12 Hkv=32 hd=128 layer-indexed stack "
          "(deepseek7b.decode_closed)", 128, b=12, hq=32, hkv=32, hd=128,
          pages=66, stacked=True)
    paged("paged decode t=1 p=128 b=48 Hkv=8 hd=64 layer-indexed stack "
          "(granite4h.reason_closed)", 128, b=48, hq=32, hkv=8, hd=64,
          pages=456, stacked=True)

    # the latent sweep (one pool, Hkv = 1, the row is key and value, several
    # pages a pass: `_plan`) at the two latent cells' shapes: A.X-K1's decode
    # call and its 512-row slice (256 q tiles, scattered by XLA first) and
    # Kimi-Linear's decode call, each on the layer-stacked pool
    def latent(name, b, t, hq, layers, pages, nb, rank=512, pe=64, page=128):
        w, lanes = rank + pe, pool_lanes(rank + pe)
        args = (S((b, t, hq, w), jnp.bfloat16),
                S((layers, pages + 1, 1, page, lanes), jnp.bfloat16),
                S((layers, 1, 1, 8, 128), jnp.bfloat16), S((b, nb), jnp.int32),
                S((b,), jnp.int32), S((b, 1, t, w), jnp.bfloat16),
                S((b,), jnp.bool_), S((), jnp.int32))
        fn = lambda q, pool, ph, tb, pos, new, act, li: paged_decode_attention(
            q, pool, ph, tb, pos, new, None, act, layer=li, interpret=False,
            latent=rank, scale=w ** -0.5)
        out.append((name, fn, args))

    latent("paged latent decode t=1 p=128 b=32 64 heads x 576 layer-indexed "
           "stack (axk1.long_reason_closed)", 32, 1, 64, 9, 2368, 128)
    latent("paged latent slice t=512 p=128 64 heads x 576 layer-indexed stack "
           "(axk1.long_reason_closed, XLA pre-scatter)", 1, 512, 64, 9, 2368, 128)
    latent("paged latent decode t=1 p=128 b=48 32 heads x 576 layer-indexed "
           "stack (kimilinear.reason_closed)", 48, 1, 32, 7, 456, 64)

    # MoE compute schemes: no Pallas inside, but `sort` leans on
    # lax.ragged_dot and `dispatch` on .at[].add scatters — both exotic
    # enough on XLA:TPU that the check covers them
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.ops.layers import moe_ffn

    mcfg = LlamaConfig(dim=1024, hidden_dim=2048, n_layers=2, n_heads=8,
                       n_kv_heads=4, vocab_size=512, seq_len=64,
                       n_experts=8, n_active_experts=2)
    moe_args = (S((1, 64, 1024), jnp.bfloat16), S((1024, 8), jnp.float32),
                S((8, 1024, 2048), jnp.bfloat16),
                S((8, 2048, 1024), jnp.bfloat16),
                S((8, 1024, 2048), jnp.bfloat16))
    # the auto resolution takes sort (n >= E) and dense (n < E, e.g. B=1
    # decode); `--moe dispatch` asks for the third
    for impl in ("sort", "dispatch", "dense"):
        out.append((f"moe {impl} (8 experts, 64 tokens)",
                    lambda h, g, w1, w2, w3, impl=impl: moe_ffn(
                        mcfg, h, g, w1, w2, w3, impl=impl),
                    moe_args))
    return out


def abstract_params(cfg, A):
    """The param tree of any served configuration as shapes only, each leaf
    `A(shape, dtype)`: what models/formats.load_params hands an engine.
    Stacked by layer, the mixers apart by kind (attention tensors by
    `attn_suffix` where the windowed layers have heads of their own), dense
    and expert feed-forward weights apart; packed u8 nibbles + f16 scales as
    the .m file stores them, bf16 embedding, f32 norms; in_proj, kda_proj
    and mla_kva zero-padded to whole lane tiles, W_kvb float32 by head."""
    def qw(lead, k, n):
        return QTensor(A((*lead, k // 2, n), jnp.uint8),
                       A((*lead, k // Q_BLOCK, n), jnp.float16))

    f32 = lambda *shape: A(shape, jnp.float32)
    pad = lambda n, to: -(-n // to) * to
    L, d, h = cfg.n_layers, cfg.dim, cfg.n_heads
    layers = {"rms_att": f32(L, d), "rms_ffn": f32(L, d)}
    if cfg.latent:
        Lm, qd = cfg.n_attn_layers, h * (cfg.qk_nope_dim + cfg.qk_pe_dim)
        if cfg.q_lora_rank:
            layers.update(mla_qa=qw((Lm,), d, cfg.q_lora_rank),
                          mla_q_norm=f32(Lm, cfg.q_lora_rank),
                          mla_qb=qw((Lm,), cfg.q_lora_rank, qd))
        else:
            layers["mla_q"] = qw((Lm,), d, qd)
        layers.update(
            mla_kva=qw((Lm,), d, pad(cfg.cache_row, 128)),
            mla_kv_norm=f32(Lm, cfg.kv_lora_rank),
            mla_kvb=f32(Lm, h, cfg.qk_nope_dim + cfg.v_head_dim, cfg.kv_lora_rank),
            mla_o=qw((Lm,), h * cfg.v_head_dim, d))
    else:
        # stacked apart by kind where the windowed layers have heads of their
        # own; retention layers hold the attention tensors and their gate
        La, Lw = cfg.n_attn_layers or cfg.n_retention_layers, cfg.n_window_layers
        for windowed, n in ([(False, La - Lw), (True, Lw)] if cfg.window_heads
                            else [(False, La)]):
            sfx, ad = cfg.attn_suffix(windowed), cfg.attn_dim_of(windowed)
            layers.update({
                "wq" + sfx: qw((n,), d, ad), "wk" + sfx: qw((n,), d, cfg.kv_dim),
                "wv" + sfx: qw((n,), d, cfg.kv_dim), "wo" + sfx: qw((n,), ad, d)})
            if cfg.qk_norm:
                layers.update({"q_norm" + sfx: f32(n, cfg.head_size),
                               "k_norm" + sfx: f32(n, cfg.head_size)})
            if cfg.attn_gate:
                layers["attn_gate" + sfx] = f32(n, d, cfg.heads_of(windowed))
        if cfg.n_retention_layers:
            layers.update(ret_gate=f32(La, d, cfg.n_kv_heads),
                          ret_gate_bias=f32(La, cfg.n_kv_heads))
    if cfg.n_ssm_layers:
        Ls, cd = cfg.n_ssm_layers, cfg.ssm_conv_dim
        layers.update(
            in_proj=qw((Ls,), d, pad(cfg.ssm_in_proj, 128)),
            conv_w=f32(Ls, cd, cfg.ssm_conv), conv_b=f32(Ls, cd),
            dt_bias=f32(Ls, cfg.ssm_heads), a_log=f32(Ls, cfg.ssm_heads),
            d=f32(Ls, cfg.ssm_heads), ssm_norm=f32(Ls, cfg.ssm_inner),
            out_proj=qw((Ls,), cfg.ssm_inner, d))
    if cfg.n_kda_layers:
        Lk, inner, rank = cfg.n_kda_layers, cfg.kda_inner, cfg.kda_rank
        layers.update(
            kda_proj=qw((Lk,), d, pad(cfg.kda_proj, 512)),
            kda_conv_w=f32(Lk, 3 * inner, cfg.kda_conv),
            kda_fb=qw((Lk,), rank, inner), kda_gb=qw((Lk,), rank, inner),
            kda_dt_bias=f32(Lk, inner), kda_a_log=f32(Lk, cfg.kda_heads),
            kda_norm=f32(Lk, cfg.kda_head_dim), kda_o=qw((Lk,), inner, d))
    Ld = cfg.n_dense_ffn_layers
    if Ld:
        layers.update(w1=qw((Ld,), d, cfg.hidden_dim), w2=qw((Ld,), cfg.hidden_dim, d),
                      w3=qw((Ld,), d, cfg.hidden_dim))
    if cfg.n_experts:
        Le, w, E = L - Ld, cfg.expert_width, cfg.n_held_experts
        layers.update(moe_gate=f32(Le, d, cfg.n_experts), moe_w1=qw((Le, E), d, w),
                      moe_w2=qw((Le, E), w, d), moe_w3=qw((Le, E), d, w))
        if cfg.router_sigmoid:
            layers["moe_bias"] = f32(Le, cfg.n_experts)
        if cfg.n_shared_experts:
            layers.update(shared_w1=qw((Le,), d, w), shared_w2=qw((Le,), w, d),
                          shared_w3=qw((Le,), d, w))
    return {"embedding": A((cfg.vocab_size, d), jnp.bfloat16), "final_norm": f32(d),
            "wcls": qw((), d, cfg.vocab_size), "layers": layers}


def on_one_chip(topo):
    """`A(shape, dtype)` for arguments pinned to one device of the described
    topology, so that XLA:TPU (not Host) compiles the module."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: S(shape, dt, sharding=one)


def _cfg_1b(seq_len=SEQ):
    from dllama_tpu.models.config import LlamaConfig

    return LlamaConfig(dim=DIM, hidden_dim=HIDDEN, n_layers=N_LAYERS, n_heads=HQ,
                       n_kv_heads=HKV, vocab_size=VOCAB, seq_len=seq_len)


def full_step_case(topo):
    """The ENTIRE 1b InferenceEngine step as kernels=auto resolves it on one
    chip — embedding gather, 16-layer scan with blockdot matmuls + flash
    attention + KV cache update, final norm, wcls — and the single-request
    speculative decoder over it. Kernel-level acceptance can miss
    interactions (Mosaic custom calls inside lax.scan, donated buffers);
    this is the whole graph."""
    from dllama_tpu.engine.kernel_select import resolve_kernels
    from dllama_tpu.models.llama import KVCache, forward

    cfg = _cfg_1b(1024)
    A = on_one_chip(topo)
    params = abstract_params(cfg, A)
    cshape = (cfg.n_layers, 1, cfg.n_kv_heads, cfg.seq_len, cfg.head_size)
    cache = KVCache(A(cshape, jnp.bfloat16), A(cshape, jnp.bfloat16))
    rope = A((cfg.seq_len, cfg.head_size // 2, 2), jnp.float32)
    tokens = A((1, 1), jnp.int32)
    pos = A((), jnp.int32)
    sel = resolve_kernels(cfg, cfg.seq_len, 1)
    assert sel.bucket_tag() == "pallas/flash" and not sel.interpret, sel

    def fwd(params, cache, tokens, pos, rope, last_only=False):
        return forward(cfg, params, tokens, pos, cache, rope, sel.attn_fn,
                       mm=sel.mm, mm_in=sel.mm_in, last_only=last_only)

    def step(params, cache, tokens, pos, rope):
        logits, cache = fwd(params, cache, tokens, pos, rope, last_only=True)
        return logits[:, -1], cache

    # the speculative decoder: while_loop(propose + (k+1)-wide verify) over
    # the same kernels — m=9 blockdot, 9-row flash fold, scan-in-while_loop
    from dllama_tpu.engine.speculative import make_spec_decode

    spec = make_spec_decode(fwd, cfg.seq_len, k=8, donate=False)
    h = A((cfg.seq_len + 1,), jnp.int32)
    cur = A((), jnp.int32)

    def spec_step(params, cache, h, cur, pos, rope):
        return spec(params, cache, h, cur, pos, rope, 32)

    return [
        ("FULL 1b decode step (scan+flash+blockdot)", step,
         (params, cache, tokens, pos, rope)),
        ("FULL 1b speculative decode (k=8 while_loop)", spec_step,
         (params, cache, h, cur, pos, rope)),
    ]


def sharded_cases(topo):
    """The shard_map'd Pallas tensor-parallel path (parallel/sharding.py) as
    `--mesh tp=4` resolves it, compiled over all four described chips at 1b
    width: the out-dim-sharded and in-dim-sharded(+psum) matmuls, the
    head-sharded flash kernel, and the whole engine step (256-token prefill
    and one decode token) that `chip_smoke.py --chips 4` runs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dllama_tpu.engine.kernel_select import resolve_kernels
    from dllama_tpu.models.llama import KVCache, forward
    from dllama_tpu.parallel.mesh import MeshConfig, make_mesh
    from dllama_tpu.parallel.sharding import LlamaShardings

    cfg = _cfg_1b()
    mesh = make_mesh(MeshConfig(tp=4), devices=topo.devices[:4])
    sh = LlamaShardings(mesh, cfg)
    ns = lambda spec: NamedSharding(mesh, spec)
    sel = resolve_kernels(cfg, cfg.seq_len, 1, shardings=sh)
    assert sel.bucket_tag() == "pallas/sharded_flash" and not sel.interpret, sel
    L = 2

    def operands(k, n, spec):
        return (S((L, k // 2, n), jnp.uint8, sharding=ns(spec)),
                S((L, k // Q_BLOCK, n), jnp.uint16, sharding=ns(spec)))

    x = S((1, 1, cfg.dim), jnp.bfloat16, sharding=ns(P()))
    li = S((), jnp.int32, sharding=ns(P()))
    out = []
    p1, s1 = operands(cfg.dim, cfg.hidden_dim, P(None, None, "tp"))
    out.append(("tp=4 shard_map mm out-shard (w1)",
                lambda x, p, s, l: sel.mm(x, QTensor(p, s), l),
                (x, p1, s1, li)))
    p2, s2 = operands(cfg.hidden_dim, cfg.dim, P(None, "tp", None))
    xh = S((1, 1, cfg.hidden_dim), jnp.bfloat16, sharding=ns(P(None, None, "tp")))
    out.append(("tp=4 shard_map mm in-shard+psum (w2)",
                lambda x, p, s, l: sel.mm_in(x, QTensor(p, s), l),
                (xh, p2, s2, li)))
    q = S((1, 1, cfg.n_heads, cfg.head_size), jnp.bfloat16,
          sharding=ns(P(None, None, "tp", None)))
    kc = S((1, cfg.n_kv_heads, cfg.seq_len, cfg.head_size), jnp.bfloat16,
           sharding=ns(P(None, "tp", None, None)))
    pos = S((), jnp.int32, sharding=ns(P()))
    out.append(("tp=4 shard_map head-sharded flash", sel.attn_fn,
                (q, kc, kc, pos)))

    shapes = abstract_params(cfg, S)
    params = jax.tree.map(lambda a, spec: S(a.shape, a.dtype, sharding=ns(spec)),
                          shapes, sh.param_spec_tree(shapes))
    cshape = (cfg.n_layers, 1, cfg.n_kv_heads, cfg.seq_len, cfg.head_size)
    cspec = ns(sh.cache_spec(batch=1))
    cache = KVCache(S(cshape, jnp.bfloat16, sharding=cspec),
                    S(cshape, jnp.bfloat16, sharding=cspec))
    rope = S((cfg.seq_len, cfg.head_size // 2, 2), jnp.float32, sharding=ns(P()))

    def step(params, cache, tokens, pos, rope):
        logits, cache = forward(cfg, params, tokens, pos, cache, rope,
                                sel.attn_fn, mm=sel.mm, mm_in=sel.mm_in,
                                last_only=True)
        return logits[:, -1], cache

    for t in (256, 1):
        out.append((f"FULL 1b tp=4 engine step t={t}", step,
                    (params, cache, S((1, t), jnp.int32, sharding=ns(P())),
                     pos, rope)))
    return out


def engine_programs(topo, tag, cfg, params, slots, spec, kv_pages=0,
                    hybrid_p=(64,), seq: int = SEQ, prefill_chunk: int = 256,
                    prefill_m=()):
    """(name, thunk) for the step programs of a paged
    BatchEngine (`serve --slots N --max-seq-len 2048 [--spec-k K]`): the
    engine is built here on the CPU over shapes, and each thunk lowers one
    of its programs for the described chip and returns the compiled
    executable. `kv_pages` 0 = full coverage; `hybrid_p` = the prefill
    slices to offer the hybrid step at, `prefill_m` the chunks to offer the
    slot prefill at beside a speculating engine's. serving_cases() and
    experiments/pool_copies.py (the 7B cell's sizes) both build on this."""
    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models.llama import PagedKVCache
    from dllama_tpu.ops.layers import build_rope_cache

    A = on_one_chip(topo)
    place = lambda tree: jax.tree.map(lambda a: A(a.shape, a.dtype), tree)
    i32 = lambda *shape: A(shape, jnp.int32)
    f32 = lambda *shape: A(shape, jnp.float32)
    page = 128
    # a 2-page pool keeps construction cheap; the programs are lowered
    # against the pool the server allocates
    be = BatchEngine(cfg, params, n_slots=slots, max_seq_len=seq,
                     kv_layout="paged", page_size=page, kv_pages=2,
                     spec=spec, max_prefill_chunk=prefill_chunk)
    from dllama_tpu.engine.kernel_select import kinds_tag

    assert be.kernel_route == (
        ("pallas/paged_kernel" + (".window" if cfg.n_window_layers else "")
         + (".latent" if cfg.latent else "") + kinds_tag(cfg)
         if cfg.n_attn_layers else "pallas/no_cache_rows")
        + (f"+{cfg.state_kind}_step.float32" if cfg.recurrent else "")
        + ("+moe_grouped" if cfg.n_experts else "")
        + (f".groups{cfg.expert_groups_kept}of{cfg.n_expert_groups}"
           if cfg.grouped_routing else "")), be.kernel_route
    nb = seq // page
    n_pages = kv_pages or slots * nb
    row = (cfg.cache_kv_heads, page, be.cache.k.shape[-1])
    pool = A((cfg.n_attn_layers - cfg.n_window_layers, n_pages + 1, *row),
             jnp.bfloat16)
    # a latent cache's v is the placeholder the engine made
    vpool = place(be.cache.v) if cfg.latent else pool
    wpool = wtables = None
    if cfg.n_window_layers:
        # the window pool as the engine sizes it beside `kv_pages`
        be._build_pools(n_pages, nb)
        wpool = A((cfg.n_window_layers, be.wpool.n_pages + 1, *row), jnp.bfloat16)
        wtables = i32(slots, nb)
    cache = PagedKVCache(pool, vpool, i32(slots, nb), place(be.cache.state),
                         wpool, wpool, wtables,
                         place(be.cache.moe_stats) if cfg.n_experts else None)
    rope = place(jax.eval_shape(lambda: build_rope_cache(cfg, seq)))
    vecs = (i32(slots), A((slots,), jnp.bool_), A((slots, 2), jnp.uint32),
            f32(slots), f32(slots))  # pos, active, keys, temps, topp
    dec = lambda n: (params, cache, i32(slots, 1), *vecs, n, rope, i32(slots))
    out = [(f"{tag} paged decode chunk n=4",
            lambda: be._decode.lower(*dec(4)).compile())]
    if spec or cfg.recurrent or cfg.n_window_layers or cfg.q_lora_rank:
        out += [(f"{tag} hybrid step p={p} n=4", lambda p=p: be._hybrid.lower(
            params, cache, i32(1, p), i32(), i32(), i32(slots, 1),
            *vecs, 4, rope, i32(slots)).compile()) for p in hybrid_p]
    out += [(f"{tag} paged prefill chunk m={m}", lambda m=m: be._prefill_slot.lower(
        params, cache, i32(1, m), i32(), i32(), rope).compile())
            for m in ((256, 1) if spec else prefill_m)]
    if spec:
        out += [
            (f"{tag} spec-verify chunk K={spec} m=4", lambda: be._spec_step.lower(
                params, cache, i32(slots, seq + 1), i32(slots), vecs[0],
                vecs[1], i32(slots), *vecs[2:], rope, i32(slots), 4).compile()),
            (f"{tag} paged penalized decode chunk n=4", lambda: be._decode_pen.lower(
                *dec(4), i32(slots, cfg.vocab_size), f32(slots),
                f32(slots)).compile()),
        ]
    return out


def serving_cases(topo):
    """The whole BatchEngine programs `serve --slots 8 --max-seq-len 2048
    --spec-k 4` dispatches at 1b width, through the engine's own jits
    (static args, donation, pool aliasing inside the layer scan): paged
    decode chunk, one hybrid prefill-slice + decode step, prefill chunks,
    the K+1-wide spec-verify chunk, the penalized scan — plus a decode
    chunk of an OLMoE-width sparse-expert block (the grouped expert kernel
    inside a homogeneous layer scan; not on the 1b path)."""
    from dllama_tpu.models.config import LlamaConfig

    A = on_one_chip(topo)
    cfg = _cfg_1b()
    out = engine_programs(topo, "serve 1b", cfg, abstract_params(cfg, A), SLOTS, SPEC_K)
    # OLMoE-1B-7B width (ROADMAP Reach #1): 64 experts top-8, expert hidden
    # 1024, MHA 16/16 hd 128, 64 slots, over a 256-page (32k-token, 4.3 GB)
    # pool: full coverage of 64 x 2048 rows of this MHA cache is 17 GB, more
    # than the chip holds
    mcfg = LlamaConfig(dim=2048, hidden_dim=1024, n_layers=16, n_heads=16,
                       n_kv_heads=16, vocab_size=50304, seq_len=SEQ,
                       n_experts=64, n_active_experts=8)
    return out + engine_programs(topo, "serve olmoe-width 64-slot", mcfg,
                                 abstract_params(mcfg, A), 64, 0, kv_pages=256)


# ---- the served architectures at their published widths: a configuration,
# ---- and what its benchmark cell's `serve` line gives


def hybrid_cfg(n_layers: int = 40):
    """The hybrid state-space / attention stack of
    benchmark/configs/granite-4.0-h-micro.json: 40 layers of period
    `m m m m m a m m m m`, Mamba-2 64 x 64 x 128, GQA 32/8 heads of 64, MLP
    8192, a 100,352-row head."""
    from dllama_tpu.models.config import ArchType, LlamaConfig, RopeType

    period = (1, 1, 1, 1, 1, 0, 1, 1, 1, 1)
    return LlamaConfig(
        dim=2048, hidden_dim=8192, n_layers=n_layers, n_heads=32, n_kv_heads=8,
        vocab_size=100352, seq_len=SEQ, arch=ArchType.HYBRID_SSM,
        rope_type=RopeType.NONE, attn_scale=0.015625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, tied_head=True,
        layer_kinds=period * (n_layers // 10), ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, ssm_conv=4, ssm_chunk=256)


def window_moe_cfg(n_layers: int = 24):
    """The window-and-global, routed-expert stack of
    benchmark/configs/smallthinker-21b-a3b.json, cut to 24 layers as there:
    period `g w w w`, 28/4 heads of 128 over a 2,560 stream, 64 experts of
    width 768 with 6 active, a 151,936-row head."""
    from dllama_tpu.models.config import HiddenAct, LlamaConfig

    return LlamaConfig(
        dim=2560, hidden_dim=768, n_layers=n_layers, n_heads=28, n_kv_heads=4,
        head_dim=128, vocab_size=151936, seq_len=16384, n_experts=64,
        n_active_experts=6, hidden_act=HiddenAct.RELU, rope_theta=1.5e6,
        norm_epsilon=1e-6, window=4096, layer_windows=(0, 1, 1, 1) * (n_layers // 4),
        layer_ropes=(0, 1, 1, 1) * (n_layers // 4), router_pre_attention=True)


#: the published pattern: layer 1 dense, latent attention at 4, 8, ..., 24, 27
DELTA_LATENT_KINDS = tuple(3 if i in (4, 8, 12, 16, 20, 24, 27) else 2
                           for i in range(1, 28))


def delta_latent_cfg(kinds: tuple = DELTA_LATENT_KINDS):
    """The delta-rule / latent-attention stack over sigmoid-routed experts
    of benchmark/configs/kimi-linear-48b-a3b.json: 2,304 stream, 32 KDA
    heads of 128, latent 512 + 64 under 32 heads of 128 + 64 / 128, a dense
    layer of 9,216, then 64 held of 256 experts of 1,024 with 8 active and a
    shared expert, a 40,960-row head."""
    from dllama_tpu.models.config import LlamaConfig, RopeType

    return LlamaConfig(
        dim=2304, hidden_dim=9216, n_layers=len(kinds), n_heads=32,
        n_kv_heads=32, vocab_size=40960, seq_len=8192,
        n_experts=256, n_active_experts=8, rope_type=RopeType.NONE,
        layer_kinds=kinds, kda_heads=32, kda_head_dim=128, kda_conv=4,
        kda_rank=128, kv_lora_rank=512, qk_nope_dim=128, qk_pe_dim=64,
        v_head_dim=128, router_sigmoid=True, routed_scale=2.446,
        n_shared_experts=1, experts_held=64, expert_offset=0,
        moe_hidden_dim=1024, layer_ffn=(1,) + (0,) * (len(kinds) - 1))


def attn_kinds_cfg(n_layers: int = 40):
    """Attention by layer kind over sigmoid-routed experts, the widths and
    depth of benchmark/configs/laguna-xs.2.json: 2,048 stream, 48 global /
    64 windowed query heads over 8 kv heads of 128, a 512-row window, a dense
    layer of 8,192, then 64 held of 256 experts of 512 with 8 active and a
    shared expert, a 25,088-row head."""
    from dllama_tpu.models.config import LlamaConfig, RopeSpec, RopeType

    return LlamaConfig(
        dim=2048, hidden_dim=8192, n_layers=n_layers, n_heads=48, n_kv_heads=8,
        vocab_size=25088, seq_len=4224, head_dim=128,
        norm_epsilon=1e-6, n_experts=256, n_active_experts=8, window=512,
        layer_windows=tuple(int(i % 4 != 0) for i in range(n_layers)),
        window_heads=64, qk_norm=True, attn_gate=True,
        global_rope=RopeSpec(RopeType.YARN, 500000.0, 0.5, 64.0, 4096, 64.0,
                             1.0, 1.415888),
        router_sigmoid=True, routed_scale=2.5, n_shared_experts=1,
        experts_held=64, expert_offset=0, moe_hidden_dim=512,
        layer_ffn=(1,) + (0,) * (n_layers - 1))


def rot_latent_cfg(n_layers: int = 9):
    """Rotated latent attention with a q-side low rank over group-limited
    sigmoid-routed experts, the widths of benchmark/configs/a.x-k1.json:
    7,168 stream, 64 heads over a 512 + 64 latent row, q through 1,536, YaRN
    x32 over the 64 shared dims, a dense layer of 18,432, then 24 held of 192
    experts (8 groups, 4 kept, 8 a token) of width 2,048 and a shared expert,
    a 20,480-row head; 9 layers."""
    from dllama_tpu.models.config import LlamaConfig, RopeSpec, RopeType

    return LlamaConfig(
        dim=7168, hidden_dim=18432, n_layers=n_layers, n_heads=64, n_kv_heads=64,
        vocab_size=20480, seq_len=16384, norm_epsilon=1e-6,
        attn_scale=0.130861, layer_kinds=(3,) * n_layers, kv_lora_rank=512,
        qk_nope_dim=128, qk_pe_dim=64, v_head_dim=128, q_lora_rank=1536,
        global_rope=RopeSpec(RopeType.YARN, 10000.0, 1.0, 32.0, 4096, 32.0,
                             1.0, 1.0),
        n_experts=192, n_active_experts=8, router_sigmoid=True,
        routed_scale=2.5, n_shared_experts=1, experts_held=24, expert_offset=0,
        moe_hidden_dim=2048, n_expert_groups=8, expert_groups_kept=4,
        layer_ffn=(1,) + (0,) * (n_layers - 1))


def retention_cfg(n_layers: int = 10):
    """Power retention in every layer on a Qwen3-14B skeleton, the widths of
    benchmark/configs/brumby-14b-base.json: 5,120 stream, 40 query heads on 8
    states of 129 x 8,256, QK-norm, rope theta 1e6, SwiGLU 17,408, a
    151,936-row head; 10 of 40 layers (the first of four pipeline stages)."""
    from dllama_tpu.models.config import LlamaConfig

    return LlamaConfig(
        dim=5120, hidden_dim=17408, n_layers=n_layers, n_heads=40, n_kv_heads=8,
        vocab_size=151936, seq_len=32768, norm_epsilon=1e-6, rope_theta=1e6,
        head_dim=128, qk_norm=True, layer_kinds=(4,) * n_layers, ret_degree=2,
        ret_gate=True)


# experiments/warm_compile.py asks for each configuration's parameters by name
hybrid_params = window_moe_params = delta_latent_params = abstract_params
attn_kinds_params = rot_latent_params = retention_params = abstract_params


@dataclasses.dataclass(frozen=True)
class Family:
    """A configuration and its cell's `serve --slots --kv-pages
    [--max-prefill-chunk]`: `slice` is the prefill rows a hybrid launch
    carries there."""
    cfg: callable
    slots: int
    pages: int
    slice: int = 64
    chunk: int = 256
    more_slices: tuple = ()  # further hybrid slices to compile
    prefill: tuple = ()  # slot-prefill chunks to compile


FAMILIES = {
    "hybrid-ssm": Family(hybrid_cfg, 48, 456),
    "window-moe": Family(window_moe_cfg, 16, 1232, 512, 512),
    "delta-latent": Family(delta_latent_cfg, 48, 456),
    "attn-kinds": Family(attn_kinds_cfg, 24, 816, 256, 256),
    "rot-latent": Family(rot_latent_cfg, 32, 2368, 512, 512),
    # a page costs nothing where no layer holds rows: full coverage
    "retention": Family(retention_cfg, 24, 0, 64, 256, (16,), (256,)),
}


def family_cases(topo, name, slots=None, pages=None, **depth):
    """The decode chunk and the hybrid step of FAMILIES[name] at its
    published widths, named `serve <name> <slots>-slot ...`. `slots`,
    `pages` and `depth` (the configuration's own depth argument) cut it to
    what a test compiles in seconds: building the engine allocates the
    slots' state and the window pool on the host (3.7 GB for hybrid-ssm's
    48 slots), which is why these stay out of all_cases()."""
    fam = FAMILIES[name]
    cfg = fam.cfg(**depth)
    slots = slots or fam.slots
    return engine_programs(
        topo, f"serve {name} {slots}-slot", cfg, abstract_params(cfg, on_one_chip(topo)),
        slots, 0, kv_pages=pages or fam.pages,
        hybrid_p=(fam.slice, *fam.more_slices), seq=cfg.seq_len,
        prefill_chunk=fam.chunk, prefill_m=fam.prefill)


def all_cases(topo):
    """Every case of CASES as (name, thunk): thunk() compiles for the
    described chip and raises what the chip's compiler would raise."""
    A = on_one_chip(topo)

    def thunk(fn, args):
        return lambda: jax.jit(fn).trace(*args).lower().compile()

    out = [(name, thunk(fn, tuple(A(a.shape, a.dtype) for a in args)))
           for name, fn, args in cases()]
    out += [(name, thunk(fn, args))
            for name, fn, args in sharded_cases(topo) + full_step_case(topo)]
    return out + serving_cases(topo)

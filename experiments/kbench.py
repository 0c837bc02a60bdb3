"""Microbench harness for Q40 matmul kernel variants on the real TPU.

Usage: python experiments/kbench.py suite
       python experiments/kbench.py paged
       python experiments/kbench.py q40 [--no-tiles]
       python experiments/kbench.py deq [--no-tiles] [--parent]
       python experiments/kbench.py expert [--no-tiles]
       python experiments/kbench.py moe_layer [CELL ...] [--no-profile | --aot]
       python experiments/kbench.py sampler
       python experiments/kbench.py M SHAPE [variant ...]
'suite' benches the decode variants (m=8 on w1/wcls) and the prefill tier
comparison (m=256/512: in-kernel deq vs XLA dequant-dot) in one process.
'paged' times the paged flash-decode kernel alone, as a decode step of each
benchmark cell calls it (300 calls on the layer-stacked pool, pools threaded),
against what the HBM would take for the rows it needs.
'q40' times the block-dot Q40 kernel (m <= 16) alone at each cell's real
shapes at its 16 rows, 300 calls in one scan with the layer cycling: parity,
the kernel as it is and with each part taken out, the (tk, tn) tile sweep
(--no-tiles leaves it out) and the inner loops' (lanes, rows a pass) sweep.
'deq' times the dequantising Q40 tier (m > 16) alone at Granite's shapes at
48 and 64 rows and a prefill slice: parity, the kernel as it is and with each
part taken out, the block-dot kernel at the same rows, the (tk, tn, rows a
pass) sweep; --parent adds PR 36's byte-wise body whole, in parts and over its
tiles (the yardstick PR 37 rebuilt the tier against).
'expert' times the grouped Q40 expert kernel (`_expert_call`) alone at the two
expert cells' shapes and fills (SmallThinker's decode step and 512-row slice,
Kimi-Linear's held share of a decode step): parity against dequantise-then-dot
of each tile's expert, the kernel as it is and with each part taken out, every
tile live against the cell's dead ones, the (tn, lanes) sweep (--no-tiles
leaves it out), other tile heights for the slice.
'moe_layer' times ONE whole grouped expert layer-step (`ops/layers.moe_ffn`:
router logits in, [N, D] out) at the three expert cells' decode and slice
shapes: PR 42's route (its `expert_groups` and `h[src]`, kept here as the
yardstick) against today's, the three kernel calls alone (so: what the XLA
ops around them cost), and each route's device ops from a profile of the
same scan (--no-profile leaves it out). 'moe_layer --aot' needs no chip: it compiles the
layer-step for `v5e:2x2` and lists the entry computation's scheduled ops by
kind and result shape, with the seconds tracing and lowering took.
'sampler' times the sampler that closes every decode step
(`engine/sampling.sample_logits`, per-row keys) ALONE at four cells' slots x
vocabulary: PR 51's one straight-line body mapped over rows (kept here as the
yardstick) against today's conditional bodies on a greedy, a temperature and a
nucleus batch, and on a greedy batch with one nucleus row; what a sampled
batch pays for the conditional shows as nucleus against PR 51.
'suite --smoke' (and 'sampler --smoke', 'paged --smoke', 'q40 --smoke', 'deq --smoke', 'expert --smoke',
'moe_layer --smoke') runs the
same code path on CPU (interpret-mode Pallas, tiny shapes, 2 iters) so CI proves the harness cannot crash on the chip; smoke
numbers are meaningless, only completion matters.
  variants: A  production dispatch (q40_matmul: blockdot for m<=16, deq above)
            DQ the dequantising tier's call BD the block-dot tier's call
            B  legacy fma-f32 kernel        D  bf16-weights roofline reference
            E  XLA dequantize-then-dot
Measures achieved HBM GB/s (packed+scales bytes) on 1B-preset shapes.
"""
import contextlib
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.quant import Q_BLOCK, QTensor
from dllama_tpu.ops.pallas import q40_matmul as qmod
from dllama_tpu.ops.pallas.tiling import pick_tile as _pick_tile

# --smoke flips these: interpret-mode Pallas, 2 timing iters (see docstring)
INTERPRET = False
ITERS = 30


# ---------------------------------------------------------------- variant B
# u8 unpack kept narrow, dequant via fma (w = f*s - 8s), f32 dot (no bf16 cast)
def _kernel_b(x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, tk, tn):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    p = packed_ref[:].astype(jnp.int32)  # [tk/2, tn]
    lo = (p & 0x0F)
    hi = (p >> 4)
    codes = jnp.concatenate(
        [lo.reshape(tk // Q_BLOCK, Q_BLOCK // 2, tn), hi.reshape(tk // Q_BLOCK, Q_BLOCK // 2, tn)],
        axis=1,
    )  # i32 [tk/32, 32, tn]
    s = scales_ref[:].astype(jnp.float32)[:, None, :]
    f = codes.astype(jnp.float32)
    w = (f * s - 8.0 * s).reshape(tk, tn)
    acc_ref[:] += jnp.dot(x_ref[:].astype(jnp.float32), w, preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        out_ref[:] = acc_ref[:]


# ---------------------------------------------------------------- variant D
# bf16 weights materialized (roofline reference for unquantized): plain dot
def _kernel_d(x_ref, w_ref, out_ref, acc_ref, *, tk, tn):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def make_call(kernel, m, k, n, *, tiles=None, bf16=False):
    tm = _pick_tile(m, (256, 128, 64, 32, 16, 8))
    tn, tk = tiles or (_pick_tile(n, (512, 256, 128)), _pick_tile(k, (512, 256, 128, 64, 32)))
    grid = (m // tm, n // tn, k // tk)
    if bf16:
        in_specs = [
            pl.BlockSpec((tm, tk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((tk, tn), lambda i, j, kb: (kb, j)),
        ]
    else:
        in_specs = [
            pl.BlockSpec((tm, tk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((tk // 2, tn), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((tk // Q_BLOCK, tn), lambda i, j, kb: (kb, j)),
        ]
    return pl.pallas_call(
        functools.partial(kernel, tk=tk, tn=tn),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=INTERPRET,
    )


def bench(fn, args, iters=None):
    """Each iteration gets a DISTINCT x buffer (no layer may answer an
    identical (executable, args) pair from a cache); dispatch is async with
    a single block at the end."""
    iters = iters or ITERS
    x, *rest = args
    jfn = jax.jit(fn)
    xs = [x + jnp.float32(i).astype(x.dtype) for i in range(iters)]
    jax.block_until_ready(xs)
    out = jfn(xs[0], *rest)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    outs = [jfn(xi, *rest) for xi in xs]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / iters


SHAPES = {
    "wq": (2048, 2048),
    "w1": (2048, 8192),
    "w2": (8192, 2048),
    "wcls": (2048, 128256),
}


def make_inputs(m, label):
    """Shared test data for run_one and the tile sweep — ONE definition so the
    sweep always benchmarks the same (w, x, qbytes) as the variant rows."""
    k, n = SHAPES[label]
    rng = np.random.default_rng(0)
    w = QTensor.quantize((rng.standard_normal((k, n)) * 0.02).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    qbytes = k * n // 2 + (k // Q_BLOCK) * n * 2  # packed + f16 scales
    return w, x, qbytes


def dispatch_closure(w, style):
    """`q40_matmul` as it serves ('auto') or one tier's jitted call on the
    same operands ('deq' / 'blockdot', rows padded to its tile); a FRESH
    closure per row so each traces for itself."""
    if style == "auto":
        return lambda x, w=w: qmod.q40_matmul(x, w, interpret=INTERPRET)
    call = {"deq": qmod._deq_call, "blockdot": qmod._blockdot_call}[style]
    scales = jax.lax.bitcast_convert_type(w.scales, jnp.uint16)[None]

    def tier(x, packed=w.packed[None], scales=scales):
        x = jnp.pad(x, ((0, -x.shape[0] % 16), (0, 0)))
        return call(jnp.zeros((1,), jnp.int32), x, packed, scales, interpret=INTERPRET)

    return tier


def run_one(m, label, variants):
    k, n = SHAPES[label]
    w, x, qbytes = make_inputs(m, label)
    rows = []
    for v in variants:
        # per-variant isolation: one Mosaic rejection must not eat the
        # row's other timings in a one-shot TPU window
        try:
            if v in ("A", "DQ", "BD"):
                style = {"A": "auto", "DQ": "deq", "BD": "blockdot"}[v]
                t = bench(dispatch_closure(w, style), (x,))
                rows.append((f"{v} {style}", t, qbytes))
            elif v == "B":
                call = make_call(_kernel_b, m, k, n)
                # legacy f32-scales kernel: feed widened scales (QTensor is f16 now)
                t = bench(call, (x, w.packed, w.scales.astype(jnp.float32)))
                rows.append(("B fma-f32", t, qbytes + (k // Q_BLOCK) * n * 2))  # f32 scales
            elif v == "D":
                wb = w.dequantize(jnp.bfloat16)
                call = make_call(_kernel_d, m, k, n, bf16=True)
                t = bench(call, (x, wb))
                rows.append(("D bf16-ref", t, k * n * 2))
            elif v == "E":
                t = bench(
                    lambda x, w: jnp.dot(x, w.dequantize(jnp.bfloat16), preferred_element_type=jnp.float32),
                    (x, w),
                )
                rows.append(("E xla-deq", t, qbytes))
            elif v == "Q8":
                # fused Q80 path (Q8Tensor): int8 codes + f16 scales,
                # 1.0625 B/weight streamed — same dispatch split as q40
                from dllama_tpu.ops.pallas.q80_matmul import q80_matmul
                from dllama_tpu.ops.quant import Q8Tensor

                rng8 = np.random.default_rng(0)
                w8 = Q8Tensor.quantize(
                    (rng8.standard_normal((k, n)) * 0.02).astype(np.float32))
                q8bytes = k * n + (k // Q_BLOCK) * n * 2
                t = bench(lambda x, w8=w8: q80_matmul(x, w8, interpret=INTERPRET), (x,))
                rows.append(("Q8 q80-fused", t, q8bytes))
            else:
                raise SystemExit(f"unknown variant {v!r}; see module docstring")
        except SystemExit:
            raise
        except Exception as e:
            print(f"m={m} {label} {v}: FAILED {e!r}"[:250])
            sys.stdout.flush()
    out = f"m={m} {label}: "
    for name, t, nb in rows:
        out += f"{name}={t*1e6:.0f}us({nb/t/1e9:.0f}GB/s) "
    print(out)
    sys.stdout.flush()


SUITE = [
    # decode shapes: the production dispatch + each forced style + rooflines
    # (+ Q8: the fused Q80-weight path at the same shape)
    (8, "w1", ["A", "BD", "DQ", "D", "E", "Q8"]),
    (8, "wcls", ["A", "D", "E"]),  # the lm head is ~18% of 1B weight bytes
    # prefill shapes: in-kernel deq vs the XLA dequant-dot the MXU loves
    (256, "w1", ["DQ", "D", "E", "Q8"]),
    (512, "w1", ["DQ", "D", "E"]),
]


def enable_smoke():
    """Same code path, CPU-sized: every SUITE row and every mode run in
    interpret mode on shapes small enough for CI (seconds, not windows)."""
    global INTERPRET, ITERS, SHAPES, SUITE
    INTERPRET = True
    ITERS = 2
    SHAPES = {
        "wq": (128, 128),
        "w1": (256, 256),  # the block-dot tier walks k by 256
        "w2": (256, 128),
        "wcls": (128, 512),
    }
    SUITE = [
        (8, "w1", ["A", "BD", "DQ", "B", "D", "E", "Q8"]),
        (8, "wcls", ["A", "D", "E"]),
        (32, "w1", ["DQ", "D", "E", "Q8"]),
    ]
    global PAGED_CELLS, PAGED_CALLS, Q40_CELLS, Q40_CALLS
    global Q40_SWEEP_TK, Q40_SWEEP_TN, Q40_SWEEP_LANES, Q40_SWEEP_ROWS
    global DEQ_SHAPES, DEQ_SWEEP_TK, DEQ_SWEEP_TN, DEQ_SWEEP_BYTES, DEQ_PARENT_TK, DEQ_PARENT_TN
    DEQ_SHAPES = {"tiny stacked": (512, 384, 2, (48,)), "tiny head": (256, 256, 1, (24,))}
    DEQ_SWEEP_TK, DEQ_SWEEP_TN, DEQ_SWEEP_BYTES = (256, None), (128, -1), (0, 1 << 30)
    DEQ_PARENT_TK, DEQ_PARENT_TN = (256, None), (128,)
    Q40_CALLS = 2
    Q40_CELLS = {"tiny": {"stacked": (8192, 256, 2), "head": (256, 384, 1)}}
    Q40_SWEEP_TK, Q40_SWEEP_TN, Q40_SWEEP_LANES = (4096, None), (128, -1), (128,)
    Q40_SWEEP_ROWS = (256, None)
    global EXPERT_CELLS, EXPERT_CALLS, EXPERT_SWEEP_LANES
    EXPERT_CALLS, EXPERT_SWEEP_LANES = 2, (128, -1)
    EXPERT_CELLS = {
        "tiny": dict(held=4, routed=4, active=2, layers=2, shapes=((512, 256), (256, 512)),
                     fills={"decode": (3, (16,)), "slice": (40, (64, 32))}),
        "tiny share": dict(held=4, routed=8, active=3, layers=2, shapes=((256, 256),),
                           fills={"decode": (6, (16,))})}
    global MOE_LAYER_CELLS, MOE_LAYER_CALLS
    MOE_LAYER_CALLS = 2
    MOE_LAYER_CELLS = {
        "tiny": dict(held=4, routed=4, active=2, d=256, f=256, sigmoid=False, scale=1.0,
                     act="relu", layers=2, rows={"decode": 3, "slice": 160}),
        "tiny share": dict(held=4, routed=8, active=3, d=256, f=256, sigmoid=True, scale=2.5,
                           act="silu", layers=2, rows={"decode": 6})}
    global SAMPLER_CELLS, SAMPLER_CALLS
    SAMPLER_CELLS, SAMPLER_CALLS = {"tiny": (3, 512)}, 2
    global PAGED_LATENT_CELLS, PAGED_LATENT_PP, PAGED_SLICE_CALLS
    PAGED_LATENT_PP, PAGED_SLICE_CALLS = (2,), 2
    PAGED_LATENT_CELLS = {
        "tiny latent": dict(slots=3, hq=8, rank=64, pe=32, page=8, layers=2,
                            kv_pages=16, rows=(3, 70), slice=(24, 20))}
    PAGED_CALLS = 2
    PAGED_CELLS = {
        "tiny mha": dict(slots=2, hq=4, hkv=4, hd=64, page=16, layers=2,
                         kv_pages=8, rows=(10, 40)),
        "tiny gqa": dict(slots=3, hq=8, hkv=2, hd=64, page=8, layers=2,
                         kv_pages=16, rows=(3, 30)),
        "tiny window": dict(slots=2, hq=8, hkv=2, hd=128, page=64, layers=2,
                            kv_pages=8, rows=(70, 200), window=80),
    }
    global PAGED_SUBS
    PAGED_SUBS = (16, 0)


def bench_flash_decode():
    """Flash decode-shape A/Bs (VERDICT r3 weak #3/#4):

    1. pad-row cost: t=1 decode at group=4 (4 live rows padded to the tq=8
       sublane tile) vs group=8 with the SAME hkv (8 live rows, zero pad) —
       identical KV bytes streamed, identical grid, only live-row count
       differs. time(group=4) ~= time(group=8) proves the kernel is
       KV-DMA-bound: pad rows are free, doubling live rows is free, and a
       fold-2-kv-heads layout rework would buy nothing (it cannot reduce KV
       bytes). time(group=4) << time(group=8) means rows cost compute and a
       fold layout halving program count is worth building.
    2. pruning vs static grid: decode ms at S=8192 for pos 64 -> 7936. Time
       must scale ~linearly with the LIVE cache (pruned DMA+compute); a flat
       curve means the ~S/ts no-op grid steps dominate and the grid needs a
       dynamic bound.
    """
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

    rng = np.random.default_rng(0)
    hd = 64 if INTERPRET else 128
    s_ab = 512 if INTERPRET else 1024
    for hq, hkv, kvdt, label in (
        (32, 8, jnp.bfloat16, "group=4 (4 live rows, 4 pad)"),
        (64, 8, jnp.bfloat16, "group=8 (8 live rows, 0 pad)"),
        # 3. f8 KV cache (--cache-dtype f8): same shapes as row 1 at HALF the
        #    cache bytes — if decode is cache-DMA-bound this should approach
        #    2x row 1's time-per-byte advantage
        (32, 8, jnp.float8_e4m3fn, "group=4 f8 KV cache"),
    ):
        q = jnp.asarray(rng.standard_normal((1, 1, hq, hd)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, hkv, s_ab, hd)), kvdt)
        v = jnp.asarray(rng.standard_normal((1, hkv, s_ab, hd)), kvdt)
        fn = lambda q, k, v: flash_gqa_attention(q, k, v, jnp.int32(s_ab - 2),
                                                 interpret=INTERPRET)
        try:
            t = bench(fn, (q, k, v))
            kv_bytes = 2 * hkv * s_ab * hd * jnp.dtype(kvdt).itemsize
            print(f"flash decode {label}: {t*1e6:.0f}us ({kv_bytes/t/1e9:.0f}GB/s cache)")
        except Exception as e:
            print(f"flash decode {label}: FAILED {e!r}"[:250])
        sys.stdout.flush()

    s_long = 1024 if INTERPRET else 8192
    k = jnp.asarray(rng.standard_normal((1, 8, s_long, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 8, s_long, hd)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((1, 1, 32, hd)), jnp.bfloat16)
    fn = lambda q, k, v, p: flash_gqa_attention(q, k, v, p, interpret=INTERPRET)
    rows = []
    for frac in (1 / 128, 1 / 8, 1 / 2, 63 / 64):
        pos = max(1, int(s_long * frac))
        try:
            t = bench(fn, (q, k, v, jnp.int32(pos)))
            rows.append((pos, t))
            print(f"flash decode S={s_long} pos={pos}: {t*1e6:.0f}us")
        except Exception as e:
            print(f"flash decode S={s_long} pos={pos}: FAILED {e!r}"[:250])
        sys.stdout.flush()
    if len(rows) >= 2:
        # live-cache scaling ratio vs grid-overhead floor
        (p0, t0), (p1, t1) = rows[0], rows[-1]
        print(f"pruning scaling: pos x{p1/p0:.0f} -> time x{t1/t0:.1f} "
              f"(~linear = pruning works; ~flat = static-grid overhead dominates)")
    sys.stdout.flush()

    # same depth sweep on the bucketed grid (DLLAMA_FLASH_BUCKETS): the
    # lax.switch dispatches to a pow-2 cache view, so shallow positions walk
    # a short grid instead of S/ts no-op steps. bucketed << static at small
    # pos (and ~equal at pos ~= S) => flip the engine default
    fnb = lambda q, k, v, p: flash_gqa_attention(q, k, v, p, interpret=INTERPRET,
                                                 s_buckets=True)
    for frac in (1 / 128, 1 / 8, 1 / 2, 63 / 64):
        pos = max(1, int(s_long * frac))
        try:
            t = bench(fnb, (q, k, v, jnp.int32(pos)))
            print(f"flash decode BUCKETED S={s_long} pos={pos}: {t*1e6:.0f}us")
        except Exception as e:
            print(f"flash decode BUCKETED S={s_long} pos={pos}: FAILED {e!r}"[:250])
        sys.stdout.flush()

    # prefill-chunk-at-shallow-depth A/B: an early chunk of a long chunked
    # prefill (pos=256, t=256) sees <= 512 live slots but statically walks
    # all of S — bucketing rides the 512 view instead
    tq_pf = 64 if INTERPRET else 256
    qp = jnp.asarray(rng.standard_normal((1, tq_pf, 32, hd)), jnp.bfloat16)
    for name, f in (("static", fn), ("BUCKETED", fnb)):
        try:
            t = bench(f, (qp, k, v, jnp.int32(tq_pf)))
            print(f"flash prefill t={tq_pf} {name} S={s_long} pos={tq_pf}: {t*1e6:.0f}us")
        except Exception as e:
            print(f"flash prefill {name}: FAILED {e!r}"[:250])
        sys.stdout.flush()


#: The decode-shaped paged-attention call of each benchmark cell (PERF.md
#: section 4): slots, heads, the pool as the engine allocates it, and the
#: span of rows the slots stand at in a window; `window`: a windowed
#: layer's call (`_paged_window`).
PAGED_CELLS = {
    "deepseek7b.decode_closed": dict(
        slots=12, hq=32, hkv=32, hd=128, page=128, layers=30, kv_pages=66,
        rows=(150, 420)),
    "granite4h.reason_closed": dict(
        slots=48, hq=32, hkv=8, hd=64, page=128, layers=4, kv_pages=456,
        rows=(100, 1000)),
    "lagunaxs2.reason_long_closed window": dict(
        slots=24, hq=64, hkv=8, hd=128, page=128, layers=30, kv_pages=168,
        rows=(512, 4096), window=512),
    "lagunaxs2.reason_long_closed global": dict(
        slots=24, hq=48, hkv=8, hd=128, page=128, layers=10, kv_pages=816,
        rows=(512, 4096)),
    "smallthinker.long_decode_closed window": dict(
        slots=16, hq=28, hkv=4, hd=128, page=128, layers=18, kv_pages=592,
        rows=(5120, 9700), window=4096),
    "smallthinker.long_decode_closed global": dict(
        slots=16, hq=28, hkv=4, hd=128, page=128, layers=6, kv_pages=1232,
        rows=(5120, 9700)),
}
PAGED_CALLS = 300
PAGED_SUBS = (16, 32, 64, 0)  # rows a copy of an end page (0: the page, PR 52's kernel)
HBM_GBS, MXU_TFLOPS = 819.0, 197.0  # TPU v5e (benchmark/peaks.json)


def bench_paged_decode(cells=None, calls=None, rows=None, subs=()):
    """The paged flash-decode kernel alone on the chip, as a decode step
    calls it: `paged_decode_attention` with t = 1 on the layer-stacked pool,
    the layer cycling, the pools threaded through PAGED_CALLS calls of one
    jitted scan. Prints ms a call beside what the HBM would take for the
    rows the call NEEDS (`benchmark/costs/paged_attention.py`'s bytes), for
    the whole pages it touches and for the bytes its copies MOVE (in and
    back: `pa.rows_moved`; a tree without it moved the pages touched and a
    page's head block back), with the new row's scatter fused into the
    kernel and without it (the same kernel over pools nobody writes).
    `subs`: the fused call again at each size of an end page's copies
    (`pa._END_COPY_ROWS`; 0 = the whole page), whatever the head block's
    bytes (`pa._END_MIN_PAGE_BYTES` = 0)."""
    from dllama_tpu.ops.pallas import paged_attention as pa

    calls = calls or PAGED_CALLS
    for name, c in (cells or PAGED_CELLS).items():
        b, hq, hkv, hd, page = c["slots"], c["hq"], c["hkv"], c["hd"], c["page"]
        window = c.get("window")
        lo, hi = rows or c["rows"]
        lanes = pa.pool_lanes(hd)
        nb = -(-(hi + 1) // page)
        # a windowed layer's table holds the window's pages alone
        held = nb if window is None else min(nb, -(-window // page) + 1)
        n_pool = max(c["kv_pages"], b * held) + 1  # + the trash page
        rng = np.random.default_rng(0)
        pool = lambda: jnp.asarray(
            rng.standard_normal((1, n_pool, hkv, page, lanes), np.float32),
            jnp.bfloat16) * jnp.ones((c["layers"], 1, 1, 1, 1), jnp.bfloat16)
        kp, vp = pool(), pool()
        pos_h = np.linspace(lo, hi, b).astype(np.int32)
        pos = jnp.asarray(pos_h)
        # distinct pages a slot, shuffled: the physical order must not help
        pages = rng.permutation(n_pool - 1)[: b * held].reshape(b, held)
        tables = np.zeros((b, nb), np.int32)
        for i, p in enumerate(pos_h):  # the blocks the walk reads
            first = 0 if window is None else max(p - window + 1, 0) // page
            blocks = np.arange(first, p // page + 1)
            tables[i, blocks] = pages[i, : len(blocks)]
        tables = jnp.asarray(tables)
        q = jnp.asarray(rng.standard_normal((b, 1, hq, hd)), jnp.bfloat16)
        nk = jnp.asarray(rng.standard_normal((b, hkv, 1, hd)), jnp.bfloat16)
        nv = jnp.asarray(rng.standard_normal((b, hkv, 1, hd)), jnp.bfloat16)
        group = hq // hkv

        def fused(q, kp, vp, li):
            return pa.paged_decode_attention(
                q, kp, vp, tables, pos, nk, nv, None, layer=li,
                interpret=INTERPRET, window=window)

        def read_only(q, kp, vp, li):
            # the wrapper's fold, then the kernel with fused=False: the
            # public read-only call drops the aliased pools (PERF.md
            # section 7), so it cannot be threaded through a loop
            pad = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, lanes - hd),))
            qf = pad(q).reshape(b, 1, hkv, group, lanes).transpose(
                0, 2, 1, 3, 4).reshape(b, hkv, group, lanes)
            qf = jnp.pad(qf, ((0, 0), (0, 0), (0, (-group) % 8), (0, 0)))
            zero = jnp.zeros((b, 1), jnp.int32)
            row = jnp.zeros((b, hkv, 1, lanes), kp.dtype)
            call = pa._paged_folded if window is None else functools.partial(
                pa._paged_window, window=window)
            out, k2, v2 = call(
                qf, kp.reshape(-1, *kp.shape[2:]), vp.reshape(-1, *vp.shape[2:]),
                pos, tables + li * n_pool, zero, zero, row, row, group=group,
                interpret=INTERPRET, rows_live=group, fused=False,
                scale=hd ** -0.5)
            return out, k2.reshape(kp.shape), v2.reshape(vp.shape)

        row_bytes = 2 * hkv * lanes * 2  # k and v, every head, bf16
        seen = pos_h + 1 if window is None else np.minimum(pos_h + 1, window)
        first = 0 if window is None else np.maximum(pos_h - window + 1, 0) // page
        touched_rows = (pos_h // page + 1 - first) * page
        needed = float(seen.sum()) * 2 * hkv * hd * 2 + b * hq * hd * (2 + 4)
        touched = float(touched_rows.sum()) * row_bytes

        def moved_bytes(is_fused):
            if not hasattr(pa, "rows_moved"):  # whole pages, a page back
                return float((touched_rows + (page if is_fused else 0)).sum()) * row_bytes
            win, sub = pa.decode_tiles(hq, hkv, page, lanes, 2)
            return float((pa.rows_moved(pos_h, page, nb, win, sub, window)
                          - (0 if is_fused else win)).sum()) * row_bytes

        def timed(label, call, is_fused):
            @jax.jit
            def loop(q, kp, vp):
                def step(carry, i):
                    kp, vp, acc = carry
                    out, kp, vp = call(q, kp, vp, i % c["layers"])
                    return (kp, vp, acc + out.astype(jnp.float32).sum()), None
                return jax.lax.scan(
                    step, (kp, vp, jnp.float32(0)),
                    jnp.arange(calls, dtype=jnp.int32))[0]

            nonlocal kp, vp
            try:
                kp, vp, acc = loop(q, kp, vp)  # compiles; pools stay threaded
                jax.block_until_ready(acc)
                t0 = time.perf_counter()
                kp, vp, acc = loop(q, kp, vp)
                jax.block_until_ready(acc)
                ms = (time.perf_counter() - t0) / calls * 1e3
                share = lambda nbytes: (
                    f"{nbytes / 1e6:.1f} MB = {nbytes / HBM_GBS / 1e3:.1f} us "
                    f"({nbytes / HBM_GBS / 1e4 / ms:.1f}% of the call)")
                print(f"paged decode {name} {label}: {ms:.4f} ms a call over "
                      f"{calls} calls; slots {b} x {hkv} kv heads x {hd}, rows "
                      f"{lo}-{hi}{f', window {window}' if window else ''}; "
                      f"needed {share(needed)} at {HBM_GBS:.0f} GB/s; pages "
                      f"touched {share(touched)}; moved "
                      f"{share(moved_bytes(is_fused))}")
            except Exception as e:
                print(f"paged decode {name} {label}: FAILED {e!r}"[:300])
            sys.stdout.flush()

        timed("fused scatter", fused, True)
        timed("read-only", read_only, False)
        keep = (getattr(pa, "_END_COPY_ROWS", None),
                getattr(pa, "_END_MIN_PAGE_BYTES", None))
        for sub in subs if keep[0] else ():
            # (whatever the head block's bytes: the sweep prices that gate too)
            pa._END_COPY_ROWS, pa._END_MIN_PAGE_BYTES = sub or page, 0
            timed(f"fused scatter, end copies of {sub or page} rows", fused, True)
        if keep[0]:
            pa._END_COPY_ROWS, pa._END_MIN_PAGE_BYTES = keep


#: The latent cells' sweep (one pool, Hkv = 1, the row is key and value):
#: each cell's decode call and A.X-K1's hybrid slice (rows, at context).
PAGED_LATENT_CELLS = {
    "kimilinear.reason_closed": dict(
        slots=48, hq=32, rank=512, pe=64, page=128, layers=7, kv_pages=456,
        rows=(100, 1100)),
    "axk1.long_reason_closed": dict(
        slots=32, hq=64, rank=512, pe=64, page=128, layers=9, kv_pages=2368,
        rows=(3000, 9200), slice=(512, 2300)),
}
PAGED_LATENT_PP = (2, 3, 4, 6, 8)  # pages a pass, each on a ring of two passes
PAGED_SLICE_CALLS = 40


def bench_paged_latent(cells=None, calls=None, slice_calls=None, sweep=None):
    """`_paged_latent` alone, as a decode step calls it (t = 1, fused
    scatter, the layer cycling over the stacked pool, the pool threaded
    through one jitted scan) and as a hybrid launch's slice does: ms a call
    beside the rows' bytes (`benchmark/costs/paged_attention_latent.py`),
    parity against float64, and the pass fill share (live pages over
    pp x passes). Rows: the plan as `_plan` gives it, the parent's body (a
    page a pass, ring of 4: `_plan`'s answer for any call that is not a
    latent one) and the pages-a-pass sweep. What each part of the page-a-pass
    body costs is in PERF.md section 7 (PR 45's ablations), not here."""
    from dllama_tpu.ops.pallas import paged_attention as pa

    real_plan = pa._plan

    @contextlib.contextmanager
    def planned(pp):
        """`_plan` answering `pp` pages a pass for the rows traced inside
        (None: as it is; 1: the page-a-pass plan of every other call)."""
        def plan(*a):
            if pp == 1:
                return real_plan(*a[:7])
            hb, depth, _, nbytes = real_plan(*a)
            return hb, depth, pp, nbytes
        try:
            if pp is not None:
                pa._plan = plan
            pa._paged_latent.clear_cache()
            yield
        finally:
            pa._plan = real_plan
            pa._paged_latent.clear_cache()

    for name, c in (cells or PAGED_LATENT_CELLS).items():
        b, hq, rank, page = c["slots"], c["hq"], c["rank"], c["page"]
        w = rank + c["pe"]
        lanes, scale = pa.pool_lanes(w), w ** -0.5
        lo, hi = c["rows"]
        nb = -(-(hi + 1) // page)
        n_pool = max(c["kv_pages"], b * nb) + 1  # + the trash page
        rng = np.random.default_rng(0)
        one = rng.standard_normal((n_pool, page, lanes), np.float32)
        pool0 = jnp.asarray(one[None, :, None], jnp.bfloat16) * jnp.ones(
            (c["layers"], 1, 1, 1, 1), jnp.bfloat16)
        placeholder = jnp.zeros((c["layers"], 1, 1, 8, 128), jnp.bfloat16)
        tables = rng.permutation(n_pool - 1)[: b * nb].reshape(b, nb)
        pos = np.linspace(lo, hi, b).astype(np.int32)
        q = jnp.asarray(rng.standard_normal((b, 1, hq, w)), jnp.bfloat16)
        new = jnp.asarray(rng.standard_normal((b, 1, 1, w)), jnp.bfloat16)
        plan = real_plan(1, page, lanes, 2, pa._q_tile(-(-hq // 8) * 8), 1,
                         pa._VMEM_BUDGET_BYTES, True)
        print(f"paged latent {name}: slots {b} x {hq} q heads x {w} "
              f"({lanes} lanes), rows {lo}-{hi}; _plan: {plan[2]} pages a "
              f"pass, ring of {plan[1]} passes, {plan[3]:,} B of VMEM")

        def sweep_of(q, pool, tables, pos, new, li):
            return pa.paged_decode_attention(
                q, pool, placeholder, tables, pos, new, None, None, layer=li,
                interpret=INTERPRET, latent=rank, scale=scale)

        def float64(q, pos, new, tables):
            """The float64 form of layer 0's call: [b, t, hq, rank]."""
            f64 = lambda x: np.asarray(x.astype(jnp.float32), np.float64)
            q, new = f64(q), f64(new)
            rows64 = f64(jnp.asarray(one, jnp.bfloat16))
            out = np.zeros(q.shape[:3] + (rank,))
            for bi in range(q.shape[0]):
                t = q.shape[1]
                n = int(pos[bi]) + t
                rows = rows64[tables[bi]].reshape(-1, lanes)[:n, :w].copy()
                rows[n - t:] = new[bi, 0]
                sc = np.einsum("thw,sw->ths", q[bi], rows) * scale
                keep = np.arange(n)[None, :] <= int(pos[bi]) + np.arange(t)[:, None]
                sc = np.where(keep[:, None], sc, -np.inf)
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                out[bi] = np.einsum("ths,sr->thr", pr / pr.sum(-1, keepdims=True),
                                    rows[:, :rank])
            return out

        def timed(label, forced, n_calls, q, tables, pos, new, needed, want):
            pp = forced or plan[2]
            live = -(-(np.asarray(pos) + 1) // page)  # pages a decode step sweeps
            fill = "" if q.shape[1] > 1 else (
                f"; pass fill {live.sum() / (pp * (-(-live // pp)).sum()):.1%}")
            tables_d, pos_d = jnp.asarray(tables, jnp.int32), jnp.asarray(pos)

            @jax.jit
            def loop(q, pool):
                def step(carry, i):
                    pool, acc = carry
                    out, pool, _ = sweep_of(q, pool, tables_d, pos_d, new,
                                            i % c["layers"])
                    return (pool, acc + out.astype(jnp.float32).sum()), None
                return jax.lax.scan(step, (pool, jnp.float32(0)),
                                    jnp.arange(n_calls, dtype=jnp.int32))[0]

            try:
                with planned(forced):
                    got = sweep_of(q.astype(jnp.float32), pool0, tables_d,
                                   pos_d, new, jnp.int32(0))[0]
                    err = np.abs(np.asarray(got, np.float64) - want).max()
                    pool, acc = loop(q, pool0)  # compiles; the pool stays threaded
                    jax.block_until_ready(acc)
                    t0 = time.perf_counter()
                    pool, acc = loop(q, pool)
                    jax.block_until_ready(acc)
                ms = (time.perf_counter() - t0) / n_calls * 1e3
                print(f"paged latent {name} {label}: {ms:.4f} ms a call over "
                      f"{n_calls} calls; rows' bytes {needed / 1e6:.1f} MB = "
                      f"{needed / HBM_GBS / 1e3:.1f} us at {HBM_GBS:.0f} GB/s "
                      f"({needed / HBM_GBS / 1e4 / ms:.1f}% of the call); max "
                      f"|diff| against float64 {err:.3g} of max |out| "
                      f"{np.abs(want).max():.3g}{fill}")
            except Exception as e:
                print(f"paged latent {name} {label}: FAILED {e!r}"[:300])
            sys.stdout.flush()

        shapes = [("decode", calls or PAGED_CALLS, q, tables, pos, new)]
        if "slice" in c:  # one slot's slice: scattered by XLA, many q tiles
            t, at = c["slice"]
            shapes.append((
                f"slice of {t} rows at {at} ({t * hq // pa._q_tile(t * hq)} q tiles)",
                slice_calls or PAGED_SLICE_CALLS,
                jnp.asarray(rng.standard_normal((1, t, hq, w)), jnp.bfloat16),
                tables[:1], np.asarray([at], np.int32),
                jnp.asarray(rng.standard_normal((1, 1, t, w)), jnp.bfloat16)))
        for shape, n_calls, q_, tables_, pos_, new_ in shapes:
            t = q_.shape[1]
            # the rows the call needs ONCE (a slice's q tiles each read the
            # context's pages again: that is the kernel's cost, not the floor)
            needed = (float((pos_ + t).sum()) * w * 2
                      + q_.shape[0] * t * hq * (w * 2 + rank * 4))
            want = float64(q_, pos_, new_, tables_)
            rows = [(f"{shape}, as it is ({plan[2]} pages a pass)", None),
                    (f"{shape}, the parent's body (a page a pass, ring of 4)", 1)]
            rows += [(f"{shape}, {pp} pages a pass", pp)
                     for pp in (PAGED_LATENT_PP if sweep is None else sweep)]
            for label, pp in rows:
                timed(label, pp, n_calls, q_, tables_, pos_, new_, needed, want)


# ------------------------------------------------------------------ q40 mode
#: The Q40 matmul shapes of each benchmark cell at m <= 16 (PERF.md section
#: 4): name -> (k, n, layers of the stacked array; 1 = the unstacked head).
Q40_CELLS = {
    "deepseek7b.decode_closed": {
        "wq..wo": (4096, 4096, 30), "w1/w3": (4096, 11008, 30),
        "w2": (11008, 4096, 30), "head": (4096, 102400, 1)},
    "granite4h.reason_closed": {"head": (2048, 100352, 1)},
}
Q40_CALLS = 300
Q40_M = 16  # the kernel takes 16 rows whatever the batch


def q40_inputs(m, k, n, layers, seed=0):
    """Random packed nibbles and f16 scales made ON the device (a stacked
    4096 x 11008 x 30 array is 0.76 GB), bf16 activations."""
    kp, ks, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    packed = jax.random.bits(kp, (layers, k // 2, n), jnp.uint8)
    scales = jax.lax.bitcast_convert_type(
        jax.random.uniform(ks, (layers, k // Q_BLOCK, n), jnp.float32, 1e-3, 2e-2
                           ).astype(jnp.float16), jnp.uint16)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
    return x, packed, scales


def _timed(fn, args, calls):
    """us a call: the best of two timed runs after a warm one."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def q40_loop_us(call, x, packed, scales, calls=None):
    """us a call of `call(layer[1], x, packed, scales) -> f32[m, n]` over
    `calls` calls in ONE jitted scan, the layer cycling as the layer scan
    does; the best of two timed runs."""
    calls = calls or Q40_CALLS
    layers = packed.shape[0]

    @jax.jit
    def loop(x, packed, scales):
        def step(acc, i):
            # one layer (a head) would make the call loop-invariant: let x
            # hang on the carry (an add the size of x, under a microsecond)
            xi = x if layers > 1 else x + (acc * 1e-30).astype(x.dtype)
            out = call((i % layers).reshape(1), xi, packed, scales)
            return acc + out[0, 0], None
        return jax.lax.scan(step, jnp.float32(0),
                            jnp.arange(calls, dtype=jnp.int32))[0]

    return _timed(loop, (x, packed, scales), calls)


def q40_floor_us(m, k, n):
    """What the HBM takes for the bytes `q40_matmul_roofline` prices: 18 B
    per 32 weights + the bf16 activations in and out."""
    from benchmark.costs.q40_matmul import cost

    return cost(m, k, n)[1] / HBM_GBS / 1e3


def _q40_ablations():
    """The kernel with one part taken out, by swapping the helper that does
    it (`q40_matmul._unpack_words`, `_group_dot`, `_scaled`): what is left
    says what the part costs where the others cover it."""
    feed = lambda w: [pltpu.bitcast(w ^ jnp.uint32(c), jnp.bfloat16)
                      for c in (0, 0x10001, 0x20002, 0x30003)]
    # [g, 4m, 128] x [g, 128, lanes]: rows of the codes stand for the dot
    rows = lambda xa, codes: codes[:, : xa.shape[1]].astype(jnp.float32)
    plain = lambda y, sb: y.sum(axis=0)
    return {
        "as it is": {},
        "no dot (unpack + scale + DMA)": {"_group_dot": rows},
        "no unpack (MXU + scale + DMA)": {"_unpack_words": feed},
        "no scale multiply": {"_scaled": plain},
        "DMA only": {
            "_unpack_words": lambda w: [pltpu.bitcast(w, jnp.bfloat16)] * 4,
            "_group_dot": lambda xa, codes: jnp.zeros(
                xa.shape[:2] + codes.shape[2:], jnp.float32),
            "_scaled": plain},
    }


@contextlib.contextmanager
def _swapped(call, patch):
    """`q40_matmul`'s helpers swapped (name -> stand-in) for one row's trace
    of the jitted `call`, and put back."""
    # (a helper another form of the kernel has is left alone)
    patch = {name: fn for name, fn in (patch or {}).items() if hasattr(qmod, name)}
    saved = {name: getattr(qmod, name) for name in patch}
    try:
        for name, fn in patch.items():
            setattr(qmod, name, fn)
        call.clear_cache()
        yield
    finally:
        for name, fn in saved.items():
            setattr(qmod, name, fn)
        call.clear_cache()


def _q40_row(tag, m, k, n, data, patch=None, **tiles):
    """One timed row; `patch` swaps kernel helpers for the row's trace."""
    call = lambda layer, x, p, s: qmod._blockdot_call(
        layer, x, p, s, interpret=INTERPRET, **tiles)
    try:
        with _swapped(qmod._blockdot_call, patch):
            us = q40_loop_us(call, *data)
        floor = q40_floor_us(m, k, n)
        print(f"q40 {tag}: {us:.2f} us a call, {100 * floor / us:.1f}% of the "
              f"byte roofline ({floor:.2f} us)")
    except Exception as e:
        print(f"q40 {tag}: FAILED {e!r}"[:300])
    sys.stdout.flush()


def bench_q40(cells=None, tiles=True):
    """The block-dot Q40 kernel alone on the chip at a cell's real shapes
    (16 rows: smaller batches ride padded): parity against the XLA
    dequantise-then-dot, the kernel as it is and with each part taken out,
    then the (tk, tn) sweep and the inner loop's (lanes, rows a pass) sweep;
    300 calls in one jitted scan over the layer-stacked arrays, the layer
    cycling. It is what prices a change to the kernel before any cell runs
    (PERF.md section 6, PR 32)."""
    from dllama_tpu.ops.quant import QTensor

    for cell, shapes in (cells or Q40_CELLS).items():
        for name, (k, n, layers) in shapes.items():
            m = Q40_M
            data = x, packed, scales = q40_inputs(m, k, n, layers)
            li = layers // 2
            w = QTensor(packed[li], jax.lax.bitcast_convert_type(
                scales[li], jnp.float16)).dequantize(jnp.float32)
            want = jnp.dot(x.astype(jnp.float32), w, precision="highest")
            got = qmod._blockdot_call(jnp.full((1,), li, jnp.int32), x, packed,
                                      scales, interpret=INTERPRET)
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            del w, want, got
            tk0, tn0 = qmod._blockdot_tiles(k, n)
            print(f"q40 {cell} {name} {k}x{n} x{layers}: tiles tk={tk0} "
                  f"tn={tn0}, parity {err:.2e} of the largest value")
            for label, patch in _q40_ablations().items():
                _q40_row(f"{name} {k}x{n} m={m} {label}", m, k, n, data, patch)
            seen = {(tk0, tn0)}
            for tk in Q40_SWEEP_TK if tiles else ():
                for tn in Q40_SWEEP_TN:
                    tk_, tn_ = tk or k, n // -tn if tn < 0 else tn
                    if (k % tk_ or n % tn_ or tn_ % 128
                            or (tk_ != k and tk_ % (qmod._CHUNK * qmod._GROUP))
                            or (tk_, tn_) in seen
                            or tk_ * tn_ // 2 > Q40_SWEEP_BYTES):
                        continue
                    seen.add((tk_, tn_))
                    _q40_row(f"{name} {k}x{n} m={m} sweep tk={tk_} tn={tn_}",
                             m, k, n, data, tk=tk_, tn=tn_)
            seen = set()
            for lanes in Q40_SWEEP_LANES:
                for rows in Q40_SWEEP_ROWS:
                    rows = min(rows or tk0, tk0)
                    if tn0 % lanes == 0 and (lanes, rows) not in seen:
                        seen.add((lanes, rows))
                        _q40_row(f"{name} {k}x{n} m={m} sweep lanes={lanes} "
                                 f"rows={rows}", m, k, n, data, lanes=lanes, rows=rows)
            del x, packed, scales, data


# ------------------------------------------------------------------ deq mode
#: The Q40 matmul shapes the dequantising tier (m > 16) runs in the
#: benchmark's cells (PERF.md section 4): name -> (k, n, layers, rows).
DEQ_SHAPES = {
    "granite in_proj": (2048, 8576, 40, (48, 64)),
    "granite out_proj": (4096, 2048, 40, (48, 64)),
    "granite w1/w3": (2048, 8192, 40, (48, 64)),
    "granite w2": (8192, 2048, 40, (48, 64)),
    "granite head": (2048, 100352, 1, (48,)),
    "smallthinker wq (a slice)": (2560, 3584, 24, (256, 512)),
}
DEQ_SWEEP_TK = (256, 512, 1024, 2048, None)  # None = the whole of k
DEQ_SWEEP_TN = (256, 512, 1024, 2048, -1, -2, -14)  # -d = n / d
DEQ_SWEEP_ROWS = (256, 1024, None)  # None = what `_deq_pass` gives the tile
DEQ_SWEEP_BYTES = (256 * 1024, 4 * 1024 * 1024)  # packed bytes a grid step
DEQ_SWEEP_PASS = 5 << 19  # weights a pass
DEQ_PARENT_TK, DEQ_PARENT_TN = (512, 2048, None), (512, 2048)

# f32 2^23 + q with the nibble OR-ed into the mantissa; minus (2^23 + 8) = q - 8
_EXP_BITS, _V_OFFSET = 0x4B000000, 8388608.0 + 8.0

#: the byte-wise body this tier ran until PR 37, whole or with parts taken
#: out: what feeds the dot instead of the dequantised tile
DEQ_PARENT_PARTS = ("as it was", "unpack gone", "scale multiply gone",
                    "convert + dot + DMA", "dot + DMA", "DMA only")


def _deq_parent_kernel(layer_ref, x_ref, packed_ref, scales_ref, out_ref, acc_ref,
                       *, tk, tn, part):
    """PR 36's `_deq_kernel` (every packed byte widened to int32, mask, or, a
    concatenate, a subtract, one f32 multiply a weight, a convert), kept here
    as the yardstick the rebuilt tier is read against, with one part swapped
    for the cheapest thing of the same shape and dtype."""
    del layer_ref
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    nb = tk // Q_BLOCK
    raw = lambda dt: pltpu.bitcast(packed_ref[:], dt)  # no byte widened
    if part == "DMA only":
        acc_ref[0:8, 0:128] += jax.lax.bitcast_convert_type(
            raw(jnp.uint32)[0:8, 0:128], jnp.float32)
    else:
        if part == "dot + DMA":
            w = jnp.concatenate([raw(jnp.bfloat16)] * 4, axis=0)  # [tk, tn]
        else:
            if part in ("unpack gone", "convert + dot + DMA"):
                c = jnp.concatenate([jax.lax.bitcast_convert_type(
                    raw(jnp.uint32), jnp.float32)] * 8, axis=0).reshape(nb, Q_BLOCK, tn)
            else:
                p = packed_ref[:].astype(jnp.int32)
                lo = (p & 0x0F) | _EXP_BITS
                hi = (p >> 4) | _EXP_BITS
                c = jax.lax.bitcast_convert_type(jnp.concatenate(
                    [lo.reshape(nb, 16, tn), hi.reshape(nb, 16, tn)], axis=1),
                    jnp.float32) - _V_OFFSET
            if part in ("as it was", "unpack gone"):
                c = c * qmod._scales_f32(scales_ref[:])[:, None, :]
            w = c.reshape(tk, tn).astype(x_ref.dtype)
        acc_ref[:] += jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("tk", "tn", "part"))
def _deq_parent_call(layer, x, packed, scales, *, tk=None, tn=None, part="as it was"):
    """PR 36's `_deq_call` (one m tile, tiles 512 x 512 unless given)."""
    m, k = x.shape
    n = packed.shape[-1]
    tn = tn or _pick_tile(n, (512, 256, 128))
    tk = tk or _pick_tile(k, (512, 256, 128, 64, 32))
    return pl.pallas_call(
        functools.partial(_deq_parent_kernel, tk=tk, tn=tn, part=part),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, k // tk),
            in_specs=[
                pl.BlockSpec((m, tk), lambda j, kb, L: (0, kb)),
                pl.BlockSpec((None, tk // 2, tn), lambda j, kb, L: (L[0], kb, j)),
                pl.BlockSpec((None, tk // Q_BLOCK, tn), lambda j, kb, L: (L[0], kb, j)),
            ],
            out_specs=pl.BlockSpec((m, tn), lambda j, kb, L: (0, j)),
            scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the default tiles need no claim; the sweep's larger ones do
            **({"vmem_limit_bytes": 96 * 1024 * 1024} if tk * tn > 512 * 512 else {})),
        interpret=INTERPRET,
    )(layer, x, packed, scales)


def _deq_row(tag, m, k, n, data, call):
    """One timed row beside what `q40_deq_roofline` prices the call at: the
    larger of its bytes over 819 GB/s and 2mkn over 197 TFLOP/s."""
    from benchmark.costs.q40_matmul import cost

    try:
        us = q40_loop_us(call, *data)
        flops, nbytes = cost(m, k, n)
        by_bytes, by_mxu = nbytes / HBM_GBS / 1e3, flops / MXU_TFLOPS / 1e6
        floor = max(by_bytes, by_mxu)
        print(f"deq {tag}: {us:.2f} us a call, {100 * floor / us:.1f}% of the "
              f"roofline ({floor:.2f} us: bytes {by_bytes:.2f}, MXU {by_mxu:.2f})")
        return us
    except Exception as e:
        print(f"deq {tag}: FAILED {e!r}"[:300])
    finally:
        sys.stdout.flush()


def _deq_parity(call, x, packed, scales):
    """Largest difference from the float32 dequantise-then-dot, as a share
    of the largest value, at the middle layer."""
    from dllama_tpu.ops.quant import QTensor

    li = packed.shape[0] // 2
    w = QTensor(packed[li], jax.lax.bitcast_convert_type(
        scales[li], jnp.float16)).dequantize(jnp.float32)
    want = jnp.dot(x.astype(jnp.float32), w, precision="highest")
    got = call(jnp.full((1,), li, jnp.int32), x, packed, scales)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def _deq_ablations():
    """The tier's kernel with one part taken out, by swapping the helper
    that does it (`q40_matmul._dequant_words`, `_scale_rows`)."""
    raw = lambda w, sb, dt: jnp.concatenate(
        [pltpu.bitcast(w ^ jnp.uint32(1), dt)] * (2 * jnp.dtype(dt).itemsize), axis=0)

    unscaled = lambda w, sb, dt: qmod._nibble_planes(w).astype(dt).reshape(
        8 * w.shape[0], w.shape[1])

    return {
        "as it is": {},
        "no scale rows (one row for all)": {"_scale_rows": lambda s: jnp.broadcast_to(
            s[0:1], (4 * s.shape[0], s.shape[1]))},
        "no scale multiply": {"_scale_rows": lambda s: s[0:1], "_dequant_words": unscaled},
        "no dequantise (the packed bits fed to the dot)": {
            "_scale_rows": lambda s: s[0:1], "_dequant_words": raw},
        "DMA only": {"_scale_rows": lambda s: s[0:1], "_dequant_words": raw,
                     "_deq_dot": lambda xa, w: jnp.zeros(
                         (xa.shape[0], w.shape[1]), jnp.float32) + w[0:1].astype(jnp.float32)},
    }


def _deq_now(tag, m, k, n, data, patch=None, **tiles):
    """One timed row of the tier as it is; `patch` swaps kernel helpers."""
    with _swapped(qmod._deq_call, patch):
        return _deq_row(tag, m, k, n, data, lambda layer, x, p, s: qmod._deq_call(
            layer, x, p, s, interpret=INTERPRET, **tiles))


def bench_deq(shapes=None, tiles=True, parent=False):
    """The dequantising Q40 tier (m > 16) alone on the chip at the cells'
    real shapes and rows: parity against the float32 dequantise-then-dot,
    the tier as it is and with each part taken out, the block-dot kernel at
    the same rows, the (tk, tn, rows a pass) sweep, and (--parent) PR 36's
    byte-wise body whole, with each part taken out and over its tiles; 300
    calls in one jitted scan over the layer-stacked arrays, the layer
    cycling. A layer's sum x 40 is what a Granite decode step spends in
    `_deq_call` (PERF.md section 6, PR 37)."""
    now = lambda layer, x, p, s: qmod._deq_call(layer, x, p, s, interpret=INTERPRET)
    for name, (k, n, layers, rows) in (shapes or DEQ_SHAPES).items():
        for m in rows:
            data = q40_inputs(m, k, n, layers)
            tag = f"{name} {k}x{n} m={m}"
            tm, tk0, tn0, rows0 = qmod._deq_tiles(m, k, n)
            print(f"deq {tag}: tiles tm={tm} tk={tk0} tn={tn0} rows={rows0}, parity "
                  f"{_deq_parity(now, *data):.2e} of the largest value"
                  + (f", PR 36's body {_deq_parity(_deq_parent_call, *data):.2e}"
                     if parent else ""))
            for label, patch in _deq_ablations().items():
                _deq_now(f"{tag} {label}", m, k, n, data, patch)
            if m % 16 == 0 and k % qmod._SUB_K == 0 and m <= 64:
                _deq_row(f"{tag} the block-dot kernel", m, k, n, data,
                         lambda layer, x, p, s: qmod._blockdot_call(
                             layer, x, p, s, interpret=INTERPRET))
            first = tiles and m == rows[0]
            seen = {(tk0, tn0, rows0)}
            for tk in DEQ_SWEEP_TK if first else ():
                for tn in DEQ_SWEEP_TN:
                    for r in DEQ_SWEEP_ROWS:
                        tk_, tn_ = tk or k, n // -tn if tn < 0 else tn
                        lo, hi = DEQ_SWEEP_BYTES
                        if k % tk_ or n % tn_ or tn_ % 128 or not lo <= tk_ * tn_ // 2 <= hi:
                            continue
                        r_ = min(r or qmod._deq_pass(tk_, tn_) or tk_, tk_)
                        if tk_ % r_ or (tk_, tn_, r_) in seen or r_ * tn_ > DEQ_SWEEP_PASS:
                            continue
                        seen.add((tk_, tn_, r_))
                        _deq_now(f"{tag} sweep tk={tk_} tn={tn_} rows={r_}", m, k, n,
                                 data, tk=tk_, tn=tn_, rows=r_)
            for part in DEQ_PARENT_PARTS if parent else ():
                _deq_row(f"{tag} PR 36's body, {part}", m, k, n, data,
                         functools.partial(_deq_parent_call, part=part))
            seen = {(512, 512)}
            for tk in DEQ_PARENT_TK if first and parent else ():
                for tn in DEQ_PARENT_TN:
                    tk_ = tk or k
                    if k % tk_ or n % tn or (tk_, tn) in seen:
                        continue
                    seen.add((tk_, tn))
                    for part in ("as it was", "DMA only"):
                        _deq_row(f"{tag} PR 36's body, {part}, sweep tk={tk_} tn={tn}",
                                 m, k, n, data,
                                 functools.partial(_deq_parent_call, tk=tk_, tn=tn, part=part))
            del data


# --------------------------------------------------------------- expert mode
#: The grouped expert calls of the two expert cells (PERF.md section 4):
#: name -> experts held, experts the router chooses among, choices a token,
#: layers, the two projection shapes (k, n), and the fills: tag -> (tokens a
#: call, tile heights; the first is `expert_tile_rows`' choice).
EXPERT_CELLS = {
    "smallthinker.long_decode_closed": dict(
        held=64, routed=64, active=6, layers=24, shapes=((2560, 768), (768, 2560)),
        fills={"decode": (16, (16,)), "slice": (512, (64, 32, 128))}),
    "kimilinear.reason_closed": dict(
        held=64, routed=256, active=8, layers=26, shapes=((2304, 1024), (1024, 2304)),
        fills={"decode": (48, (16,))}),
}
EXPERT_CALLS = 240
EXPERT_SWEEP_LANES = (128, 256, 512, -2, -1)  # -d = tn / d
EXPERT_SWEEP_TN = (-2, -1)  # -d = n / d


def expert_inputs(cell, k, n, tokens, tm, seed=0):
    """One projection's stacked experts (random nibbles and f16 scales made
    ON the device: 1.5-2 GB a stack) and, a layer, the rows in expert order
    as `ops/layers.expert_groups` lays them out for `tokens` tokens routed
    uniformly (distinct experts a token; a choice past the held ones is
    another chip's). Returns (x [T*tm, k], packed, scales, tile maps
    [layers, T], n_live [layers], touched and rows a call); every layer's
    call reads the same rows (cutting a layer's x out of a stack inside the
    scan would be a copy of it a call: 37 MB for a slice)."""
    from dllama_tpu.ops.layers import expert_groups

    held, routed, active, layers = (cell[key] for key in ("held", "routed", "active", "layers"))
    kp, ks, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    # (32-bit words read as bytes: drawing 1.5 G bytes one by one would pass
    # through 6 GB of words)
    packed = jax.lax.bitcast_convert_type(
        jax.random.bits(kp, (layers, held, k // 2, n // 4), jnp.uint32),
        jnp.uint8).reshape(layers, held, k // 2, n)
    scales = jax.lax.bitcast_convert_type(
        jax.random.uniform(ks, (layers, held, k // Q_BLOCK, n), jnp.float32, 1e-3, 2e-2
                           ).astype(jnp.float16), jnp.uint16)
    rng = np.random.default_rng(seed)
    topi = np.stack([np.stack([rng.permutation(routed)[:active] for _ in range(tokens)])
                     for _ in range(layers)])
    topi = jnp.asarray(np.where(topi < held, topi, held), jnp.int32)
    _, tile_expert, tile_src, n_live, sizes = jax.vmap(
        lambda t: expert_groups(t, held, tm))(topi)
    x = jax.random.normal(kx, (tile_src.shape[1] * tm, k), jnp.float32).astype(jnp.bfloat16)
    touched = float(jnp.count_nonzero(sizes, axis=1).mean())
    rows = float(sizes.sum(axis=1).mean())
    return x, packed, scales, tile_expert, tile_src, n_live, touched, rows


def expert_loop_us(call, data, calls=None, live=None):
    """us a call of `call(layer[1], tile_expert, tile_src, n_live[1], x,
    packed, scales)` over `calls` calls in ONE jitted scan, the layer (and
    its routing) cycling; `live` overrides every layer's n_live. The best of
    two timed runs."""
    calls = calls or EXPERT_CALLS
    x, packed, scales, tile_expert, tile_src, n_live = data[:6]
    layers = packed.shape[0]
    if live is not None:
        n_live = jnp.full_like(n_live, live)

    @jax.jit
    def loop(x, packed, scales):
        def step(acc, i):
            li = i % layers
            out = call(li.reshape(1), tile_expert[li], tile_src[li],
                       n_live[li].reshape(1), x, packed, scales)
            return acc + out[0, 0], None
        return jax.lax.scan(step, jnp.float32(0), jnp.arange(calls, dtype=jnp.int32))[0]

    return _timed(loop, (x, packed, scales), calls)


def expert_parity(call, data, tm):
    """Largest difference of a layer's live tiles from the float32
    dequantise-then-dot of each tile's expert, as a share of the largest
    value."""
    from dllama_tpu.ops.quant import QTensor

    x, packed, scales, tile_expert, tile_src, n_live = data[:6]
    li = packed.shape[0] // 2
    got = call(jnp.full((1,), li, jnp.int32), tile_expert[li], tile_src[li],
               n_live[li].reshape(1), x, packed, scales)
    worst, top = 0.0, 0.0
    for t in range(int(n_live[li])):
        e = int(tile_expert[li, t])
        w = QTensor(packed[li, e], jax.lax.bitcast_convert_type(
            scales[li, e], jnp.float16)).dequantize(jnp.float32)
        want = jnp.dot(x[t * tm:(t + 1) * tm].astype(jnp.float32), w, precision="highest")
        worst = max(worst, float(jnp.abs(got[t * tm:(t + 1) * tm] - want).max()))
        top = max(top, float(jnp.abs(want).max()))
    return worst / top


def _expert_ablations():
    """`_q40_ablations`' rows (the arithmetic's helpers are shared) and the
    tile walk's own: the layout's 0/1 matrices swapped for constants nobody
    computes, and every tile's rows laid out by dots that move nothing."""
    rows = dict(_q40_ablations())
    ones = lambda *shape: jnp.ones(shape, jnp.bfloat16)
    rows["layout matrices not computed (ones)"] = {
        "_layout_constants": lambda groups: (ones(128, 512), ones(groups, 128, 128)),
        "_expert_constants": lambda k, nbp: (ones(128, 512), ones(k, nbp))}
    rows["rows laid out at the call's first tile only"] = (
        {"_expert_kernel": _pr38_kernel_laid_once} if hasattr(qmod, "_blockdot_body")
        else {"_lay_rows": lambda *refs: None})
    return rows


def _pr38_kernel_laid_once(layer_ref, expert_ref, src_ref, live_ref, x_ref, packed_ref,
                           scales_ref, out_ref, xa_ref, xs_ref, s_ref, **tiles):
    """PR 38's `_expert_kernel` (every tile runs the dense call's body, which
    lays x out where j = kb = 0) with the layout left to the first tile: for
    pricing that form from a checkout that still has it."""
    t, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t < live_ref[0])
    def _():
        qmod._blockdot_body(j + (t > 0).astype(jnp.int32), kb, x_ref, packed_ref,
                            scales_ref, out_ref, xa_ref, xs_ref, s_ref, **tiles)


def _expert_row(tag, data, k, n, patch=None, live=None, **kw):
    """One timed row beside what `moe_expert_roofline` prices the call at:
    the touched experts' packed bytes and the rows in and out over 819 GB/s
    (`benchmark/costs/moe_experts.cost`)."""
    from benchmark.costs.moe_experts import cost

    touched, rows = data[6:8]
    call = lambda *a: qmod._expert_call(*a, interpret=INTERPRET, **kw)
    try:
        with _swapped(qmod._expert_call, patch):
            us = expert_loop_us(call, data, live=live)
        floor = cost(touched, rows, k, n)[1] / HBM_GBS / 1e3
        print(f"expert {tag}: {us:.2f} us a call, {100 * floor / us:.1f}% of the byte "
              f"roofline ({floor:.2f} us), {us / touched:.3f} us a touched expert")
        return us
    except Exception as e:
        print(f"expert {tag}: FAILED {e!r}"[:300])
    finally:
        sys.stdout.flush()


def bench_expert(cells=None, tiles=True):
    """The grouped Q40 expert kernel alone on the chip at the expert cells'
    real shapes and fills, EXPERT_CALLS calls in one jitted scan over the
    layer-stacked expert stacks, the layer and its routing cycling. A
    layer's three projections x the layers is what a decode step spends in
    `_expert_call` (PERF.md section 6, PR 39)."""
    for cell, c in (cells or EXPERT_CELLS).items():
        for k, n in c["shapes"]:
            for fill, (tokens, heights) in c["fills"].items():
                for tm in heights:
                    data = expert_inputs(c, k, n, tokens, tm)
                    tag = f"{cell} {k}x{n} {fill} tm={tm}"
                    t_all = data[3].shape[1]
                    live = float(data[5].mean())
                    kw = {"tm": tm}
                    call = lambda *a: qmod._expert_call(*a, interpret=INTERPRET, **kw)
                    print(f"expert {tag}: {tokens} tokens, {data[7]:.1f} rows over "
                          f"{data[6]:.1f} touched of {c['held']} experts, {live:.1f} live of "
                          f"{t_all} tiles, parity {expert_parity(call, data, tm):.2e} of the "
                          f"largest value")
                    _expert_row(f"{tag} as it is", data, k, n, **kw)
                    if tm != heights[0]:
                        continue
                    # a tall tile runs the dequantising tier's body
                    parts = _deq_ablations() if tm > 16 and hasattr(
                        qmod, "_expert_deq_kernel") else _expert_ablations()
                    for label, patch in parts.items():
                        if patch:
                            _expert_row(f"{tag} {label}", data, k, n, patch, **kw)
                    _expert_row(f"{tag} every tile live ({t_all})", data, k, n,
                                live=t_all, **kw)
                    _expert_row(f"{tag} one tile live", data, k, n, live=1, **kw)
                    seen = set()
                    for tn in EXPERT_SWEEP_TN if tiles and hasattr(qmod, "_expert_inner") else ():
                        for lanes in EXPERT_SWEEP_LANES:
                            tn_ = n // -tn if tn < 0 else tn
                            lanes_ = tn_ // -lanes if lanes < 0 else lanes
                            if (n % tn_ or tn_ % 128 or tn_ % lanes_ or lanes_ % 128
                                    or (tn_, lanes_) in seen
                                    or (tn_, lanes_) == qmod._expert_inner(k, n)):
                                continue
                            seen.add((tn_, lanes_))
                            _expert_row(f"{tag} sweep tn={tn_} lanes={lanes_}", data, k, n,
                                        tn=tn_, lanes=lanes_, **kw)
                    del data


# ------------------------------------------------------------ moe_layer mode
#: ONE grouped expert layer-step (`ops/layers.moe_ffn`, router logits in, [N, D]
#: out) of the three expert cells (PERF.md section 4): experts held and routed
#: over, choices a token, the hidden and the expert width, the router's form,
#: and the rows of a decode step and of a prefill slice. `layers` stacks are
#: cycled through (8 here: what a call costs does not depend on how many).
MOE_LAYER_CELLS = {
    "lagunaxs2.reason_long_closed": dict(
        held=64, routed=256, active=8, d=2048, f=512, sigmoid=True, scale=2.5,
        act="silu", layers=8, rows={"decode": 24, "slice": 256}),
    "kimilinear.reason_closed": dict(
        held=64, routed=256, active=8, d=2304, f=1024, sigmoid=True, scale=2.446,
        act="silu", layers=8, rows={"decode": 48, "slice": 64}),
    "smallthinker.long_decode_closed": dict(
        held=64, routed=64, active=6, d=2560, f=768, sigmoid=False, scale=1.0,
        act="relu", layers=8, rows={"decode": 16, "slice": 512}),
}
MOE_LAYER_CALLS = 240
MOE_LAYER_TOP = 28  # rows of the profile's op table


def _pr42_expert_groups(topi, e, tm):
    """PR 42's `ops/layers.expert_groups`, kept as the yardstick: two
    argsorts, a bincount's scatter, `searchsorted`, and the lookups over the
    padded order. Returns (src, pos, tile_expert, tile_src, n_live, sizes)."""
    n, k = topi.shape
    r = n * k
    t = min(e, r) + r // tm
    assign = topi.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(assign)  # stable: a group stays in token order
    sizes = jnp.bincount(assign, length=e).astype(jnp.int32)
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    group_start = jnp.cumsum(sizes) - sizes
    n_live = tile_end[-1]
    last = jnp.maximum(n_live - 1, 0)
    tile_ids = jnp.minimum(jnp.arange(t, dtype=jnp.int32), last)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile_ids, side="right"), e - 1).astype(jnp.int32)
    p_tile = jnp.repeat(tile_ids, tm)
    p_exp = jnp.repeat(tile_expert, tm)
    lane = jnp.tile(jnp.arange(tm, dtype=jnp.int32), t)
    rank = (p_tile - tile_start[p_exp]) * tm + lane
    sorted_ix = jnp.where(rank < sizes[p_exp], group_start[p_exp] + rank, 0)
    src = order[jnp.clip(sorted_ix, 0, r - 1)] // k
    rank_in_group = jnp.argsort(order) - group_start[assign]
    pos = (tile_start[assign] * tm + rank_in_group).reshape(n, k)
    return (src.astype(jnp.int32), pos.astype(jnp.int32), tile_expert,
            tile_ids, n_live.astype(jnp.int32), sizes)


def _route(cfg, logits, bias):
    """`moe_ffn`'s routing, to the letter: (topi with a share's sentinel,
    probs, mine or None, e)."""
    k, e = cfg.n_active_experts, cfg.n_experts
    if cfg.router_sigmoid:
        score = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, topi = jax.lax.top_k(score + bias.astype(jnp.float32), k)
        topv = jnp.take_along_axis(score, topi, axis=-1)
        probs = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    else:
        topv, topi = jax.lax.top_k(logits.astype(jnp.float32), k)
        probs = jax.nn.softmax(topv, axis=-1)
    if cfg.routed_scale != 1.0:
        probs = probs * cfg.routed_scale
    mine = None
    if cfg.experts_held:
        e = cfg.experts_held
        local = topi - cfg.expert_offset
        mine = (local >= 0) & (local < e)
        topi = jnp.where(mine, local, e)
        probs = jnp.where(mine, probs, 0.0)
    return topi, probs, mine, e


def _pr42_grouped(cfg, h, logits, w1, w2, w3, layer, bias, stats):
    """PR 42's grouped route of `moe_ffn` whole (the yardstick): its
    `expert_groups`, `h[src]`, the three calls, `y[pos]` and the combine."""
    from dllama_tpu.ops.layers import activation, expert_tile_rows
    from dllama_tpu.ops.pallas.q40_matmul import q40_expert_matmul

    b, t, d = h.shape
    n, k = b * t, cfg.n_active_experts
    topi, probs, mine, e = _route(cfg, logits, bias)
    tm = expert_tile_rows(n * k if mine is None else n * k * e // cfg.n_experts, e)
    src, pos, tile_expert, tile_src, n_live, sizes = _pr42_expert_groups(
        topi.reshape(n, k), e, tm)
    xs = h.reshape(n, d)[src]
    mm = functools.partial(q40_expert_matmul, layer=layer, tile_expert=tile_expert,
                           tile_src=tile_src, n_live=n_live, tm=tm, interpret=INTERPRET)
    g = mm(xs, w1)
    up = mm(xs, w3)
    act = (activation(g, cfg.hidden_act) * up).astype(h.dtype)
    y = mm(act, w2)
    if mine is None:
        out = jnp.sum(y[pos] * probs.reshape(n, k)[..., None], axis=1)
    else:
        held = mine.reshape(n, k)
        yk = jnp.where(held[..., None], y[jnp.where(held, pos, 0)], 0.0)
        out = jnp.sum(yk * probs.reshape(n, k)[..., None], axis=1)
    rows = jnp.asarray(n * k) if mine is None else jnp.count_nonzero(mine)
    stats = stats + jnp.stack(
        [rows, jnp.count_nonzero(sizes), jnp.asarray(1), sizes.max()]
        + ([] if mine is None else [jnp.asarray(n * k)])).astype(stats.dtype)
    return out.reshape(b, t, d).astype(h.dtype), stats


def _as_rows(logits, h):
    """A layer's logits [tokens, routed] as [B, T, routed] beside h [B, T, D]."""
    return logits.reshape(*h.shape[:2], -1)


def _now_grouped(cfg, h, logits, w1, w2, w3, layer, bias, stats):
    from dllama_tpu.ops.layers import moe_ffn

    return moe_ffn(cfg, h, None, w1, w2, w3, impl="grouped", logits=logits,
                   layer=layer, stats=stats, bias=bias)


MOE_LAYER_FORMS = {"PR 42's route": _pr42_grouped, "as it is": _now_grouped}


def moe_layer_cfg(c):
    from dllama_tpu.models.config import HiddenAct, LlamaConfig

    share = c["held"] != c["routed"]
    return LlamaConfig(
        dim=c["d"], hidden_dim=c["f"], n_layers=c["layers"], n_heads=2, n_kv_heads=1,
        vocab_size=64, seq_len=32, n_experts=c["routed"], n_active_experts=c["active"],
        hidden_act=HiddenAct.RELU if c["act"] == "relu" else HiddenAct.SILU,
        router_sigmoid=c["sigmoid"], routed_scale=c["scale"],
        experts_held=c["held"] if share else 0,
        expert_offset=c["held"] if share else 0)


def moe_layer_shapes(c, tokens, fill):
    """The layer-step's arguments as shapes: (h, logits [layers], bias,
    w1, w2, w3 as QTensors of shapes, stats). A decode step's rows are one a
    sequence ([tokens, 1, D]), a slice's one sequence's ([1, tokens, D]); a
    layer's logits are kept [tokens, routed] and given h's leading shape
    where they are used (`_as_rows`), as the router's product leaves them in
    the serving program: a value whose layout the compiler is free to choose.
    (As an ARGUMENT of shape [tokens, 1, routed] they are tiled with the 1 in
    the sublanes, and everything from the sigmoid to `top_k` costs 70-140 us
    a layer-step more, on either route; my chip run, PR 43.)"""
    S = jax.ShapeDtypeStruct
    L, E, d, f = c["layers"], c["held"], c["d"], c["f"]
    qw = lambda k, n: QTensor(S((L, E, k // 2, n), jnp.uint8),
                              S((L, E, k // Q_BLOCK, n), jnp.float16))
    bt = (tokens, 1) if fill == "decode" else (1, tokens)
    return (S((*bt, d), jnp.bfloat16), S((L, tokens, c["routed"]), jnp.float32),
            S((c["routed"],), jnp.float32), qw(d, f), qw(f, d), qw(d, f),
            S((5 if E != c["routed"] else 4,), jnp.uint32))


def moe_layer_inputs(c, tokens, fill, seed=0):
    """Those arguments made ON the device: random nibbles and f16 scales,
    normal rows, and router logits whose top choices are uniform over the
    routed experts (a layer's own: the routing cycles with the layer)."""
    shapes = moe_layer_shapes(c, tokens, fill)
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    def stack(kp, ks, w):
        L, E, kh, n = w.packed.shape
        packed = jax.lax.bitcast_convert_type(
            jax.random.bits(kp, (L, E, kh, n // 4), jnp.uint32), jnp.uint8).reshape(L, E, kh, n)
        scales = jax.random.uniform(ks, w.scales.shape, jnp.float32, 1e-3, 2e-2
                                    ).astype(jnp.float16)
        return QTensor(packed, scales)

    h = jax.random.normal(keys[0], shapes[0].shape, jnp.float32).astype(jnp.bfloat16)
    logits = jax.random.normal(keys[1], shapes[1].shape, jnp.float32)
    return (h, logits, jnp.zeros(shapes[2].shape, jnp.float32),
            stack(keys[2], keys[3], shapes[3]), stack(keys[4], keys[5], shapes[4]),
            stack(keys[6], keys[7], shapes[5]), jnp.zeros(shapes[6].shape, jnp.uint32))


def _moe_layer_loop(cfg, form, calls):
    """`calls` layer-steps in ONE jitted scan, the layer and its routing
    cycling, a step's rows the step before's result (as layers follow one
    another; squashed so that they stay finite)."""
    def loop(h, logits, bias, w1, w2, w3, stats):
        layers = logits.shape[0]

        def step(carry, i):
            h, stats = carry
            li = i % layers
            out, stats = form(cfg, h, _as_rows(logits[li], h), w1, w2, w3, li, bias, stats)
            return (jnp.tanh(out.astype(jnp.float32)).astype(h.dtype), stats), None
        return jax.lax.scan(step, (h, stats), jnp.arange(calls, dtype=jnp.int32))[0]
    return jax.jit(loop)


def _moe_kernels_loop(cfg, tm, calls):
    """The layer-step's three `_expert_call`s ALONE over the same routings
    (their layouts made before the scan): what `expert` mode times, a layer's
    three projections at once."""
    from dllama_tpu.ops.layers import expert_groups
    from dllama_tpu.ops.pallas.q40_matmul import q40_expert_matmul

    def loop(h, logits, bias, w1, w2, w3, stats):
        layers, n = logits.shape[0], h.shape[0] * h.shape[1]
        e = cfg.experts_held or cfg.n_experts
        topi = jax.vmap(lambda lg: _route(cfg, lg, bias)[0].reshape(n, -1))(logits)
        _, tile_expert, tile_src, n_live, _ = jax.vmap(
            lambda t: expert_groups(t, e, tm))(topi)
        rows = tile_src.shape[1] * tm
        xs = jnp.tile(h.reshape(n, -1), (-(-rows // n), 1))[:rows]
        f = w2.shape[-2]
        act = jnp.tile(xs, (1, -(-f // xs.shape[1])))[:, :f]

        def step(acc, i):
            li = i % layers
            mm = functools.partial(
                q40_expert_matmul, layer=li, tile_expert=tile_expert[li],
                tile_src=tile_src[li], n_live=n_live[li], tm=tm, interpret=INTERPRET)
            return acc + mm(xs, w1)[0, 0] + mm(xs, w3)[0, 0] + mm(act, w2)[0, 0], None
        return jax.lax.scan(step, jnp.float32(0), jnp.arange(calls, dtype=jnp.int32))[0]
    return jax.jit(loop)


def _entry_ops(hlo_text):
    """[(kind, result shape, name)] of the scheduled instructions of a
    compiled module's ENTRY computation, parameters and scalar plumbing
    (get-tuple-element, bitcast, constant, tuple) counted apart."""
    import re

    entry = hlo_text[hlo_text.index("ENTRY "):]
    entry = entry[:entry.index("\n}")]
    ops, plumbing = [], {}
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|[\w\[\],{}:()#*\s]+?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, shape, kind = m.groups()
        shape = re.sub(r"\{[^}]*\}", "", shape).strip()
        if kind in ("parameter", "get-tuple-element", "bitcast", "constant", "tuple"):
            plumbing[kind] = plumbing.get(kind, 0) + 1
            continue
        if kind == "fusion":
            meta = re.search(r'op_name="([^"]*)"', line)
            called = re.search(r"calls=%?([\w.\-]+)", line)
            kind = f"fusion {'<- ' + meta.group(1).split('/')[-1] if meta else ''}"
            name = called.group(1) if called else name
        elif kind == "custom-call":
            kind = "custom-call " + (re.search(r'custom_call_target="([^"]*)"', line) or [0, ""])[1]
        ops.append((kind.strip(), shape, name))
    return ops, plumbing


def moe_layer_aot(cells=None):
    """No chip: each cell's layer-step, both forms, compiled for `v5e:2x2`
    (one device of it) and the entry computation's scheduled ops listed by
    kind and result shape; beside it the seconds tracing + lowering took here
    (a warm program pays them again: set-up time)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dllama_tpu.ops import matmul as mmod

    mmod.device_platform = lambda: "tpu"  # the kernels compile, not interpret
    one = SingleDeviceSharding(
        topologies.get_topology_desc("v5e:2x2", platform="tpu").devices[0])
    pin = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    for cell, c in (cells or MOE_LAYER_CELLS).items():
        cfg = moe_layer_cfg(c)
        for fill, tokens in c["rows"].items():
            h, logits, bias, w1, w2, w3, stats = pin(moe_layer_shapes(c, tokens, fill))
            logits = jax.ShapeDtypeStruct(logits.shape[1:], logits.dtype, sharding=one)
            li = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
            for tag, form in MOE_LAYER_FORMS.items():
                fn = lambda h, lg, b, w1, w2, w3, li, st: form(
                    cfg, h, _as_rows(lg, h), w1, w2, w3, li, b, st)
                t0 = time.perf_counter()
                lowered = jax.jit(fn).trace(h, logits, bias, w1, w2, w3, li, stats).lower()
                t_lower = time.perf_counter() - t0
                ops, plumbing = _entry_ops(lowered.compile().as_text())
                around = [o for o in ops if "_expert_call" not in o[2] and "tpu_custom_call" not in o[0]]
                by_kind = {}
                for kind, shape, _ in around:
                    by_kind.setdefault(kind.split(" ")[0], []).append(shape)
                print(f"moe_layer aot {cell} {fill} ({tokens} rows) {tag}: {len(around)} ops "
                      f"around {len(ops) - len(around)} kernel calls; "
                      + ", ".join(f"{len(v)} {k}" for k, v in sorted(by_kind.items()))
                      + f"; plumbing {plumbing}; traced + lowered in {t_lower:.2f} s")
                for kind, shape, name in around:
                    print(f"    {kind:<46} {shape:<34} {name}")
                sys.stdout.flush()


def _profile_ops(fn, args, calls, tag):
    """One profiled run of a scan: the device's ops by self time, us a call,
    with each op's result shape (from the HLO text the trace carries)."""
    import glob
    import tempfile

    from benchmark.trace_reduce import reduce_file

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(fn(*args))
        found = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            print(f"moe_layer {tag}: no device trace here")
            return
        ops = reduce_file(found[0])["ops"]
    total = sum(o["seconds"] for o in ops)
    print(f"moe_layer {tag}: device ops by self time, us a layer-step "
          f"({total / calls * 1e6:.1f} in all, {len(ops)} ops)")
    for o in ops[:MOE_LAYER_TOP]:
        shape = o["hlo"].split(" = ", 1)[-1].split(" ", 1)[0][:40]
        print(f"    {o['seconds'] / calls * 1e6:8.2f}  x{o['count'] // max(calls, 1) or 1:<3} "
              f"{o['name']:<40} {shape}")
    sys.stdout.flush()


def bench_moe_layer(cells=None, profile=True):
    """ONE whole grouped layer-step on the chip at the expert cells' decode
    and slice shapes: PR 42's route against today's, the three kernel calls
    alone (so: what the XLA ops around them cost), and each form's device
    ops from a profile of the same scan."""
    from dllama_tpu.ops.layers import expert_tile_rows

    calls = MOE_LAYER_CALLS
    for cell, c in (cells or MOE_LAYER_CELLS).items():
        cfg = moe_layer_cfg(c)
        e, k = c["held"], c["active"]
        for fill, tokens in c["rows"].items():
            data = moe_layer_inputs(c, tokens, fill)
            r = tokens * k
            tm = expert_tile_rows(r * e // c["routed"], e)
            tag = f"{cell} {fill} ({tokens} rows, r={r}, tm={tm}, T*tm={(min(e, r) + r // tm) * tm})"
            try:
                want = _moe_layer_loop(cfg, _pr42_grouped, 1)(*data)
                got = _moe_layer_loop(cfg, _now_grouped, 1)(*data)
                diff = float(jnp.abs(got[0].astype(jnp.float32) - want[0].astype(jnp.float32)).max())
                print(f"moe_layer {tag}: today's route against PR 42's: largest difference "
                      f"{diff:.2e} of tanh(out), counters {'equal' if (got[1] == want[1]).all() else 'DIFFER'}")
                kernels = _timed(_moe_kernels_loop(cfg, tm, calls), data, calls)
                print(f"moe_layer {tag} the three kernel calls alone: {kernels:.2f} us")
                for name, form in MOE_LAYER_FORMS.items():
                    loop = _moe_layer_loop(cfg, form, calls)
                    us = _timed(loop, data, calls)
                    print(f"moe_layer {tag} {name}: {us:.2f} us a layer-step, "
                          f"{us - kernels:.2f} around the kernels")
                    if profile:
                        _profile_ops(loop, data, calls, f"{tag} {name}")
            except Exception as ex:
                print(f"moe_layer {tag}: FAILED {ex!r}"[:400])
            sys.stdout.flush()
            del data


Q40_SWEEP_TK = (4096, 8192, None)  # None = the whole of k
Q40_SWEEP_TN = (256, 512, 1024, 2048, -2, -1)  # -d = n / d
Q40_SWEEP_LANES = (256, 512)
#: k rows a pass of the kernel's inner loop covers (None = the tile's)
Q40_SWEEP_ROWS = (1024, 2048, 4096, 8192, None)
Q40_SWEEP_BYTES = 6 * 1024 * 1024

# ------------------------------------------------ the sampler alone (PR 52)

#: cell -> (slots, vocabulary): the logits a decode step hands the sampler
SAMPLER_CELLS = {"granite4h": (48, 100352), "kimilinear": (48, 40960),
                 "brumby14b": (24, 151936), "deepseek7b": (12, 102400)}
SAMPLER_CALLS = 200


def _pr51_sample_rows(logits, keys, temps, topps, active=None):
    """PR 51's sampler, the yardstick: ONE straight-line body (the
    candidates' top-k, the logsumexp and both draws for every row, thrown
    away with a `where`), mapped over rows with per-row keys."""
    from dllama_tpu.engine.sampling import NUCLEUS_K

    def one(lg, key, t, p):
        scaled = lg[None] / jnp.maximum(t, 1e-6)
        key_p, key_t = jax.random.split(key)
        vals, idx = jax.lax.approx_max_k(scaled, min(NUCLEUS_K, lg.shape[-1]),
                                         recall_target=0.99, aggregate_to_topk=True)
        lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
        pk = jnp.exp(vals - lse)
        cum = jnp.cumsum(pk, axis=-1)
        masked = jnp.where((cum - pk) < p, vals, -jnp.inf)
        choice = jax.random.categorical(key_p, masked, axis=-1)
        tok_topp = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
        tok_temp = jax.random.categorical(key_t, scaled, axis=-1)
        use_topp = (p > 0.0) & (p < 1.0) & (cum[:, -1] >= p)
        sampled = jnp.where(use_topp, tok_topp, tok_temp)
        return jnp.where(t == 0.0, jnp.argmax(lg[None], axis=-1), sampled)[0]

    return jax.vmap(one)(logits, keys, temps, topps).astype(jnp.int32)


def sampler_loop_us(fn, logits, temps, topps, calls=None):
    """us a call of `fn(logits, keys, temps, topps, active) -> i32[B]` over
    `calls` calls in ONE jitted scan that does what a decode step does
    around it (the per-row key split, the frozen-row `where`). The logits
    hang on the carry by one add their own size, or the argmax would leave
    the loop: the first row of a cell prices that add with the argmax."""
    calls = calls or SAMPLER_CALLS
    b = logits.shape[0]
    active = jnp.ones(b, bool)

    @jax.jit
    def loop(logits, keys, temps, topps):
        def step(carry, _):
            tok, keys = carry
            splits = jax.vmap(jax.random.split)(keys)
            lg = logits + (tok[:, None] * 1e-30).astype(logits.dtype)
            nxt = fn(lg, splits[:, 1], temps, topps, active)
            return (jnp.where(active, nxt, tok), splits[:, 0]), None
        return jax.lax.scan(step, (jnp.zeros(b, jnp.int32), keys), None,
                            length=calls)[0][0]

    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(b))
    return _timed(loop, (logits, keys, temps, topps), calls)


def bench_sampler(cells=None):
    """The sampler's bodies at the cells' slots x vocabulary, us a call."""
    from dllama_tpu.engine.sampling import sample_logits

    print("--- the sampler alone: us a call (a decode step calls it once)")
    for cell, (b, v) in (cells or SAMPLER_CELLS).items():
        logits = jax.random.normal(jax.random.PRNGKey(52), (b, v), jnp.float32) * 3
        full = lambda x: jnp.full(b, x, jnp.float32)
        one_nucleus = (full(0.0).at[0].set(0.8), full(0.9))
        rows = (
            ("the carry's add + the argmax alone",
             lambda lg, *_: jnp.argmax(lg, axis=-1).astype(jnp.int32), full(0.0), full(0.9)),
            ("PR 51, a greedy batch", _pr51_sample_rows, full(0.0), full(0.9)),
            ("today, a greedy batch", sample_logits, full(0.0), full(0.9)),
            ("today, a temperature batch", sample_logits, full(0.8), full(1.0)),
            ("PR 51, a nucleus batch", _pr51_sample_rows, full(0.8), full(0.9)),
            ("today, a nucleus batch", sample_logits, full(0.8), full(0.9)),
            ("today, greedy with ONE nucleus row", sample_logits, *one_nucleus),
        )
        for tag, fn, temps, topps in rows:
            us = sampler_loop_us(fn, logits, temps, topps)
            print(f"sampler {cell} {b} x {v}: {tag}: {us:.1f} us")
            sys.stdout.flush()


def main():
    # argv: 'suite [--smoke] [--no-flash]' | 'flash [--smoke]' |
    # 'paged [--smoke]' (the paged decode call of each benchmark cell) |
    # 'q40 [--smoke] [--no-tiles]' (the block-dot kernel at the cells' shapes) |
    # 'deq [--smoke] [--no-tiles] [--parent]' (the dequantising tier, m > 16) |
    # 'expert [--smoke] [--no-tiles]' (the grouped expert kernel at the cells' fills) |
    # 'moe_layer [--smoke] [--no-profile | --aot]' (one whole grouped expert layer-step) |
    # 'sampler [--smoke]' (the sampler's bodies at the cells' slots x vocabulary) |
    # M SHAPE [variant ...] — suite runs the whole decode + prefill matrix in
    # ONE process (one device init, not six). --no-flash skips the flash
    # section; the q40 rows still land.
    from dllama_tpu.obs.compile import place_compile_cache

    place_compile_cache()
    no_flash = "--no-flash" in sys.argv
    if no_flash:
        sys.argv.remove("--no-flash")
    if "--smoke" in sys.argv:
        sys.argv.remove("--smoke")
        enable_smoke()
    if sys.argv[1:2] == ["flash"]:
        bench_flash_decode()
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["paged"]:
        # (--latent: the latent cells alone; --decode: the other cells alone;
        # --sub: the fused call again at each size of an end page's copies)
        if "--latent" not in sys.argv:
            bench_paged_decode(subs=PAGED_SUBS if "--sub" in sys.argv else ())
        if "--decode" not in sys.argv:
            bench_paged_latent()
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["q40"]:
        bench_q40(tiles="--no-tiles" not in sys.argv)
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["expert"]:
        bench_expert(tiles="--no-tiles" not in sys.argv)
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["moe_layer"]:
        # (cells by the start of their names, all of them if none is given)
        want = tuple(a for a in sys.argv[2:] if not a.startswith("--"))
        cells = {c: v for c, v in MOE_LAYER_CELLS.items() if c.startswith(want)} if want else None
        if "--aot" in sys.argv:
            moe_layer_aot(cells)
        else:
            bench_moe_layer(cells, profile="--no-profile" not in sys.argv)
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["sampler"]:
        bench_sampler()
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["deq"]:
        bench_deq(tiles="--no-tiles" not in sys.argv, parent="--parent" in sys.argv)
        print("KBENCH DONE")
        return
    if sys.argv[1:2] == ["suite"]:
        for m, label, variants in SUITE:
            try:
                run_one(m, label, variants)
            except Exception as e:
                print(f"m={m} {label}: FAILED {e!r}"[:300])
                sys.stdout.flush()
        if no_flash:
            print("flash bench SKIPPED (--no-flash)")
        else:
            try:
                bench_flash_decode()
            except Exception as e:
                print(f"flash bench: FAILED {e!r}"[:300])
                sys.stdout.flush()
        print("KBENCH DONE")
        sys.stdout.flush()
        return
    run_one(int(sys.argv[1]), sys.argv[2], sys.argv[3:] or ["A", "B", "D", "E"])


if __name__ == "__main__":
    main()

"""Flash-style causal GQA attention over the KV cache — Pallas TPU kernel.

The reference computes attention per head with an explicit scores buffer of
size seqLen (multiheadAtt_F32, nn-cpu-ops.cpp:752-787): scores → softmax →
weighted sum, all materialized. On TPU that buffer would round-trip HBM; this
kernel is the online-softmax (flash) formulation instead — the KV cache is
streamed tile-by-tile through VMEM while a running (max, sum, acc) state stays
resident, so nothing of size S ever leaves the chip.

Layout: queries are folded to [B*Hkv, T*group, hd] — one program per KV
head, with that head's `group` query heads interleaved t-major into the row
axis (row = t*group + g) — and the grid walks (kv_head, q_tile, kv_tile)
with the kv sweep innermost ("arbitrary" — it carries the accumulator). One
kv sweep serves the WHOLE query group: folding per *query* head instead
(the naive layout) re-DMAs every KV tile `group` times, which at decode
makes cache traffic group x larger than the cache (GQA group is 4 on the
llama 3 models; at 8 Ki context that redundancy costs more than the weight
stream). No materialized repeat_kv either way.

Causality follows gqa_attention's fixed-size-cache masking (ops/layers.py):
query t sees cache slots s <= pos_base + t, which also masks the unwritten
tail of the ring buffer.

KV-tile pruning: the cache is a fixed [S] ring (static shapes for XLA), but a
decode step at position p only has p+1 live rows. `pos` rides as a
scalar-prefetch argument so the k/v index maps can clamp the kv-tile index to
the last live tile — Pallas elides the DMA when consecutive grid steps map to
the same block — and the kernel skips the masked tiles' compute entirely.
Decode cost then scales with the *live* cache, not S (the reference's
`t = 0..pos` loop bound, nn-cpu-ops.cpp:752-787, recovered without dynamic
shapes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.pallas.tiling import pick_tile as _pick_tile

_NEG_INF = -1e30  # large-finite: keeps fully-masked tiles NaN-free


def _kernel(pos_ref, q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref, *, scale, tq, ts, hkv, group, rows_live):
    iq = pl.program_id(1)
    ks = pl.program_id(2)

    @pl.when(ks == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # query-row absolute positions: row r holds (t, g) = divmod(iq*tq + r,
    # group) interleaved t-major, so its token offset is (iq*tq + r) // group
    # (b = this program's batch row; padded tail rows are discarded by the
    # wrapper) — computed OUTSIDE the pl.when (program_id can't lower inside
    # its branch in interpret mode). The row index is clamped to the last REAL
    # row (ADVICE r3): sublane-pad rows would otherwise map past the true last
    # token and admit one extra live KV tile per decode step when group < 8.
    pos_b = pos_ref[pl.program_id(0) // hkv]
    qpos_max = pos_b + jnp.minimum(iq * tq + tq - 1, rows_live - 1) // group

    # kv tiles fully past the last visible position are dead (their DMA was
    # elided by the clamped index map too): skip their compute
    @pl.when(ks * ts <= qpos_max)
    def _():
        q = q_ref[:].astype(jnp.float32)  # [tq, hd]
        k = k_ref[:].astype(jnp.float32)  # [ts, hd]
        v = v_ref[:].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * scale  # [tq, ts]

        # causal mask against absolute cache positions
        row = jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 0)
        qpos = pos_b + (iq * tq + row) // group
        span = ks * ts + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 1)
        mask = span <= qpos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:][:, :1]  # replicated across lanes; take one
        l_prev = l_ref[:][:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)  # [tq, ts]
        l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ks == pl.num_programs(2) - 1)
    def _():
        l = l_ref[:][:, :1]
        out_ref[:] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "hkv", "interpret", "rows_live"))
def _flash_folded(q, k, v, pos, *, group: int, hkv: int, interpret: bool,
                  rows_live: int | None = None):
    """q[BHkv, Tp*group, hd] x cache[BHkv, S, hd] -> [BHkv, Tp*group, hd] f32.
    Query rows are t-major interleaved over the GQA group (row = t*group + g)
    so one kv sweep serves the whole group. pos: i32[B] per-row base
    positions (replicated for the scalar case). rows_live: real (pre-padding)
    row count — pad rows are excluded from the live-KV-tile horizon."""
    bhkv, rows, hd = q.shape
    s = k.shape[1]
    rows_live = rows_live or rows
    tq = _pick_tile(rows, (128, 64, 32, 16, 8))
    ts = _pick_tile(s, (512, 256, 128, 64))
    grid = (bhkv, rows // tq, s // ts)

    def kv_index(h, i, ks, pos):
        # clamp dead kv tiles to the last LIVE tile: the repeated block index
        # makes Pallas skip the DMA, and the kernel skips their compute (the
        # row index clamp mirrors the kernel's qpos_max — pad rows must not
        # widen the horizon)
        last_row = jnp.minimum(i * tq + tq - 1, rows_live - 1)
        last_live = (pos[h // hkv] + last_row // group) // ts
        return (h, jnp.minimum(ks, last_live), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # pos: i32[B]
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tq, hd), lambda h, i, ks, pos: (h, i, 0)),
            pl.BlockSpec((None, ts, hd), kv_index),
            pl.BlockSpec((None, ts, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, tq, hd), lambda h, i, ks, pos: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, hd), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd), tq=tq, ts=ts,
                          hkv=hkv, group=group, rows_live=rows_live),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * bhkv * rows * s * hd,
            bytes_accessed=(bhkv * rows * hd * 2) * q.dtype.itemsize
            + 2 * bhkv * s * hd * k.dtype.itemsize,
            transcendentals=bhkv * rows * s,
        ),
        interpret=interpret,
    )(pos, q, k, v)


def _s_buckets(s: int) -> tuple[int, ...]:
    """Ascending static cache-view lengths for the bucketed grid: powers of
    two from 512 up to S (each tileable per `supported`), always ending at S.
    Empty when the cache is too short to bucket. Valid for decode AND prefill
    chunks: the dispatch horizon is max(pos) + t, so a chunk ending inside
    bucket k rides bucket k's view and the causal mask handles the rest."""
    if s <= 512:
        return ()
    out = []
    b = 512
    while b < s:
        out.append(b)
        b *= 2
    out.append(s)
    return tuple(out)


def flash_gqa_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_cache: jax.Array,  # [B, Hkv, S, hd]
    v_cache: jax.Array,  # [B, Hkv, S, hd]
    pos_base: jax.Array,  # i32 scalar or [B] per-row positions
    *,
    interpret: bool = False,
    s_buckets: bool = False,
) -> jax.Array:
    """Drop-in for ops.layers.gqa_attention (same signature/semantics).

    s_buckets: bucket the kv grid by live-context length. The KV-tile pruning
    already elides dead tiles' DMA and compute, but the grid itself is static
    in S — at 8 Ki context and small pos the kernel still issues ~S/ts no-op
    grid steps per head per layer. With bucketing, the call dispatches
    (lax.switch) to a kernel instance whose cache view is the smallest
    power-of-two bucket covering max(pos)+t, so the walked grid tracks the
    live context — for decode steps and for the early chunks of a long
    chunked prefill alike. Off by default until the depth sweep (kbench
    flash) shows the no-op steps cost real time; flip via
    DLLAMA_FLASH_BUCKETS=1."""
    b, t, hq, hd = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    # fold the GQA group into the row axis, t-major: q head h = kv*group + g
    # lands at row t*group + g of kv head kv (see module docstring)
    qf = (
        q.reshape(b, t, hkv, group, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b * hkv, t * group, hd)
    )
    rows = t * group
    pad = (-rows) % 8
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos_base, jnp.int32)), (b,))
    kf = k_cache.reshape(b * hkv, s, hd)
    vf = v_cache.reshape(b * hkv, s, hd)
    call = functools.partial(_flash_folded, group=group, hkv=hkv,
                             interpret=interpret, rows_live=rows)

    buckets = _s_buckets(s) if s_buckets else ()
    if len(buckets) > 1:
        # every query row sees cache slots <= max(pos) + t - 1; the branch's
        # static view must cover that horizon
        horizon = jnp.max(pos) + t
        idx = sum((horizon > be).astype(jnp.int32) for be in buckets[:-1])
        out = jax.lax.switch(
            idx,
            [functools.partial(lambda se, qq, kk, vv, pp: call(
                qq, kk[:, :se], vv[:, :se], pp), se) for se in buckets],
            qf, kf, vf, pos,
        )
    else:
        out = call(qf, kf, vf, pos)
    if pad:
        out = out[:, :rows]
    return (
        out.reshape(b, hkv, t, group, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, hq, hd)
        .astype(q.dtype)
    )


def supported(q_shape: tuple[int, ...], cache_seq_len: int) -> bool:
    """Tileability check for the engine's attention dispatcher."""
    return cache_seq_len % 64 == 0 and q_shape[-1] >= 8


# --------------------------------------------------------------- paged cache
#
# LEGACY block-spec-pipelined paged variant: requires a page to hold whole
# 64-row kv tiles (`paged_supported`), which is why the serving tier no
# longer routes it — engine/kernel_select resolves the paged layout to the
# GENERAL any-page-size kernel in ops/pallas/paged_attention.py (manual
# ring of head-block page DMAs + fused KV scatter). Kept as the pipelined
# reference/A/B variant for tileable pages; tests/test_paged_kv.py still
# pins it against the jnp gather.


def _paged_kernel(pos_ref, tables_ref, *args, **kw):
    """The paged grid prefetches (pos, tables); the flash math itself is
    identical — masking is by LOGICAL position, which the index maps (not
    the kernel body) translate to pool pages."""
    return _kernel(pos_ref, *args, **kw)


@functools.partial(jax.jit, static_argnames=("group", "hkv", "interpret",
                                             "rows_live"))
def _flash_paged_folded(q, k_pool, v_pool, pos, tables, *, group: int,
                        hkv: int, interpret: bool, rows_live: int | None = None):
    """q[BHkv, Tp*group, hd] x pool[P, Hkv, page, hd] -> [BHkv, rows, hd] f32.

    Same folded layout and online-softmax state as _flash_folded; the kv
    BlockSpecs index the PAGE POOL through the block tables (scalar-prefetch
    arg #2), so each kv grid step DMAs one page tile — the kernel never sees
    a materialized contiguous cache. The live-tile clamp carries over: dead
    logical tiles map to the last live tile's page (repeated block index =>
    Pallas elides the DMA) and their compute is skipped by the kernel."""
    bhkv, rows, hd = q.shape
    page = k_pool.shape[2]
    nb = tables.shape[1]
    s = nb * page  # logical cache view length
    rows_live = rows_live or rows
    tq = _pick_tile(rows, (128, 64, 32, 16, 8))
    ts = _pick_tile(page, (512, 256, 128, 64))
    tiles_per_page = page // ts
    grid = (bhkv, rows // tq, s // ts)

    def kv_index(h, i, ks, pos, tables):
        # clamp dead LOGICAL tiles to the last live one (mirrors _flash_folded),
        # then translate the logical tile to (pool page, tile-within-page)
        last_row = jnp.minimum(i * tq + tq - 1, rows_live - 1)
        last_live = (pos[h // hkv] + last_row // group) // ts
        lk = jnp.minimum(ks, last_live)
        pg = tables[h // hkv, lk // tiles_per_page]
        return (pg, h % hkv, lk % tiles_per_page, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # pos: i32[B], tables: i32[B, nb]
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tq, hd), lambda h, i, ks, pos, tables: (h, i, 0)),
            pl.BlockSpec((None, None, ts, hd), kv_index),
            pl.BlockSpec((None, None, ts, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, tq, hd),
                               lambda h, i, ks, pos, tables: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, hd), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd), tq=tq,
                          ts=ts, hkv=hkv, group=group, rows_live=rows_live),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * bhkv * rows * s * hd,
            bytes_accessed=(bhkv * rows * hd * 2) * q.dtype.itemsize
            + 2 * bhkv * s * hd * k_pool.dtype.itemsize,
            transcendentals=bhkv * rows * s,
        ),
        interpret=interpret,
    )(pos, tables, q, k_pool, v_pool)


def paged_flash_gqa_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_pool: jax.Array,  # [P, Hkv, page, hd] (one layer's pool slice)
    v_pool: jax.Array,
    tables: jax.Array,  # i32 [B, max_blocks]
    pos_base: jax.Array,  # i32 scalar or [B] per-row positions
    *,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for ops.layers.paged_gqa_attention (same signature/semantics):
    block-table-indexed flash attention — the kv sweep walks pool pages via
    the prefetched tables, so the paged layout pays no gather materialization
    and keeps the dense kernel's live-tile DMA pruning."""
    b, t, hq, hd = q.shape
    hkv = k_pool.shape[1]
    group = hq // hkv
    qf = (
        q.reshape(b, t, hkv, group, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b * hkv, t * group, hd)
    )
    rows = t * group
    pad = (-rows) % 8
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos_base, jnp.int32)), (b,))
    out = _flash_paged_folded(qf, k_pool, v_pool, pos,
                              jnp.asarray(tables, jnp.int32),
                              group=group, hkv=hkv, interpret=interpret,
                              rows_live=rows)
    if pad:
        out = out[:, :rows]
    return (
        out.reshape(b, hkv, t, group, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, hq, hd)
        .astype(q.dtype)
    )


def paged_supported(q_shape: tuple[int, ...], page_size: int) -> bool:
    """Tileability check for the paged dispatcher: a page must hold a whole
    number of 64-wide kv tiles (the tile never spans a page boundary)."""
    return page_size % 64 == 0 and q_shape[-1] >= 8

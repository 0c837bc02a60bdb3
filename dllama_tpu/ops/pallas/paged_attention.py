"""General paged flash-decode attention — any-page-size Pallas TPU kernel.

PR 5's block-table flash variant (ops/pallas/flash_attention.paged_*) rides
the automatic BlockSpec pipeline, which constrains a page to hold whole
64-row kv tiles (`page_size % 64 == 0`); every other page size fell back to
``ops/layers.paged_gqa_attention`` — a jnp gather that re-materializes the
ENTIRE paged KV through XLA every step, the exact memory-traffic blowup
PagedAttention (Kwon et al., 2023, vLLM) exists to avoid.

This kernel drops the tileability requirement by driving the KV pipeline
manually (the jax-ml TPU paged_attention pattern, exemplified in
SNIPPETS.md [1]/[2]'s `pltpu.PrefetchScalarGridSpec` scalar-prefetch idiom):

* the page pools stay in HBM (``memory_space=ANY``) — the kernel, not the
  BlockSpec machinery, owns their movement;
* ``(pos, tables)`` ride as scalar-prefetch arguments, so the kernel walks
  each slot's block table and issues double-buffered ``make_async_copy``
  DMAs of one PAGE at a time into VMEM (page i+1's copy is in flight while
  page i is in the MXU) — any page size, the partial last page masked by
  the same absolute-position causal mask the dense kernel uses;
* the grid is one step per (slot, kv_head, q_tile); the page run is a
  dynamic ``fori_loop`` bounded by the slot's LIVE page count (``pos``-
  derived), so decode cost scales with the live context exactly like the
  dense kernel's tile pruning;
* the new token's KV rows are scatter-written into the pool INSIDE the same
  launch (``input_output_aliases`` keeps the pool update in place): the
  separate `_paged_cache_update` dispatch decode used to pay per layer is
  gone, and the attention sweep reads the row it just wrote;
* the pool the kernel walks is the WHOLE layer-stacked array, viewed as one
  run of L*P pages, and the layer is data: its first page is added to the
  prefetched page indices (``paged_decode_attention(layer=)``). The decoder's
  layer scan carries that one buffer through every layer's aliased call, so
  no layer's slice is cut out of the stack before its call or written back
  after it — the same reason the matmuls DMA-index the weight stacks. A
  per-layer pool (``layer=None``) is the same call with first page 0.

Numerics are the same online-softmax (flash) formulation as
``flash_attention._kernel``: f32 accumulation, large-finite mask fill, one
running (m, l, acc) state per q tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.pallas.tiling import pick_tile as _pick_tile

_NEG_INF = -1e30  # large-finite: keeps fully-masked pages NaN-free

#: Chunks longer than this scatter their KV rows with a single XLA scatter
#: before the kernel launches instead of fusing per-row DMAs into it — a
#: 256-token prefill chunk would otherwise serialize 2*T row copies per
#: (slot, head) program. Decode (t=1) and batched spec verify (t=k+1) sit
#: far below it and always fuse.
FUSED_SCATTER_MAX_T = 16

#: VMEM budget for the double-buffered page landing zones (2 pages x (k, v)
#: live at once). Pages above it route to the gather fallback instead of
#: risking a Mosaic VMEM overflow at compile time.
_PAGE_VMEM_BYTES = 4 * 1024 * 1024

_LANES = 128  # TPU vector lane count: the minor-dim tile of every memref


def pool_lanes(head_size: int) -> int:
    """Minor-dim width of a page pool this kernel can DMA-walk: the head
    size rounded up to whole 128-lane rows. Mosaic slices an HBM memref only
    at tile granularity, so a [P, Hkv, page, 64] pool (Llama-3.2-1B's
    head_size) is refused at compile time ("Slice shape along dimension 3
    must be aligned to tiling (128), but is 64") and XLA would hold it
    page-minor, a whole-pool relayout away from what a Mosaic call takes.
    Engines on the kernel route allocate their pool this wide; the pad
    lanes stay zero (zero q lanes score 0, zero v lanes are sliced off)."""
    return -(-head_size // _LANES) * _LANES


def paged_decode_supported(q_shape: tuple[int, ...], page_size: int,
                           kv_dtype=jnp.bfloat16) -> bool:
    """Capability check for the engine's paged-attention dispatcher — the
    explicit (dtype / head-dim / page-geometry) contract that replaced the
    old `paged_supported` whole-64-row-tile gate:

    * any page size that is a whole number of 8-row sublanes (the DMA
      granularity of the VMEM landing buffers); no power-of-two or 64-row
      requirement — 8, 24, 120 all route to the kernel;
    * head_size >= 8 (same floor as the dense flash kernel);
    * 16- or 32-bit kv elements (bf16 / f32 pools). f8 pools route to the
      gather fallback: Mosaic rejects the f8->f32 in-register extension
      (`arith.extf` is 16->32-bit only — the same rejection the DENSE f8
      flash path now hits in the AOT gate, a libtpu-level pre-existing
      condition, so paged matches dense f8 behavior rather than extending
      the breakage);
    * double-buffering two (k, v) page pairs — at the pool's lane-padded
      row width (:func:`pool_lanes`) — must fit the VMEM budget.

    Ragged tables need no capability: unallocated entries are clamped to
    the last live page by the kernel and masked by position, so any
    ``max_blocks`` works.
    """
    hd = q_shape[-1]
    el = jnp.dtype(kv_dtype).itemsize
    return (
        page_size >= 8
        and page_size % 8 == 0
        and hd >= 8
        and el in (2, 4)
        and 4 * page_size * pool_lanes(hd) * el <= _PAGE_VMEM_BYTES
    )


def _kernel(pos_ref, tables_ref, wpages_ref, woffs_ref,  # scalar prefetch
            q_ref, newk_ref, newv_ref,  # VMEM blocks
            kpool_in, vpool_in,  # HBM (ANY) — aliased to outputs
            out_ref, kpool_ref, vpool_ref,  # out block + aliased pools
            kbuf, vbuf, acc_ref, m_ref, l_ref, copy_sems, write_sem,
            *, scale, page, group, t, tq, rows_live, nb, fused):
    b = pl.program_id(0)
    h = pl.program_id(1)
    iq = pl.program_id(2)

    # ---- fused KV scatter: the new token rows land in the pool before this
    # (slot, head)'s sweep starts. Mosaic cannot DMA a dynamically-offset
    # single sublane row, so each write is a whole-page read-modify-write:
    # DMA the target page into the (not-yet-used) double buffer, blend the
    # row at its offset (f32 blend — sub-32-bit sublane broadcasts don't
    # lower; bf16<->f32 round-trips exactly), DMA the page back. One page
    # round-trip per row per pool — trivial against the decode sweep, and
    # t is capped at FUSED_SCATTER_MAX_T (prefill pre-scatters via XLA).
    # Only the first q tile of each (slot, head) writes; rows are blended
    # in order, so a duplicate (page, offset) target — only possible for
    # trash-page collisions when t > page_size — resolves last-row-wins.
    if fused:
        @pl.when(iq == 0)
        def _():
            for tt in range(t):  # static unroll: t is a trace-time int
                pg = wpages_ref[b, tt]
                off = woffs_ref[b, tt]
                sel = jax.lax.broadcasted_iota(
                    jnp.int32, (page, newk_ref.shape[-1]), 0) == off
                for src, pool, buf in ((newk_ref, kpool_ref, kbuf),
                                       (newv_ref, vpool_ref, vbuf)):
                    cp = pltpu.make_async_copy(
                        pool.at[pg, h], buf.at[0], write_sem)
                    cp.start()
                    cp.wait()
                    row = src[tt].astype(jnp.float32)[None, :]
                    buf[0] = jnp.where(
                        sel, jnp.broadcast_to(row, sel.shape),
                        buf[0].astype(jnp.float32)).astype(buf.dtype)
                    cp = pltpu.make_async_copy(
                        buf.at[0], pool.at[pg, h], write_sem)
                    cp.start()
                    cp.wait()

    # ---- live-page horizon for this q tile (mirrors flash_attention's
    # kv-tile clamp: pad rows must not widen it)
    pos_b = pos_ref[b]
    last_row = jnp.minimum(iq * tq + tq - 1, rows_live - 1)
    qpos_max = pos_b + last_row // group
    # clamp to the table capacity: the logical view is exactly nb*page rows
    # (a horizon past it reads nothing, same as the gather reference's view)
    npages = jnp.minimum(qpos_max // page + 1, nb)

    q = q_ref[...].astype(jnp.float32)  # [tq, hd]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def start_copy(i, slot):
        # defensive clamp like _paged_cache_update: a horizon past the
        # allocated table reads the last entry (its rows are masked anyway)
        pg = tables_ref[b, jnp.minimum(i, nb - 1)]
        ck = pltpu.make_async_copy(
            kpool_ref.at[pg, h], kbuf.at[slot], copy_sems.at[slot, 0])
        cv = pltpu.make_async_copy(
            vpool_ref.at[pg, h], vbuf.at[slot], copy_sems.at[slot, 1])
        return ck, cv

    ck0, cv0 = start_copy(0, 0)
    ck0.start()
    cv0.start()

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < npages)
        def _():
            ck, cv = start_copy(i + 1, jax.lax.rem(i + 1, 2))
            ck.start()
            cv.start()

        ck, cv = start_copy(i, slot)
        ck.wait()
        cv.wait()
        k = kbuf[slot].astype(jnp.float32)  # [page, hd]
        v = vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale  # [tq, page]

        # causal mask against absolute cache positions: query row r of tile
        # iq holds token offset (iq*tq + r) // group (t-major GQA fold)
        row = jax.lax.broadcasted_iota(jnp.int32, (tq, page), 0)
        qpos = pos_b + (iq * tq + row) // group
        span = i * page + jax.lax.broadcasted_iota(jnp.int32, (tq, page), 1)
        mask = span <= qpos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
        return 0

    jax.lax.fori_loop(0, npages, body, 0)
    l = l_ref[...][:, :1]
    out_ref[...] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("group", "interpret",
                                             "rows_live", "fused", "scale"))
def _paged_folded(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                  new_v, *, group: int, interpret: bool, rows_live: int,
                  fused: bool, scale: float):
    """qf[B, Hkv, rows_pad, hd] x pool[N, Hkv, page, hd] ->
    (out f32 [B, Hkv, rows_pad, hd], k_pool, v_pool).

    The pools ride in HBM (ANY memory space) and alias their outputs, so the
    fused scatter is an in-place update at the XLA level; the kernel DMA-
    walks them through the prefetched block tables. N is one layer's P
    pages, or all L*P of the layer-merged stack with ``tables``/``wpages``
    already offset to the layer (the kernel cannot tell, and need not). The
    name and the 4-D pool in the result are what the benchmark's trace
    reader finds this call by (benchmark/costs/paged_attention.py)."""
    b, hkv, rows, hd = qf.shape
    npool, _, page, _ = k_pool.shape
    nb = tables.shape[1]
    t = new_k.shape[2]
    tq = _pick_tile(rows, (128, 64, 32, 16, 8))
    grid = (b, hkv, rows // tq)
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # pos[B], tables[B, nb], wpages/woffs[B, t]
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, tq, hd), lambda b, h, iq, *_: (b, h, iq, 0)),
            pl.BlockSpec((None, None, t, hd), lambda b, h, iq, *_: (b, h, 0, 0)),
            pl.BlockSpec((None, None, t, hd), lambda b, h, iq, *_: (b, h, 0, 0)),
            any_spec,  # k pool (HBM)
            any_spec,  # v pool (HBM)
        ],
        out_specs=[
            pl.BlockSpec((None, None, tq, hd), lambda b, h, iq, *_: (b, h, iq, 0)),
            any_spec,
            any_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((2, page, hd), k_pool.dtype),  # double-buffered k pages
            pltpu.VMEM((2, page, hd), v_pool.dtype),
            pltpu.VMEM((tq, hd), jnp.float32),  # acc
            pltpu.VMEM((tq, 128), jnp.float32),  # m
            pltpu.VMEM((tq, 128), jnp.float32),  # l
            pltpu.SemaphoreType.DMA((2, 2)),  # (buffer slot, k/v) copies
            pltpu.SemaphoreType.DMA(()),  # scatter writes
        ],
    )
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page,
                          group=group, t=t, tq=tq, rows_live=rows_live,
                          nb=nb, fused=fused),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # after the 4 scalar-prefetch args: qf=4, newk=5, newv=6, kpool=7,
        # vpool=8; the pools alias outputs 1 and 2 (in-place update)
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hkv * rows * nb * page * hd,
            bytes_accessed=(b * hkv * rows * hd * 2) * qf.dtype.itemsize
            + 2 * b * hkv * nb * page * hd * k_pool.dtype.itemsize,
            transcendentals=b * hkv * rows * nb * page,
        ),
        interpret=interpret,
    )(pos, tables, wpages, woffs, qf, new_k, new_v, k_pool, v_pool)
    return out, k_pool, v_pool


def _scatter_rows_by_page(pool, new, nb, pos, wpages, woffs, trash):
    """``pool.at[wpages, :, woffs, :].set(rows)`` for a chunk of T
    consecutive rows per slot, done page by page so that the pool itself is
    only ever updated by whole pages at its leading dim.

    The direct form scatters at dims 0 and 2 of [N, Hkv, page, lanes], and
    XLA:TPU serves it by re-laying-out the WHOLE operand before and after
    (three copies of whatever it is handed: a layer's 70 MB slice when the
    pool was sliced per layer, the 2.1 GB stack once it is not). A chunk
    touches at most ceil(T / page) + 1 pages a slot, so: gather those
    pages (a few MB), scatter the rows into that small buffer with the same
    (page, offset) addressing, and put each page back with a
    dynamic_update_slice at the leading dim, which is in place. Pages of
    the buffer that received no row (the chunk ended before them) go back
    to the trash page, never over a page another entry writes.

    pool [N, Hkv, page, lanes]; new [B, Hkv, T, lanes]; wpages/woffs i32
    [B, T] from ``paged_write_targets`` (same block clipping, inactive slots
    already routed to ``trash``)."""
    b, _, t, _ = new.shape
    page = pool.shape[2]
    n_local = min(-(-t // page) + 1, nb)  # pages one slot's chunk can touch
    rows = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    blk = jnp.clip(rows // page, 0, nb - 1)  # as paged_write_targets clips
    local = blk - blk[:, :1]  # [B, T] which of the slot's pages a row hits
    ahead = blk[:, :1] + jnp.arange(n_local, dtype=jnp.int32)[None]
    # page id of local page j = the target of the first row that hits it
    first_row = jnp.clip(ahead * page - pos[:, None], 0, t - 1)
    ids = jnp.where(ahead <= blk[:, -1:],
                    jnp.take_along_axis(wpages, first_row, axis=1),
                    trash).reshape(-1)
    buf = jnp.take(pool, ids, axis=0, mode="clip")  # [B*n_local, Hkv, page, lanes]
    slot_base = jnp.arange(b, dtype=jnp.int32)[:, None] * n_local
    buf = buf.at[slot_base + local, :, woffs, :].set(
        new.transpose(0, 2, 1, 3).astype(pool.dtype))

    def put(i, pool):
        pg = jax.lax.dynamic_slice_in_dim(buf, i, 1, axis=0)
        return jax.lax.dynamic_update_slice(pool, pg, (ids[i], 0, 0, 0))

    return jax.lax.fori_loop(0, b * n_local, put, pool)


def paged_decode_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_pool: jax.Array,  # [P, Hkv, page, hd or pool_lanes(hd)] (one layer),
    # or with `layer` the stored stack [L, P, Hkv, page, lanes]
    v_pool: jax.Array,
    tables: jax.Array,  # i32 [B, max_blocks]
    pos_base: jax.Array,  # i32 scalar or [B] per-row positions
    new_k: jax.Array | None = None,  # [B, Hkv, T, hd] rows to scatter first
    new_v: jax.Array | None = None,
    active: jax.Array | None = None,  # [B] bool: inactive rows -> trash page
    *,
    layer: jax.Array | None = None,  # i32 scalar: the pools are the stack
    interpret: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array, jax.Array]:
    """Block-table paged attention over the HBM page pool, any page size.

    Without ``new_k``/``new_v`` this is a drop-in for
    ``ops.layers.paged_gqa_attention`` (returns the [B, T, Hq, hd] output
    only). With them, the call is the FUSED decode step: the new rows are
    scatter-written at their block-table positions (``active=False`` rows
    to the trash page) and the attention sweep reads them — returns
    ``(out, k_pool, v_pool)`` with the pools updated in place
    (input/output aliased). Chunks longer than ``FUSED_SCATTER_MAX_T``
    scatter via XLA before the launch instead (identical result; prefill
    chunks should not serialize per-row DMAs).

    With ``layer`` the pools are the whole layer-stacked arrays as
    ``PagedKVCache`` stores them and the call reads and writes that layer's
    pages IN the stack: the stack is viewed as one pool of L*P pages (a
    merge of the two leading dims — a bitcast, no bytes move) and the
    layer's first page, ``layer * P``, is added to every page index that
    rides in as scalar prefetch (block tables, write targets; the layer's
    trash page is its own last page). The kernel body, the rows written
    and their order are the per-layer call's; what goes is the layer's
    slice being cut out of the stack before the call and put back after
    it. Returns the pools at the stacked shape."""
    b, t, hq, hd = q.shape
    stack_shape = k_pool.shape
    if layer is not None:
        k_pool = k_pool.reshape(-1, *stack_shape[2:])
        v_pool = v_pool.reshape(-1, *stack_shape[2:])
    n_pool, hkv, page, lanes = stack_shape[-4:]
    group = hq // hkv
    if lanes != hd:
        # lane-padded pool (pool_lanes): zero-pad the head dim of every row
        # that meets it — scores and outputs are unchanged (exact zeros)
        pad_hd = lambda x: None if x is None else jnp.pad(
            x, ((0, 0),) * 3 + ((0, lanes - hd),))
        q, new_k, new_v = pad_hd(q), pad_hd(new_k), pad_hd(new_v)
    qf = (
        q.reshape(b, t, hkv, group, lanes)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, hkv, t * group, lanes)
    )
    rows = t * group
    pad = (-rows) % 8
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos_base, jnp.int32)),
                           (b,))
    tables = jnp.asarray(tables, jnp.int32)
    # page indices of the layer-merged view: the layer's own first page on
    # top of what the per-layer call computes
    first_page = 0 if layer is None else jnp.asarray(layer, jnp.int32) * n_pool

    write = new_k is not None
    if write:
        # the ONE definition of paged write addressing (shared with
        # _paged_cache_update — the fused scatter is write-for-write
        # identical to the separate dispatch it replaces)
        from dllama_tpu.ops.layers import paged_write_targets

        wpages, woffs = paged_write_targets(tables, pos, t, page, n_pool,
                                            active)
        wpages = wpages + first_page
        if t > FUSED_SCATTER_MAX_T:
            # prefill-sized chunk: scattered by XLA, then a read-only sweep
            k_pool, v_pool = (
                _scatter_rows_by_page(pool, new, tables.shape[1], pos,
                                      wpages, woffs, first_page + n_pool - 1)
                for pool, new in ((k_pool, new_k), (v_pool, new_v)))
            write = False
    if not write:
        # dummy single-row write of what the trash page already gets —
        # the kernel skips the scatter entirely (fused=False)
        wpages = jnp.zeros((b, 1), jnp.int32)
        woffs = jnp.zeros((b, 1), jnp.int32)
        nk = jnp.zeros((b, hkv, 1, lanes), k_pool.dtype)
        nv = jnp.zeros((b, hkv, 1, lanes), v_pool.dtype)
    else:
        nk = new_k.astype(k_pool.dtype)
        nv = new_v.astype(v_pool.dtype)

    out, k_pool, v_pool = _paged_folded(
        qf, k_pool, v_pool, pos, tables + first_page, wpages, woffs, nk, nv,
        group=group, interpret=interpret, rows_live=rows, fused=write,
        scale=1.0 / math.sqrt(hd))
    out = (
        out[:, :, :rows, :hd].reshape(b, hkv, t, group, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, hq, hd)
        .astype(q.dtype)
    )
    if new_k is None:
        return out
    return out, k_pool.reshape(stack_shape), v_pool.reshape(stack_shape)

"""General paged flash-decode kernel (ISSUE 8): interpret-mode parity of
``ops/pallas/paged_attention`` against the jnp block-table gather reference
across page sizes the old ``% 64`` gate rejected ({8, 16, 24}), plus 64;
partial last pages; GQA group > 1; the fused KV scatter landing rows exactly
where ``PagedKVCache``/`_paged_cache_update` expects (bitwise, incl. the
trash-page routing of inactive rows); and the engine-level contract — the
fused kernel's token streams are BIT-IDENTICAL to the gather path's through
the real decode scan. Every op-level case also runs LAYER-INDEXED (PR 27):
the same call on a layer-stacked pool with `layer=li` must equal the
per-layer call on that layer's slice bit for bit, and leave every other
layer's pages as they were. Since PR 30 one grid step serves a BLOCK of kv
heads whose size and ring depth come from a VMEM budget: the cases below
run blocks of 1, 3 and 8 heads, force `hb = 1` and `hb = Hkv` through the
budget (same pools bit for bit), land a verify chunk's rows in two pages of
one sweep, mix live and trash-routed slots in one call, and share a prefix
page between tables.

Numerics note: the attention OUTPUT is online-softmax (flash), so op-level
parity vs the materialized-softmax gather is allclose at f32 tolerance (the
same contract as test_paged_kv's legacy flash test); the scattered POOL
CONTENTS and the engine token streams are exact. Tiny shapes keep the file
inside the fast tier-1 band."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models.llama import _paged_cache_update
from dllama_tpu.ops.layers import paged_gqa_attention
from dllama_tpu.ops.pallas import paged_attention as pa
from dllama_tpu.ops.pallas.paged_attention import (
    FUSED_SCATTER_MAX_T,
    paged_decode_attention,
    paged_decode_supported,
)


def _setup(rng, page, nb, b=2, t=1, hq=4, hkv=2, hd=64, dtype=jnp.float32):
    npool = b * nb + 1  # +1 trash page, like PagedKVCache.create
    q = jnp.asarray(rng.standard_normal((b, t, hq, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((npool, hkv, page, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((npool, hkv, page, hd)), dtype)
    # shuffled tables: physical page order must not matter
    tables = jnp.asarray(
        rng.permutation(npool - 1)[: b * nb].reshape(b, nb), jnp.int32)
    return q, kp, vp, tables


N_LAYERS = 3


def _stack(rng, kp, vp):
    """Layer-stacked pools [L, P, Hkv, page, hd] with distinct contents per
    layer, the way PagedKVCache stores them; layer 0 is (kp, vp) itself."""
    more = lambda p: jnp.asarray(
        rng.standard_normal((N_LAYERS - 1, *p.shape)), p.dtype)
    return (jnp.concatenate([kp[None], more(kp)]),
            jnp.concatenate([vp[None], more(vp)]))


def _assert_layer_indexed_equals_sliced(rng, q, kp, vp, tables, pos,
                                        nk=None, nv=None, active=None):
    """For every layer of a stacked pool: the layer-indexed call == the
    per-layer call on the slice, BITWISE (output and pools), and the other
    layers' pages (their trash pages too) are untouched."""
    ks, vs = _stack(rng, kp, vp)
    for li in range(N_LAYERS):
        want = paged_decode_attention(q, ks[li], vs[li], tables, pos, nk, nv,
                                      active, interpret=True)
        got = paged_decode_attention(q, ks, vs, tables, pos, nk, nv, active,
                                     layer=jnp.int32(li), interpret=True)
        if nk is None:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            continue
        for (g, w, old) in ((got[1], want[1], ks), (got[2], want[2], vs)):
            assert g.shape == old.shape  # comes back at the stored shape
            np.testing.assert_array_equal(np.asarray(g[li]), np.asarray(w))
            others = [l for l in range(N_LAYERS) if l != li]
            np.testing.assert_array_equal(np.asarray(g)[others],
                                          np.asarray(old)[others])
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def _reference(q, kp, vp, tables, pos, nk=None, nv=None, active=None):
    """Scatter via the model's own `_paged_cache_update`, then the jnp
    gather attention — the exact pair of dispatches the fused kernel
    replaces."""
    if nk is not None:
        kp = _paged_cache_update(kp, nk, tables, pos, active)
        vp = _paged_cache_update(vp, nv, tables, pos, active)
    return paged_gqa_attention(q, kp, vp, tables, pos), kp, vp


LAYOUTS = pytest.mark.parametrize("stacked", [False, True],
                                  ids=["per-layer", "layer-indexed"])


@LAYOUTS
@pytest.mark.parametrize("page,nb,pos", [
    (8, 8, [19, 1]),      # small page the old gate rejected
    (16, 4, [35, 0]),     # pow-2, one slot empty
    (24, 3, [51, 17]),    # non-power-of-2, partial last page both slots
    (64, 2, [63, 127]),   # legacy-tileable size, page-boundary edges
])
def test_read_parity_any_page_size(rng, page, nb, pos, stacked):
    """Read-only sweep matches the gather reference for every (page_size,
    horizon) combo — incl. pages the old `% 64` gate rejected."""
    q, kp, vp, tables = _setup(rng, page, nb)
    pos = jnp.asarray(pos, jnp.int32)
    if stacked:
        return _assert_layer_indexed_equals_sliced(rng, q, kp, vp, tables, pos)
    want, _, _ = _reference(q, kp, vp, tables, pos)
    got = paged_decode_attention(q, kp, vp, tables, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@LAYOUTS
@pytest.mark.parametrize("page,nb,t,pos", [
    (8, 8, 1, [19, 1]),    # decode step
    (8, 8, 5, [9, 2]),     # spec-verify chunk crossing a page boundary
    (24, 3, 1, [23, 47]),  # write at the exact last row of a page
])
def test_fused_scatter_parity(rng, page, nb, t, pos, stacked):
    """Fused path: pools match `_paged_cache_update` BITWISE (the row lands
    where PagedKVCache expects) and the output reads the just-written rows."""
    q, kp, vp, tables = _setup(rng, page, nb, t=t)
    pos = jnp.asarray(pos, jnp.int32)
    nk = jnp.asarray(rng.standard_normal((2, 2, t, 64)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((2, 2, t, 64)), jnp.float32)
    if stacked:
        return _assert_layer_indexed_equals_sliced(rng, q, kp, vp, tables,
                                                   pos, nk, nv)
    want, kp_ref, vp_ref = _reference(q, kp, vp, tables, pos, nk, nv)
    got, kp2, vp2 = paged_decode_attention(q, kp, vp, tables, pos, nk, nv,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(kp2), np.asarray(kp_ref))
    np.testing.assert_array_equal(np.asarray(vp2), np.asarray(vp_ref))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@LAYOUTS
def test_fused_scatter_inactive_rows_hit_trash_page(rng, stacked):
    """active=False rows scatter to the trash page (pool page P-1) exactly
    like `_paged_cache_update`'s masked write — live pages untouched.
    Layer-indexed: to THAT layer's trash page, no other layer's."""
    q, kp, vp, tables = _setup(rng, 16, 4)
    pos = jnp.asarray([35, 1], jnp.int32)
    active = jnp.asarray([True, False])
    nk = jnp.asarray(rng.standard_normal((2, 2, 1, 64)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((2, 2, 1, 64)), jnp.float32)
    if stacked:
        _assert_layer_indexed_equals_sliced(rng, q, kp, vp, tables, pos,
                                            nk, nv, active)
        # and the inactive slot's row really is in layer 1's trash page
        ks, vs = _stack(rng, kp, vp)
        _, ks2, _ = paged_decode_attention(
            q, ks, vs, tables, pos, nk, nv, active, layer=jnp.int32(1),
            interpret=True)
        np.testing.assert_array_equal(np.asarray(ks2[1, -1, :, 1 % 16]),
                                      np.asarray(nk[1, :, 0]))
        return
    _, kp_ref, vp_ref = _reference(q, kp, vp, tables, pos, nk, nv, active)
    _, kp2, vp2 = paged_decode_attention(q, kp, vp, tables, pos, nk, nv,
                                         active, interpret=True)
    np.testing.assert_array_equal(np.asarray(kp2), np.asarray(kp_ref))
    np.testing.assert_array_equal(np.asarray(vp2), np.asarray(vp_ref))
    # slot 1's own pages really kept their old contents (the write went to
    # the trash page, not to its table positions)
    for pg in np.asarray(tables[1]):
        np.testing.assert_array_equal(np.asarray(kp2[pg]), np.asarray(kp[pg]))


def test_gqa_group_gt_one(rng):
    """group 4 (the llama-3 ratio): one kv sweep serves the whole folded
    query group."""
    q, kp, vp, tables = _setup(rng, 8, 8, hq=8, hkv=2)
    pos = jnp.asarray([19, 3], jnp.int32)
    want, _, _ = _reference(q, kp, vp, tables, pos)
    got = paged_decode_attention(q, kp, vp, tables, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@LAYOUTS
@pytest.mark.parametrize("page,nb,t,pos,active", [
    (8, 8, FUSED_SCATTER_MAX_T * 2, [0, 3], None),  # 4-5 pages a slot
    (8, 8, FUSED_SCATTER_MAX_T + 1, [21, 40], None),  # starts mid-page
    (24, 3, FUSED_SCATTER_MAX_T + 4, [0, 29], None),  # ends before the
    # last page a chunk of this length could reach: that page is not written
    (16, 4, FUSED_SCATTER_MAX_T * 2, [5, 20], [True, False]),  # trash-routed
])
def test_prefill_chunk_pre_scatter_path(rng, page, nb, t, pos, active,
                                        stacked):
    """t > FUSED_SCATTER_MAX_T takes the XLA pre-scatter branch of the same
    wrapper (page by page, `_scatter_rows_by_page`): identical pools and
    output as the fused contract."""
    q, kp, vp, tables = _setup(rng, page, nb, t=t)
    pos = jnp.asarray(pos, jnp.int32)
    active = None if active is None else jnp.asarray(active)
    nk = jnp.asarray(rng.standard_normal((2, 2, t, 64)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((2, 2, t, 64)), jnp.float32)
    if stacked:
        return _assert_layer_indexed_equals_sliced(rng, q, kp, vp, tables,
                                                   pos, nk, nv, active)
    want, kp_ref, vp_ref = _reference(q, kp, vp, tables, pos, nk, nv, active)
    got, kp2, vp2 = paged_decode_attention(q, kp, vp, tables, pos, nk, nv,
                                           active, interpret=True)
    # every allocatable page; the trash page too unless a chunk longer than
    # a page was routed there (rows collide on it, and which one stays is
    # nobody's contract)
    live = slice(None) if active is None else slice(0, -1)
    np.testing.assert_array_equal(np.asarray(kp2[live]), np.asarray(kp_ref[live]))
    np.testing.assert_array_equal(np.asarray(vp2[live]), np.asarray(vp_ref[live]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _fused_case(rng, page, nb, t, pos, hq, hkv, active=None,
                dtype=jnp.float32, hd=64):
    """Operands of one fused call: (q, kp, vp, tables, pos, nk, nv, active)."""
    b = len(pos)
    q, kp, vp, tables = _setup(rng, page, nb, b=b, t=t, hq=hq, hkv=hkv,
                               hd=hd, dtype=dtype)
    nk = jnp.asarray(rng.standard_normal((b, hkv, t, hd)), dtype)
    nv = jnp.asarray(rng.standard_normal((b, hkv, t, hd)), dtype)
    return (q, kp, vp, tables, jnp.asarray(pos, jnp.int32), nk, nv,
            None if active is None else jnp.asarray(active))


def _assert_fused_matches_reference(args, atol=2e-5):
    """Pools BITWISE what `_paged_cache_update` writes (trash page too),
    output to the gather reference's tolerance on the active slots."""
    want, kp_ref, vp_ref = _reference(*args)
    got, kp2, vp2 = paged_decode_attention(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(kp2), np.asarray(kp_ref))
    np.testing.assert_array_equal(np.asarray(vp2), np.asarray(vp_ref))
    live = slice(None) if args[7] is None else np.asarray(args[7])
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=atol, rtol=atol)
    return got, kp2, vp2


def _plan_of(args):
    """(hb, depth) the call's shapes get at the module's budget."""
    q, kp = args[0], args[1]
    rows = -(-(q.shape[1] * (q.shape[2] // kp.shape[1])) // 8) * 8
    return pa._plan(kp.shape[1], kp.shape[2], kp.shape[3], kp.dtype.itemsize,
                    pa._q_tile(rows), q.shape[1], pa._VMEM_BUDGET_BYTES)[:2]


@LAYOUTS
@pytest.mark.parametrize("hq,hkv", [(2, 1), (3, 3), (6, 3), (8, 8)])
def test_head_blocks_of_one_three_and_whole_hkv(rng, hq, hkv, stacked):
    """One grid step serves a BLOCK of kv heads (PR 30): a block of one
    head, a non-power-of-two block and a whole-Hkv block of 8 all read and
    write what the per-head reference does — a spec-verify chunk whose rows
    land in two pages of the sweep, slots at 1, 2 and 3 live pages."""
    args = _fused_case(rng, 8, 4, 3, [6, 15, 17], hq, hkv)
    assert _plan_of(args)[0] == hkv  # tiny pages: the block is all heads
    if stacked:
        return _assert_layer_indexed_equals_sliced(rng, *args)
    _assert_fused_matches_reference(args)


@pytest.mark.parametrize("t,pos,active", [
    (1, [19, 0, 44, 7], None),  # decode; slots of 3, 1, 6 and 1 live pages
    (5, [6, 13, 27, 61], None),  # verify chunks over two pages; one clipped
    # at the table's end (rows 64, 65 land in the last page, as the XLA scatter clips)
    (5, [6, 13, 27, 40], [False, True, False, True]),  # trash first and mid
    (16, [3, 0, 21, 40], None),  # t > page: three pages receive rows
])
def test_budget_forcing_one_head_equals_whole_block(rng, monkeypatch, t, pos,
                                                    active):
    """`hb` and the ring's depth are functions of the shapes and a VMEM
    budget: a budget nothing fits gives hb = 1 at depth 2 (what
    `paged_decode_supported` admits), a middling one a proper divisor, the
    default all of Hkv at full depth. The three are the SAME result: pools
    bit for bit, outputs to 2e-5 — and each matches the reference."""
    args = _fused_case(rng, 8, 8, t, pos, 8, 4, active)
    results, default = [], pa._VMEM_BUDGET_BYTES
    for hb in (1, 2, 4):
        for budget in (1, *range(10_000, 1_000_000, 10_000)):
            monkeypatch.setattr(pa, "_VMEM_BUDGET_BYTES",
                                default if hb == 4 else budget)
            if _plan_of(args)[0] == hb:
                break
        assert _plan_of(args)[0] == hb and (budget == 1) == (hb != 2)
        results.append(_assert_fused_matches_reference(args))
    assert _plan_of(args) == (4, pa._MAX_DEPTH)
    for got, kp2, vp2 in results[1:]:
        np.testing.assert_array_equal(np.asarray(kp2), np.asarray(results[0][1]))
        np.testing.assert_array_equal(np.asarray(vp2), np.asarray(results[0][2]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(results[0][0]),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("budget", [1, pa._VMEM_BUDGET_BYTES],
                         ids=["one-head", "whole-block"])
def test_ring_carries_over_q_tiles_and_slots(rng, monkeypatch, budget):
    """The landing ring does not drain between grid steps: a prefill chunk
    of several q tiles over slots of 1 to 8 live pages, read-only, walks
    (slot, head block, q tile) steps whose first pages the step BEFORE
    started — also across steps shorter than the ring is deep."""
    monkeypatch.setattr(pa, "_VMEM_BUDGET_BYTES", budget)
    q, kp, vp, tables = _setup(rng, 8, 8, b=3, t=40, hq=16, hkv=4)
    assert q.shape[1] * 4 > pa._Q_TILE_MAX  # 160 folded rows: 5 q tiles of 32
    pos = jnp.asarray([0, 23, 3], jnp.int32)
    want, _, _ = _reference(q, kp, vp, tables, pos)
    got = paged_decode_attention(q, kp, vp, tables, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@LAYOUTS
def test_active_and_inactive_slots_in_one_head_blocked_call(rng, stacked):
    """An inactive slot's rows go to the trash page through ONE
    read-modify-write a head block, beside live slots whose rows are
    blended into the sweep: every page, the trash page too, bitwise as
    `_paged_cache_update` leaves it (4 rows at distinct offsets)."""
    args = _fused_case(rng, 16, 4, 4, [35, 1, 14, 60], 8, 4,
                       [True, False, True, True])
    assert _plan_of(args)[0] == 4
    if stacked:
        return _assert_layer_indexed_equals_sliced(rng, *args)
    _, kp2, _ = _assert_fused_matches_reference(args)
    np.testing.assert_array_equal(np.asarray(kp2[-1, :, 1:5]),
                                  np.asarray(args[5][1]))  # rows 1..4 of trash
    for pg in np.asarray(args[3][1]):  # the slot's own pages: untouched
        np.testing.assert_array_equal(np.asarray(kp2[pg]),
                                      np.asarray(args[1][pg]))


@LAYOUTS
def test_two_slots_share_a_read_only_prefix_page(rng, stacked):
    """Prefix sharing: two tables hold the same first page. Both sweeps
    read it (one of them through the copy the step before started), each
    slot writes only its own tail page."""
    q, kp, vp, tables, pos, nk, nv, _ = _fused_case(
        rng, 8, 4, 2, [11, 14, 9], 8, 4)
    tables = tables.at[1, 0].set(tables[0, 0]).at[2, 0].set(tables[0, 0])
    args = (q, kp, vp, tables, pos, nk, nv, None)
    if stacked:
        return _assert_layer_indexed_equals_sliced(rng, *args)
    _, kp2, vp2 = _assert_fused_matches_reference(args)
    shared = int(tables[0, 0])
    np.testing.assert_array_equal(np.asarray(kp2[shared]), np.asarray(kp[shared]))
    np.testing.assert_array_equal(np.asarray(vp2[shared]), np.asarray(vp[shared]))


@pytest.mark.parametrize("t,group", [(9, 4), (16, 16)])
def test_folded_rows_one_q_tile_or_pre_scatter(rng, t, group):
    """A verify chunk's folded rows are ONE q tile up to 128 (9 tokens x
    group 4 = 36 rows: one sweep of the pages, not five); past that the
    rows take several tiles, so several sweeps, and the wrapper scatters
    them through XLA first — the fused blend needs the slot's one sweep."""
    args = _fused_case(rng, 8, 8, t, [5, 30], 2 * group, 2)
    rows = -(-t * group // 8) * 8
    assert (pa._q_tile(rows) == rows) == (rows <= pa._Q_TILE_MAX)
    _assert_fused_matches_reference(args)


def test_bfloat16_pool_takes_bfloat16_qk_operands(rng):
    """Where q and the pool are BOTH bfloat16 the q.k product takes them as
    they are stored (a product of two bfloat16 values is exact in float32,
    so only the order of the float32 sum can differ); p, the accumulator
    and p.v stay float32. Pools bitwise; the output against the reference
    run in float32 on the same bfloat16 values."""
    args = _fused_case(rng, 16, 4, 2, [35, 14, 60], 8, 4,
                       dtype=jnp.bfloat16, hd=128)
    q, kp, vp, tables, pos, nk, nv, _ = args
    _, kp_ref, vp_ref = _reference(*args)
    got, kp2, vp2 = paged_decode_attention(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(kp2, np.float32),
                                  np.asarray(kp_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(vp2, np.float32),
                                  np.asarray(vp_ref, np.float32))
    f32 = lambda x: x.astype(jnp.float32)
    want = paged_gqa_attention(f32(q), f32(kp_ref), f32(vp_ref), tables, pos)
    assert got.dtype == jnp.bfloat16  # the wrapper hands back q's dtype
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want),
                               atol=1e-2, rtol=1e-2)  # its one rounding


@pytest.mark.parametrize("name,hkv,page,itemsize,tq,hb_range", [
    ("deepseek-llm-7b decode", 32, 128, 2, 8, (8, 32)),
    ("granite-4.0-h-micro decode", 8, 128, 2, 8, (8, 8)),
    ("deepseek-llm-7b prefill chunk", 32, 128, 2, 128, (4, 32)),
    ("smallthinker-21b-a3b decode, global and window", 4, 128, 2, 8, (4, 4)),
    ("smallthinker-21b-a3b 512-row slice", 4, 128, 2, 128, (4, 4)),
    ("laguna-xs.2 decode, global fold 6 and window fold 8", 8, 128, 2, 8, (8, 8)),
    ("laguna-xs.2 256-row slice", 8, 128, 2, 128, (8, 8)),
    ("a page only one head of fits", 8, 4096, 2, 8, (1, 1)),
])
def test_plan_is_a_function_of_shapes(name, hkv, page, itemsize, tq, hb_range):
    """The cells' decode calls get head blocks of 4-32 heads (256 KB to
    1 MB a copy); a prefill chunk's accumulator takes its share; a page too
    large for anything else gets hb = 1 at depth 2. Every call that is not
    a latent one takes a page a pass."""
    hb, depth, pp, nbytes = pa._plan(hkv, page, 128, itemsize, tq, 1,
                                     pa._VMEM_BUDGET_BYTES)
    assert hkv % hb == 0 and hb_range[0] <= hb <= hb_range[1], (name, hb)
    assert 2 <= depth <= pa._MAX_DEPTH and pp == 1
    assert nbytes <= pa._VMEM_BUDGET_BYTES or (hb, depth) == (1, 2)
    assert paged_decode_supported((hkv, 128), page)


@pytest.mark.parametrize("name,tq,t", [
    ("a.x-k1 decode: 64 heads", 64, 1),
    ("a.x-k1 512-row slice: q tiles of 128", 128, 1),
    ("kimi-linear decode: 32 heads", 32, 1),
    ("kimi-linear 64-row slice", 128, 1),
    ("a.x-k1 verify chunk of 2 (one q tile)", 128, 2),
])
def test_plan_of_the_latent_sweep(name, tq, t):
    """A latent call (one pool, Hkv = 1, 128-row pages of 640 lanes) takes
    `_LATENT_PASS_PAGES` pages a pass on a ring two passes deep at both
    cells' shapes; a budget too small for that ring gives fewer pages a
    pass, and one that holds no pass of two pages the page-a-pass plan of
    every other call."""
    plan = lambda budget, latent=True: pa._plan(1, 128, 640, 2, tq, t, budget,
                                                latent)
    hb, depth, pp, nbytes = plan(pa._VMEM_BUDGET_BYTES)
    assert (hb, depth, pp) == (1, 2, pa._LATENT_PASS_PAGES), name
    assert nbytes <= pa._VMEM_BUDGET_BYTES
    # the pass's temporaries are in the count: s and p [tq, pp * page], the
    # widened rows [pp * page, lanes] f32, and the ring's 2 * pp pages
    assert nbytes >= (2 * tq * pp * 128 + pp * 128 * 640) * 4 + 2 * pp * 128 * 640 * 2
    pps = [plan(b)[2] for b in range(0, pa._VMEM_BUDGET_BYTES, 100_000)]
    assert pps == sorted(pps) and pps[0] == 1 and set(pps) >= {1, 2, pa._LATENT_PASS_PAGES}
    assert plan(1) == plan(1, latent=False)  # hb = 1, depth 2, a page a pass
    assert plan(pa._VMEM_BUDGET_BYTES, latent=False)[2] == 1


# ------------------------------------------------- the latent sweep's passes
# One pool whose row is key and value (Hkv = 1), `pp` pages a pass (PR 48).
# Reference: the jnp form over the gathered rows, and the pool as the
# row-by-row scatter leaves it (last row wins, inactive slots to the trash).

PP = pa._LATENT_PASS_PAGES
L_PAGE, L_NB, L_RANK, L_W, L_LANES = 8, 2 * PP + 4, 64, 96, 128


def _latent_case(rng, pos, t=1, h=8, active=None, dtype=jnp.float32,
                 fused=True):
    b = len(pos)
    npool = b * L_NB + 1
    f = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    pool = f(npool, 1, L_PAGE, L_LANES).at[..., L_W:].set(0)
    tables = jnp.asarray(
        rng.permutation(npool - 1)[: b * L_NB].reshape(b, L_NB), jnp.int32)
    q, new = f(b, t, h, L_W), f(b, 1, t, L_W)
    return (q, pool, tables, jnp.asarray(pos, jnp.int32), new,
            None if active is None else jnp.asarray(active))


def _latent_reference(q, pool, tables, pos, new, active):
    from dllama_tpu.ops.layers import (latent_attention, paged_view,
                                       paged_write_targets)

    b, t = q.shape[:2]
    wpages, woffs = paged_write_targets(tables, pos, t, L_PAGE, pool.shape[0],
                                        active)
    want_pool = np.array(pool)
    for bi in range(b):
        for tt in range(t):  # in order: a duplicate target keeps the last row
            want_pool[wpages[bi, tt], 0, woffs[bi, tt], :L_W] = new[bi, 0, tt]
    rows = paged_view(jnp.asarray(want_pool), tables)[:, 0, :, :L_W]
    return latent_attention(q, rows, pos, 0.125, L_RANK), want_pool


def _assert_latent_matches(case, atol=2e-5, **kw):
    q, pool, tables, pos, new, active = case
    want, want_pool = _latent_reference(*case)
    placeholder = jnp.zeros((1, 1, 8, 128), pool.dtype)
    out, pool2, ph2 = paged_decode_attention(
        q, pool, placeholder, tables, pos, new, None, active, interpret=True,
        latent=L_RANK, scale=0.125, **kw)
    np.testing.assert_array_equal(np.asarray(pool2, np.float32),
                                  np.asarray(want_pool, np.float32))
    live = slice(None) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=atol, rtol=atol)
    assert out.shape == q.shape[:3] + (L_RANK,) and ph2.shape == placeholder.shape
    return out, pool2


def _rows_at(pages, row):
    """The position whose decode step sweeps `pages` pages and writes its
    new row at `row` of the last."""
    return (pages - 1) * L_PAGE + row


@pytest.mark.parametrize("pages", [1, PP - 1, PP, PP + 1, 2 * PP, 2 * PP + 1],
                         ids=lambda n: f"{n}-pages")
@pytest.mark.parametrize("row", [0, L_PAGE - 1], ids=["first-row", "last-row"])
def test_latent_runs_of_whole_and_part_filled_passes(rng, pages, row):
    """A decode step (t = 1, fused scatter) over a run one page short of a
    pass, a whole pass, one page into the next, two passes and one page
    past them, and of one page, the new row on its page's first and last
    row: a part-filled pass's dead pages are out of the softmax. The other
    slots stand at lengths of their own, so passes of every fill follow one
    another through the ring."""
    assert pa._plan(1, L_PAGE, L_LANES, 4, 8, 1, pa._VMEM_BUDGET_BYTES,
                    True)[1:3] == (2, PP)
    pos = [_rows_at(pages, row), _rows_at(1, 3), _rows_at(PP + 2, row)]
    _assert_latent_matches(_latent_case(rng, pos))


@pytest.mark.parametrize("t", [1, 5, 16])
@pytest.mark.parametrize("at", ["page", "pass"])
def test_latent_rows_cross_a_page_and_a_pass_boundary(rng, t, at):
    """The fused scatter inside a pass: a verify chunk's rows (t = 5: two
    pages; t = 16 = 2 pages' rows: three pages) start three rows before a
    page's end that is inside a pass, and before one that ends a pass, so
    the written pages sit in one pass or in two; each is written back once
    and the sweep reads the blended copy."""
    edge = (PP if at == "pass" else PP - 1) * L_PAGE
    pos = [edge - 3, edge - 3 + PP * L_PAGE, edge - 1]
    _assert_latent_matches(_latent_case(rng, pos, t=t))


@pytest.mark.parametrize("t", [1, 5])
def test_latent_trash_pass_next_to_live_slots(rng, t):
    """An inactive slot's rows go to the trash page, a pass of its own
    behind the slot's sweep (masked), next to live slots; the last slot's
    chunk is clipped at the table's end."""
    pos = [_rows_at(PP, 2), _rows_at(PP + 1, 5), _rows_at(2, 0),
           L_NB * L_PAGE - 2]
    case = _latent_case(rng, pos, t=t, active=[False, True, False, True])
    _, pool2 = _assert_latent_matches(case)
    np.testing.assert_array_equal(  # rows pos % page .. of the trash page
        np.asarray(pool2[-1, 0, 0:t, :L_W]), np.asarray(case[4][2, 0]))


def test_latent_bfloat16_pool_and_q(rng):
    """bfloat16 q against a bfloat16 pool: both enter the score product as
    stored; p, the softmax state and the accumulator stay float32 (the
    result differs from the float32 form by q's and the output's rounding
    alone)."""
    pos = [_rows_at(2 * PP + 1, 4), _rows_at(PP, 7), 0]
    _assert_latent_matches(_latent_case(rng, pos, t=2, dtype=jnp.bfloat16),
                           atol=2e-2)


@pytest.mark.parametrize("pp", [1, 2, PP],
                         ids=["a-page-a-pass", "two-pages", "whole-pass"])
@pytest.mark.parametrize("t", [1, 24], ids=["decode", "slice"])
def test_latent_ring_carries_over_slots_and_q_tiles(rng, monkeypatch, pp, t):
    """The ring of passes does not drain between grid steps: decode steps
    over slots of 1 to 2 pp + 2 pages, and a slice (t = 24 x 8 heads: three
    q tiles of 64 rows, scattered by XLA first) whose q tiles each sweep the
    run again; with the budget forced small the same call takes two pages a
    pass, and a page a pass at depth 2 (every other call's plan)."""
    rows = 8 if t == 1 else pa._q_tile(t * 8)
    assert t == 1 or t * 8 // rows == 3
    plan = lambda budget: pa._plan(1, L_PAGE, L_LANES, 4, rows, 1, budget, True)
    budget = next(b for b in (1, *range(10_000, 1_000_000, 10_000),
                              pa._VMEM_BUDGET_BYTES) if plan(b)[2] == pp)
    monkeypatch.setattr(pa, "_VMEM_BUDGET_BYTES", budget)
    assert plan(budget)[:3] == (1, 2, pp)
    pos = [0, _rows_at(2 * PP + 2, 1) - t, 3, _rows_at(PP, 7) - t + 1,
           _rows_at(PP + 1, 0)]
    _assert_latent_matches(_latent_case(rng, pos, t=t))


def test_latent_layer_indexed_stack(rng):
    """The same passes on the layer-stacked pool, the layer as data: the
    layer's pages are read and written IN the stack and the other layers'
    are left as they were."""
    q, pool, tables, pos, new, _ = case = _latent_case(
        rng, [_rows_at(PP + 1, 7), _rows_at(2 * PP, 0)], t=3)
    _, want_pool = _latent_reference(*case)
    stack = jnp.stack([pool * 2, pool, pool * 3])
    out, stack2, _ = paged_decode_attention(
        q, stack, jnp.zeros((3, 1, 1, 8, 128)), tables, pos, new, None, None,
        layer=jnp.int32(1), interpret=True, latent=L_RANK, scale=0.125)
    want, _ = _assert_latent_matches(case)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(stack2[1]), want_pool)
    np.testing.assert_array_equal(np.asarray(stack2[::2]), np.asarray(stack[::2]))


def test_capability_check():
    """The explicit capability contract that replaced the %64 tileability
    gate: any 8-row-aligned page (incl. odd sizes), hd >= 8, 16/32-bit
    pools; f8 and sub-sublane pages route to the gather fallback."""
    assert paged_decode_supported((32, 128), 8)
    assert paged_decode_supported((32, 128), 24)   # old gate: rejected
    assert paged_decode_supported((32, 128), 120)  # old gate: rejected
    assert paged_decode_supported((32, 128), 128, kv_dtype=jnp.float32)
    assert not paged_decode_supported((32, 128), 12)   # not sublane-aligned
    assert not paged_decode_supported((32, 4), 128)    # head dim too small
    assert not paged_decode_supported((32, 128), 128,
                                      kv_dtype=jnp.float8_e4m3fn)


def test_engine_streams_bit_exact_kernel_vs_gather():
    """The serving contract: with the SAME engine construction, routing
    attention through the fused kernel (attn_impl='flash' -> paged_kernel)
    yields BIT-IDENTICAL greedy and sampled token streams to the jnp gather
    route — through the real decode scan, scatter fused and all."""
    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import random_params

    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=96, seq_len=64)
    params = random_params(cfg, seed=3, dtype=jnp.float32, quantize=False)

    def run(attn_impl, spec=0):
        eng = BatchEngine(cfg, params, n_slots=2, cache_dtype=jnp.float32,
                          kv_layout="paged", page_size=8, attn_impl=attn_impl,
                          spec=spec)
        eng.add(0, [1, 2, 3, 4, 5], temperature=0.0, seed=0)
        eng.add(1, [9, 8, 7], temperature=0.7, seed=42)
        if spec:
            toks, counts = eng.spec_step()
            return eng.attn_route, np.asarray(toks), np.asarray(counts)
        return eng.attn_route, np.asarray(eng.decode(10))

    route_g, toks_g = run("jnp")
    route_k, toks_k = run("flash")
    assert (route_g, route_k) == ("paged_gather", "paged_kernel")
    np.testing.assert_array_equal(toks_g, toks_k)
    # batched spec verify (t = k+1 > 1): the fused scatter's multi-row
    # page RMW through the real propose/verify cycle, same emissions
    rg, eg, ag = run("jnp", spec=4)
    rk, ek, ak = run("flash", spec=4)
    assert (rg, rk) == ("paged_gather", "paged_kernel")
    np.testing.assert_array_equal(ag, ak)
    np.testing.assert_array_equal(eg, ek)


@pytest.mark.parametrize("route", ["paged_kernel", "paged_gather", "dense"])
def test_layer_scan_carries_the_pool_only_on_the_kernel_route(route):
    """What rides the layer scan of `forward`, read off its jaxpr (PR 27).
    Kernel route: the whole stacked K and V pools are CARRIED (the kernel
    indexes the layer) and nothing of the pool's shape is an xs or a ys —
    so no layer's slice is cut out of the stack or put back. The gather
    route and the dense layout scan the per-layer slices as xs/ys, as they
    always have: only x is carried."""
    from dllama_tpu.engine.kernel_select import resolve_kernels
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import (KVCache, PagedKVCache, forward,
                                         random_params)
    from dllama_tpu.ops.layers import build_rope_cache
    from dllama_tpu.ops.pallas.paged_attention import pool_lanes

    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=3, n_heads=4,
                      n_kv_heads=2, vocab_size=96, seq_len=64)
    params = random_params(cfg, seed=0, dtype=jnp.float32, quantize=False)
    if route == "dense":
        attn_fn = None
        cache = KVCache.create(cfg, 2, jnp.float32)
    else:
        sel = resolve_kernels(
            cfg, cfg.seq_len, 2, paged=True, page_size=8,
            attn_impl="flash" if route == "paged_kernel" else "jnp")
        assert sel.attn_route == route
        attn_fn = sel.attn_fn
        cache = PagedKVCache.create(
            cfg, 2, 16, 8, jnp.float32, max_blocks=8,
            lanes=pool_lanes(cfg.head_size) if route == "paged_kernel" else 0)
    jaxpr = jax.make_jaxpr(
        lambda p, c, tok, pos: forward(cfg, p, tok, pos, c,
                                       build_rope_cache(cfg, cfg.seq_len),
                                       attn_fn))(
        params, cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros(2, jnp.int32))
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == cfg.n_layers]
    assert len(scans) == 1
    scan = scans[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = lambda vs: [v.aval.shape for v in vs]
    carry = shapes(scan.invars[n_consts:n_consts + n_carry])
    xs = shapes(scan.invars[n_consts + n_carry:])
    ys = shapes(scan.outvars[n_carry:])
    stored = cache.k.shape
    if route == "paged_kernel":
        assert carry.count(stored) == 2 and n_carry == 3  # x, K pool, V pool
        assert stored not in xs and stored not in ys
        assert xs == [(cfg.n_layers,)] and ys == []  # the layer index alone
    else:
        assert n_carry == 1 and stored not in carry  # x alone
        assert xs.count(stored) == 2 and ys == [stored, stored]

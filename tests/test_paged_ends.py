"""The two ends of a paged walk (ISSUE 53): `ops/pallas/paged_attention`
copies a walk's LAST page in by live units of `sub` rows, a windowed walk's
FIRST page from its first live unit on, and writes a new row back as its
`win`-row tile. Interpret-mode parity against the gather reference and
`_paged_cache_update`, bit-equality with the whole-page-copy build of the
same kernel (one unit a page), poisoned pools, and the host's count of the
rows moved (`rows_moved`) against a count unit by unit. Split from
`test_paged_kernel.py`, whose helpers it shares, to keep both files under
the 300 s a tier-1 file may cost."""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models.llama import _paged_cache_update
from dllama_tpu.ops.layers import paged_gqa_attention
from dllama_tpu.ops.pallas import paged_attention as pa
from dllama_tpu.ops.pallas.paged_attention import paged_decode_attention
from tests.test_paged_kernel import _fused_case, _setup

# float32: tiles of 8, units of 16 forced through `_END_COPY_ROWS` (read at
# call time and a static argument of the call, `end_copy`, like the budget)
# over pages of 32; bfloat16: tiles of 16, the default units of 32 over pages
# of 64. `_END_MIN_PAGE_BYTES` is 0 here: the tiny head blocks take units too.

E_NB = 4
ENDS = {"float32": (jnp.float32, 32, 16), "bfloat16": (jnp.bfloat16, 64, 32)}


def _ends_case(rng, monkeypatch, kind, rests, t=1, base=1, active=None,
               hq=4, hkv=2):
    """A fused call whose slots stand `base` whole pages and `rests` rows
    into their tables: (args, win, sub, page)."""
    dtype, page, sub = ENDS[kind]
    monkeypatch.setattr(pa, "_END_COPY_ROWS", sub)
    monkeypatch.setattr(pa, "_END_MIN_PAGE_BYTES", 0)  # tiny head blocks too
    win, got = pa._row_tiles(page, jnp.dtype(dtype).itemsize, 1, (sub, 0))
    assert (win, got) == (32 // jnp.dtype(dtype).itemsize, sub) and sub < page
    pos = [base * page + r for r in rests]
    return (_fused_case(rng, page, E_NB, t, pos, hq, hkv, active, dtype=dtype,
                        hd=128), win, sub, page)


def _whole_page_build(monkeypatch, args, window=None):
    """The same call with one unit a page: every copy the whole page, the
    program before PR 53."""
    page = args[1].shape[2]
    monkeypatch.setattr(pa, "_END_COPY_ROWS", page)
    assert pa._row_tiles(page, args[1].dtype.itemsize, 1, (page, 0))[1] == page
    return paged_decode_attention(*args, interpret=True, window=window)


def _assert_ends_match(monkeypatch, args, window=None, atol=2e-5):
    """Pools bitwise what `_paged_cache_update` leaves (so every row but the
    t written ones is the input's), the output to the gather reference, and
    all three bit for bit what the whole-page-copy build gives."""
    q, kp, vp, tables, pos, nk, nv, active = args
    kp_ref = _paged_cache_update(kp, nk, tables, pos, active)
    vp_ref = _paged_cache_update(vp, nv, tables, pos, active)
    f32 = lambda x: x.astype(jnp.float32)
    want = paged_gqa_attention(f32(q), f32(kp_ref), f32(vp_ref), tables, pos,
                               window)
    got, kp2, vp2 = paged_decode_attention(*args, interpret=True, window=window)
    np.testing.assert_array_equal(np.asarray(kp2, np.float32),
                                  np.asarray(kp_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(vp2, np.float32),
                                  np.asarray(vp_ref, np.float32))
    live = slice(None) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(f32(got))[live],
                               np.asarray(want)[live], atol=atol, rtol=atol)
    got0, *pools0 = _whole_page_build(monkeypatch, args, window)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[live],
                                  np.asarray(got0, np.float32)[live])
    for a, b in zip((kp2, vp2), pools0):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    return got


def _edge_rests(win, sub, page):
    return [0, win - 1, win, sub - 1, sub, page - 1]


@pytest.mark.parametrize("kind", sorted(ENDS))
@pytest.mark.parametrize("base", [0, 2], ids=["one-page-walk", "three-pages"])
def test_last_page_lands_by_live_units(rng, monkeypatch, kind, base):
    """A decode step whose new row is the first of its page, the last of a
    tile, the first of the next, the last of a unit, the first of the next
    and the last of the page, over a walk of one page (first and last at
    once) and of three; the fifth slot is inactive: its row goes to the
    trash page's tile beside the live ones."""
    _, page, sub = ENDS[kind]
    rests = _edge_rests(8 if kind == "float32" else 16, sub, page)
    active = [True] * 4 + [False, True]
    args, *_ = _ends_case(rng, monkeypatch, kind, rests, base=base, active=active)
    _assert_ends_match(monkeypatch, args,
                       atol=1e-2 if kind == "bfloat16" else 2e-5)


@pytest.mark.parametrize("kind", sorted(ENDS))
@pytest.mark.parametrize("window", [5, 24, 40],
                         ids=["one-page", "two-pages", "three-pages"])
def test_window_walk_lands_from_its_first_live_unit(rng, monkeypatch, kind,
                                                    window):
    """A windowed walk's first page is copied from the unit that holds the
    oldest visible row: a window inside one page (first and last page the
    same page: the intersection), over two pages, and with a whole page
    between its ends, the new row on every edge of `_edge_rests`."""
    _, page, sub = ENDS[kind]
    win = 8 if kind == "float32" else 16
    args, *_ = _ends_case(rng, monkeypatch, kind, _edge_rests(win, sub, page),
                          base=2)
    _assert_ends_match(monkeypatch, args, window=window,
                       atol=1e-2 if kind == "bfloat16" else 2e-5)


@pytest.mark.parametrize("kind", sorted(ENDS))
@pytest.mark.parametrize("t", [5, 16])
@pytest.mark.parametrize("window", [None, 24], ids=["global", "window"])
def test_chunk_rows_cross_a_tile_and_a_page(rng, monkeypatch, kind, t, window):
    """A verify chunk (t = 5, 16) that starts three rows before a tile's
    end, a unit's end and a page's end, and on a page's first row; one
    slot's chunk is clipped at the table's end (its rows wrap around the
    last page) and one is inactive: the tiles from the chunk's first row's
    to its last row's of each page are written back, no other row moves."""
    _, page, sub = ENDS[kind]
    win = 8 if kind == "float32" else 16
    rests = [win - 3, sub - 3, page - 3, 0, 2 * page - 2, sub - 3]
    active = [True] * 5 + [False]
    args, *_ = _ends_case(rng, monkeypatch, kind, rests, t=t, base=2,
                          active=active)
    assert int(args[4][4]) + t > E_NB * page  # the clipped one
    _assert_ends_match(monkeypatch, args, window=window,
                       atol=1e-2 if kind == "bfloat16" else 2e-5)


@pytest.mark.parametrize("kind", sorted(ENDS))
@pytest.mark.parametrize("window", [None, 24], ids=["global", "window"])
def test_rows_no_copy_moves_may_hold_anything(rng, monkeypatch, kind, window):
    """The kernel moves the units `walk_ends` names and no other row: with
    NaN in every KEY row past the new one and before the window, and in
    every VALUE row outside the units the walk moves (a dead row INSIDE a
    moved unit meets p = 0 in the value product, where 0 x NaN is NaN: what
    is moved must be finite, as before PR 53 the whole last page), and in
    every page no table holds, the output is the clean pool's bit for bit,
    and the poisoned pools come back with the t new rows and no other
    change."""
    _, page, sub = ENDS[kind]
    win = 8 if kind == "float32" else 16
    args, *_ = _ends_case(rng, monkeypatch, kind, _edge_rests(win, sub, page),
                          base=2)
    q, kp, vp, tables, pos, nk, nv, _ = args
    clean = paged_decode_attention(*args, interpret=True, window=window)[0]
    kbad, vbad = np.array(kp, np.float32), np.array(vp, np.float32)
    held = np.zeros(kp.shape[0], bool)
    for bi, p in enumerate(np.asarray(pos)):
        lo, hi, r0, r1 = pa.walk_ends(np, int(p), 0, 0, page, E_NB, window)
        first = lo * page + r0 // sub * sub
        last = (hi - 1) * page + ((r1 - 1) // sub + 1) * sub
        for blk, pg in enumerate(np.asarray(tables[bi])):
            held[pg] = True
            rows = blk * page + np.arange(page)
            dead = (rows > p) | (rows <= p - window if window else False)
            kbad[pg][:, dead] = np.nan
            vbad[pg][:, (rows < first) | (rows >= last)] = np.nan
    kbad[~held], vbad[~held] = np.nan, np.nan
    kbad[-1], vbad[-1] = 0.0, 0.0  # (the trash page: no slot is inactive)
    bad = (q, jnp.asarray(kbad, kp.dtype), jnp.asarray(vbad, vp.dtype),
           tables, pos, nk, nv, None)
    got, kp2, vp2 = paged_decode_attention(*bad, interpret=True, window=window)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    np.testing.assert_array_equal(
        np.asarray(kp2, np.float32),
        np.asarray(_paged_cache_update(bad[1], nk, tables, pos, None), np.float32))
    np.testing.assert_array_equal(
        np.asarray(vp2, np.float32),
        np.asarray(_paged_cache_update(bad[2], nv, tables, pos, None), np.float32))


@pytest.mark.parametrize("t", [1, 5, 16])
def test_page_that_is_not_whole_tiles_is_copied_and_written_whole(rng, t):
    """24 rows of bfloat16 are a tile and a half: such a page is ONE tile
    and ONE unit (`_row_tiles`), so it is copied in whole, blended over the
    whole page and written back whole, as before PR 53: decode steps and
    verify chunks across its boundary, one slot inactive, one clipped."""
    assert pa._row_tiles(24, 2, 1 << 30) == (24, 24)
    args = _fused_case(rng, 24, 3, t, [0, 23, 40, 70, 9], 4, 2,
                       [True, True, True, True, False], dtype=jnp.bfloat16,
                       hd=128)
    q, kp, vp, tables, pos, nk, nv, active = args
    kp_ref = _paged_cache_update(kp, nk, tables, pos, active)
    vp_ref = _paged_cache_update(vp, nv, tables, pos, active)
    got, kp2, vp2 = paged_decode_attention(*args, interpret=True)
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_array_equal(f32(kp2), f32(kp_ref))
    np.testing.assert_array_equal(f32(vp2), f32(vp_ref))
    want = paged_gqa_attention(*(x.astype(jnp.float32) for x in
                                 (q, kp_ref, vp_ref)), tables, pos)
    np.testing.assert_allclose(f32(got)[:4], f32(want)[:4], atol=1e-2, rtol=1e-2)


def test_slice_of_several_q_tiles_lands_its_ends_by_units(rng, monkeypatch):
    """A prefill slice (several q tiles, scattered by XLA first): each q
    tile's walk ends on the page of its own last query, copied by units,
    and the ring carries over tiles and slots; windowed too."""
    monkeypatch.setattr(pa, "_END_COPY_ROWS", 16)
    monkeypatch.setattr(pa, "_END_MIN_PAGE_BYTES", 0)
    q, kp, vp, tables = _setup(rng, 32, E_NB, b=3, t=40, hq=16, hkv=4, hd=128)
    assert q.shape[1] * 4 > pa._Q_TILE_MAX  # 160 folded rows: 5 q tiles
    pos = jnp.asarray([0, 53, 7], jnp.int32)
    for window in (None, 24):
        want = paged_gqa_attention(q, kp, vp, tables, pos, window)
        got = paged_decode_attention(q, kp, vp, tables, pos, interpret=True,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("page,itemsize,want", [
    (128, 2, (16, 32)), (128, 4, (8, 32)), (64, 2, (16, 32)),
    (24, 4, (8, 24)), (24, 2, (24, 24)), (8, 4, (8, 8)), (16, 2, (16, 16)),
    (48, 2, (16, 16)), (120, 4, (8, 24)),
])
def test_row_tiles_are_a_function_of_page_and_dtype(page, itemsize, want):
    """`win` is the dtype's sublane tile, `sub` the largest whole number of
    tiles up to `_END_COPY_ROWS` that divides the page; a page that is not
    whole tiles of its dtype (24 rows of bfloat16) is one tile and one unit:
    every copy of it is the whole page; so is a page whose head block is
    under `_END_MIN_PAGE_BYTES` (its tile is still the dtype's)."""
    win, sub = pa._row_tiles(page, itemsize)
    assert (win, sub) == want and page % sub == 0 and sub % win == 0
    assert pa._row_tiles(page, itemsize, pa._END_MIN_PAGE_BYTES) == want
    assert pa._row_tiles(page, itemsize, pa._END_MIN_PAGE_BYTES - 1) == (
        win, page)


@pytest.mark.parametrize("name,hq,hkv,lanes,sub", [
    ("deepseek-llm-7b: 32 heads, a 1 MB copy", 32, 32, 128, 32),
    ("granite-4.0-h-micro: 8 heads, 256 KB", 32, 8, 128, 32),
    ("laguna-xs.2 global and window: 8 heads, 256 KB", 48, 8, 128, 32),
    ("smallthinker-21b-a3b: 4 heads, 128 KB: whole pages", 28, 4, 128, 128),
])
def test_cells_decode_calls_copy_their_ends_by_units_or_whole(name, hq, hkv,
                                                              lanes, sub):
    """What the served shapes get (bfloat16 pools, pages of 128): units of
    32 rows where a page's head block is 256 KB or more, the whole page at
    SmallThinker's 4 kv heads; a written tile is 16 rows everywhere."""
    assert pa.decode_tiles(hq, hkv, 128, lanes, 2) == (16, sub), name


@pytest.mark.parametrize("window", [None, 5, 24, 100])
def test_rows_moved_is_the_units_and_the_tile(rng, window):
    """`rows_moved` (what the launch record counts) against a count unit by
    unit: every page of the walk whole but its two ends, those by the units
    that hold a live row, and one tile back."""
    page, nb = 32, 8
    win, sub = pa._row_tiles(page, 4, None, (16, 0))
    pos = rng.integers(0, page * nb, size=200)
    got = pa.rows_moved(pos, page, nb, win, sub, window)
    for p, g in zip(pos, got):
        oldest = 0 if window is None else max(p - window + 1, 0)
        blocks = range(oldest // page, p // page + 1)
        units = sum(
            1 for blk in blocks for u in range(page // sub)
            if (blk not in (blocks[0], blocks[-1]))
            or (blk * page + u * sub <= p and blk * page + (u + 1) * sub > oldest))
        assert g == units * sub + win, (p, window)


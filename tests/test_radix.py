"""Cross-request radix prefix cache tests (ISSUE 9, engine/radix.py).

Three layers:

* tree core over a bare PagePool (no model): insert/walk/split, partial
  boundary matching, LRU + refcount-aware eviction, audit reconciliation
  of tree refs (leaked/duplicate node refs must FAIL the audit);
* engine level: mapping a tree prefix into a slot plus the admission COW
  on divergence inside a shared boundary page;
* scheduler level: BIT-EXACT token streams with the cache on vs off across
  greedy/sampled/penalized/spec and overlap on/off, multi-turn saved-prefill
  accounting, eviction-under-pressure admitting a deferred request, and a
  warm restart dropping the tree cleanly (never stale page refs).

DLLAMA_POOL_AUDIT=1 is armed suite-wide (tests/conftest.py), so every
release in these tests runs the full refcount reconciliation — tree refs
included — making the refcount contract an implicit assertion everywhere.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine.batch import BatchEngine, PagePool, PoolAuditError
from dllama_tpu.engine.radix import RadixCache
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.serve.scheduler import Scheduler

CFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=96, seq_len=64)
PARAMS = random_params(CFG, seed=3, dtype=jnp.float32, quantize=False)


# --------------------------------------------------------------- tree core


def _pool_with_pages(n_pages=16, page=4, slots=2):
    """A bare pool + tree; returns (pool, radix, take) where take(slot, n)
    allocates n fresh pages into `slot`'s table and returns their ids."""
    pool = PagePool(n_pages, page, slots, max_blocks=n_pages)
    radix = RadixCache(pool)

    def take(slot, n):
        start = int(pool.n_blocks[slot])
        pool.grow(slot, (start + n) * page)
        return [int(p) for p in pool.tables[slot, start:start + n]]

    return pool, radix, take


def test_insert_walk_and_miss():
    pool, radix, take = _pool_with_pages()
    toks = list(range(10, 22))  # 3 full pages of 4
    pages = take(0, 3)
    assert radix.insert(toks, pages) == 3
    assert radix.stats()["nodes"] == 1 and radix.stats()["pages"] == 3
    # every tree page took one extra ref on top of the slot's
    assert all(pool.refcount[p] == 2 for p in pages)
    # a prompt extending the inserted prefix maps all 3 pages
    hit = radix.lookup(toks + [77, 78])
    assert hit.rows == 12 and hit.pages == pages and hit.part == 0
    # the cap: at least one token must remain to prefill
    hit = radix.lookup(toks)  # 12 tokens, cap 11 -> 2 full pages + 3 partial
    assert hit.rows == 11 and hit.pages == pages[:2]
    assert hit.part == 3 and hit.boundary == pages[2]
    # unrelated prompt: clean miss
    assert radix.lookup([90, 91, 92, 93, 94]).rows == 0
    assert pool.audit()["ok"]


def test_split_mid_edge_at_page_boundary():
    pool, radix, take = _pool_with_pages()
    a = list(range(1, 13))  # 3 pages
    pages_a = take(0, 3)
    radix.insert(a, pages_a)
    # b shares the first 2 pages, diverges in the third
    b = a[:8] + [60, 61, 62, 63]
    pages_b = pages_a[:2] + take(1, 1)
    radix.insert(b, pages_b)
    # edge split at the page boundary: shared prefix node + two leaves
    st = radix.stats()
    assert st["nodes"] == 3 and st["pages"] == 4
    for toks, page3 in ((a, pages_a[2]), (b, pages_b[2])):
        hit = radix.lookup(toks + [80])
        assert hit.rows == 12 and hit.pages[:2] == pages_a[:2]
        assert hit.pages[2] == page3
    assert pool.audit()["ok"]


def test_partial_boundary_within_first_page():
    """Divergence INSIDE the first page of an edge: no mappable full page,
    but the best child's first page is still offered as a shared boundary
    for the sub-page prefix."""
    pool, radix, take = _pool_with_pages()
    a = [1, 2, 3, 4, 5, 6, 7, 8]
    pages_a = take(0, 2)
    radix.insert(a, pages_a)
    hit = radix.lookup([1, 2, 9, 9, 9])
    assert hit.rows == 2 and hit.pages == [] and hit.part == 2
    assert hit.boundary == pages_a[0]


def test_no_false_boundary_after_mid_edge_divergence():
    """Review regression: a walk that diverges MID-EDGE at a page boundary
    must not fall back to comparing sibling edges — a sibling's first page
    holds KV computed at the PARENT node's depth, and offering it at the
    deeper offset would map position-mismatched rows (silently wrong
    output). The only valid boundary after a mid-edge stop is that edge's
    own next page."""
    pool, radix, take = _pool_with_pages()
    a = [1, 2, 3, 4, 5, 6, 7, 8]  # 2 pages of 4
    radix.insert(a, take(0, 2))
    b = [9, 10, 11, 12]
    radix.insert(b, take(1, 1))
    # matches a's first page, then diverges exactly at the page boundary
    # (part 0 against a's second page); b's (9, 10, ...) page must NOT be
    # offered as a boundary for rows 4-5
    hit = radix.lookup([1, 2, 3, 4, 9, 10, 99, 0])
    assert hit.rows == 4 and hit.part == 0 and hit.boundary is None


def test_fallback_boundary_child_survives_protected_eviction():
    """Review regression: the node-boundary fallback's winning child joins
    hit.path — the scheduler evicts between lookup and radix_map, and the
    page about to be mapped must not land on the free list."""
    pool, radix, take = _pool_with_pages()
    a = [1, 2, 3, 4, 5, 6, 7, 8]
    pa = take(0, 2)
    radix.insert(a, pa)
    pool.free_tail(0, 0)  # the tree is the only referent
    hit = radix.lookup([1, 2, 99])  # sub-page fallback match
    assert hit.part == 2 and hit.boundary == pa[0]
    assert radix.evict(8, protect=hit) == 0
    assert int(pool.refcount[pa[0]]) == 1  # still tree-held, mappable


def test_evict_lru_refcount_aware_and_protected():
    pool, radix, take = _pool_with_pages(n_pages=16)
    a, b, c = ([i + 1, i + 2, i + 3, i + 4] for i in (0, 10, 20))
    pa, pb, pc = take(0, 1), take(0, 1), take(0, 1)
    radix.insert(a, pa)
    radix.insert(b, pb)
    radix.insert(c, pc)
    # drop the slot's own refs: the tree is now the only referent of a/b/c
    pool.free_tail(0, 0)
    # ...except b, which a "live slot" still shares
    pool.adopt_prefix(1, pb)
    nodes = {tuple(n.tokens): n for n in radix._iter_nodes()}
    nodes[tuple(a)].last_used = 1.0   # coldest
    nodes[tuple(b)].last_used = 2.0
    nodes[tuple(c)].last_used = 3.0   # hottest
    hit_c = radix.lookup(c + [99])
    # need 2 pages: a (coldest) goes first; b would be next in LRU order but
    # frees nothing (slot 1 still references it) -> skipped, keeping the
    # cache entry; c is protected as the in-progress admission's match
    freed = radix.evict(2, protect=hit_c)
    assert freed == 1
    left = {tuple(n.tokens) for n in radix._iter_nodes()}
    assert tuple(a) not in left and tuple(b) in left and tuple(c) in left
    assert pool.audit()["ok"]
    # unprotected, with the slot ref gone, b and c are both reclaimable
    pool.free_tail(1, 0)
    assert radix.evict(8) == 2
    assert radix.stats()["nodes"] == 0 and pool.stats()["used"] == 0


def test_audit_fails_on_leaked_and_duplicate_node_refs():
    pool, radix, take = _pool_with_pages()
    toks = [1, 2, 3, 4]
    pages = take(0, 1)
    radix.insert(toks, pages)
    assert pool.audit()["ok"] and pool.audit()["radix_pages"] == 1
    # leaked node ref: the tree forgets a page without dropping its refcount
    node = next(iter(radix._iter_nodes()))
    stolen = node.pages.pop()
    node.tokens = ()
    with pytest.raises(PoolAuditError):
        pool.audit()
    node.pages.append(stolen)
    node.tokens = tuple(toks)
    assert pool.audit(raise_on_fail=False)["ok"]
    # duplicate node ref: the same page entering the tree twice is corrupt
    # even when the refcount is patched to match
    node.pages.append(stolen)
    node.tokens = tuple(toks + [9, 9, 9, 9])
    pool.refcount[stolen] += 1
    report = pool.audit(raise_on_fail=False)
    assert not report["ok"]
    assert any("radix nodes" in p for p in report["problems"])


# ------------------------------------------------------------ engine level


def _engine(radix="on", n_slots=3, kv_pages=0, spec=0):
    return BatchEngine(CFG, PARAMS, n_slots=n_slots, cache_dtype=jnp.float32,
                       kv_layout="paged", page_size=8, kv_pages=kv_pages,
                       spec=spec, radix_cache=radix)


def test_map_then_cow_on_divergence_inside_boundary_page():
    """A mapped partial boundary page is copy-on-written by the admission:
    the tree's page keeps its rows, and the diverged continuation matches
    an engine that never shared anything."""
    eng, solo = _engine(), _engine("off")
    prompt = list(range(1, 17))  # exactly 2 full pages
    for e in (eng, solo):
        e.add(0, prompt, temperature=0.0, seed=0)
    eng.radix_insert(0, prompt)  # adopt both pages (engine API the
    # scheduler drives at commit)
    eng.release(0)
    solo.release(0)
    assert eng.radix_stats()["pages"] == 2
    # diverge at token 12, INSIDE the tree's second page: reuse = 8 full
    # rows + 4 rows of the shared boundary page
    div = prompt[:12] + [70, 71, 72]
    rows, hit = eng.radix_lookup(div)
    assert rows == 12 and hit.part == 4
    tree_page = hit.boundary
    eng.radix_map(1, hit)
    assert int(eng.pool.refcount[tree_page]) == 2  # tree + slot 1
    eng.add(1, div[rows:], temperature=0.0, seed=1, start_pos=rows)
    # prepare_admission copy-on-wrote the shared boundary before the
    # divergent rows were scattered: the tree's page is whole again
    assert int(eng.pool.refcount[tree_page]) == 1
    assert int(eng.pool.tables[1, 1]) != tree_page
    solo.add(1, div, temperature=0.0, seed=1)
    np.testing.assert_array_equal(eng.decode(4)[:, 1], solo.decode(4)[:, 1])
    assert eng.pool.audit()["ok"]


# --------------------------------------------------------- scheduler level


def _sched(radix, overlap=True, n_slots=3, chunk=3, kv_pages=0, spec=0):
    return Scheduler(_engine(radix, n_slots=n_slots, kv_pages=kv_pages,
                             spec=spec), chunk=chunk, overlap=overlap)


_WORK: dict = {}


def _workload(radix, overlap=True, spec=0):
    """Mixed greedy/sampled/penalized workload with a shared system prompt
    and staggered submission; memoized per config (each run costs an engine
    compile inside the time-budgeted tier-1 window)."""
    key = (radix, overlap, spec)
    if key in _WORK:
        return _WORK[key]
    sched = _sched(radix, overlap=overlap, spec=spec)
    try:
        sys_p = list(range(1, 18))  # 17 tokens: 2 full pages + 1
        r1 = sched.submit(sys_p + [30], 0.0, 0.9, 10, frozenset(), seed=1)
        it1 = r1.tokens()
        head = [next(it1), next(it1)]
        r2 = sched.submit(sys_p + [40, 41], 1.1, 0.9, 8, frozenset(), seed=42)
        r3 = sched.submit(sys_p + [50], 0.9, 0.8, 8, frozenset(), seed=7,
                          presence=0.5, frequency=0.3)
        out2, out3 = list(r2.tokens()), list(r3.tokens())
        out1 = head + list(it1)
        _WORK[key] = [(out1, r1.finish_reason), (out2, r2.finish_reason),
                      (out3, r3.finish_reason)]
        return _WORK[key]
    finally:
        sched.shutdown()


def test_bitexact_on_off_mixed_batch():
    """The headline contract: greedy + sampled + penalized streams are
    BIT-IDENTICAL with the radix cache on vs off (reuse changes which rows
    are prefilled vs mapped, never their contents)."""
    assert _workload("on") == _workload("off")


def test_bitexact_on_off_overlap_off():
    assert _workload("on", overlap=False) == _workload("off", overlap=False)
    assert _workload("on", overlap=False) == _workload("on")


def test_bitexact_on_off_with_spec():
    """Spec engines draft from per-slot history; radix_map backfills the
    mapped prefix's tokens so proposals see the same history either way."""
    on = _workload("on", spec=4)
    assert on == _workload("off", spec=4)
    assert on == _workload("on", spec=0)


def test_multi_turn_saved_prefill_and_parity():
    """Turn 2 re-sends the whole conversation: the tree serves the full
    pages of turn 1's rows for free, and the stream matches a cold run."""
    sched = _sched("on", n_slots=2, chunk=4)
    try:
        turn1 = list(range(1, 14))  # 13 tokens
        r1 = sched.submit(turn1, 0.0, 0.9, 6, frozenset(), seed=0)
        gen1 = list(r1.tokens())
        turn2 = turn1 + gen1 + [7, 8]
        fed_rows = len(turn1) + len(gen1) - 1  # last token never fed back
        before = sched.engine.radix_stats()["hit_tokens"]
        r2 = sched.submit(turn2, 0.0, 0.9, 4, frozenset(), seed=0)
        warm = list(r2.tokens())
        saved = sched.engine.radix_stats()["hit_tokens"] - before
        # page-granular reuse: every FULL page of the fed rows maps free
        assert saved == (fed_rows // 8) * 8 > 0
        assert sched.reused_prefix_tokens >= saved
    finally:
        sched.shutdown()
    cold = _sched("off", n_slots=2, chunk=4)
    try:
        r = cold.submit(turn2, 0.0, 0.9, 4, frozenset(), seed=0)
        assert list(r.tokens()) == warm, "radix-mapped rows changed output"
    finally:
        cold.shutdown()


def test_eviction_under_pressure_admits_deferred_request():
    """Capacity composition: tree pages are reclaimable BEFORE a request
    defers — a prompt the free list cannot cover evicts LRU leaves and
    admits instead of parking behind a full pool."""
    sched = _sched("on", n_slots=2, chunk=3, kv_pages=8)  # 64 rows of pool
    try:
        # fill the tree: two disjoint completed prompts -> ~5-6 tree pages
        for base in (1, 40):
            r = sched.submit(list(range(base, base + 17)), 0.0, 0.9, 3,
                             frozenset(), seed=base)
            list(r.tokens())
        assert sched.engine.radix_stats()["pages"] >= 4
        assert sched.engine.pool.free_count < 5
        # 30-token prompt needs 4 pages + reserve: must evict tree leaves
        big = sched.submit(list(range(60, 90)), 0.0, 0.9, 4, frozenset(),
                           seed=9)
        out = list(big.tokens())
        assert big.finish_reason == "length" and len(out) == 4
        assert sched.engine.radix_stats()["evicted_pages"] >= 1
        assert sched.engine.pool.audit()["ok"]
    finally:
        sched.shutdown()


@pytest.mark.parametrize("n", [8, 3])  # to past the boundary / just short of it
def test_decoder_at_a_page_boundary_takes_tree_pages(n):
    """A pool whose spare pages all sit in the radix tree must not freeze a
    decoding slot at its page boundary: the decode top-up evicts LRU leaves
    for it (a decoder outranks a cached prefix, as an admission already
    does). On the 7B cell this was 39% of slot-steps once the step got fast
    enough to dry the pool inside a window (PERF.md section 6, PR 27)."""
    eng = _engine("on", n_slots=2, kv_pages=6)
    done = list(range(1, 18))  # a finished request: 2 full pages to the tree
    eng.add(0, done, temperature=0.0, seed=0)
    assert eng.radix_insert(0, done) == 2
    eng.release(0)
    eng.add(0, list(range(30, 39)), temperature=0.0, seed=0)
    eng.add(1, list(range(50, 59)), temperature=0.0, seed=1)
    assert eng.pool.free_count == 0 and eng.radix_stats()["pages"] == 2
    eng.decode(n)  # rows 9.. : both slots cross row 16 when n == 8
    assert eng.pos.tolist() == [9 + n, 9 + n]  # nobody froze
    assert not eng.page_starved().any()
    # the tree gave exactly what the decoders were short of, no more
    assert eng.radix_stats()["evicted_pages"] == (2 if n == 8 else 0)
    assert eng.pool.audit()["ok"]


def test_warm_restart_drops_tree_resumes_bitexact():
    """A worker crash rebuilds pool + tree from scratch (never stale page
    refs); the tree re-fills from post-restart traffic and the interrupted
    sampled stream resumes bit-exact."""
    from dllama_tpu.utils import faults

    ref_sched = _sched("on", n_slots=2, chunk=3)
    try:
        ref = ref_sched.submit([3, 1, 4, 1, 5, 9, 2, 6, 5], 0.9, 0.9, 12,
                               frozenset(), seed=11)
        want = list(ref.tokens())
    finally:
        ref_sched.shutdown()

    sched = _sched("on", n_slots=2, chunk=3)
    sched.restart_max = 3
    sched.restart_backoff_s = 0.01
    try:
        warm = sched.submit(list(range(1, 12)), 0.0, 0.9, 4, frozenset(),
                            seed=0)
        list(warm.tokens())
        assert sched.engine.radix_stats()["nodes"] >= 1
        inserted_before = sched.engine.radix_stats()["inserted_pages"]
        r = sched.submit([3, 1, 4, 1, 5, 9, 2, 6, 5], 0.9, 0.9, 12,
                         frozenset(), seed=11)
        it = r.tokens()
        got = [next(it)]
        faults.install("scheduler.loop", "raise", times=1)
        got += list(it)
        assert got == want, "resumed stream diverged from uninterrupted run"
        assert sched.health()["restarts"] == 1
        st = sched.engine.radix_stats()
        # cumulative accounting carried across the rebuild; the tree itself
        # restarted empty and only holds post-restart insertions
        assert st["inserted_pages"] >= inserted_before
        assert sched.engine.pool.audit()["ok"]
    finally:
        faults.clear()
        sched.shutdown()


def test_release_reconciles_refcounts_and_drain_audit():
    """After every request finishes, the pool's only references are the
    tree's (slots hand every page back at release); drain's audit passes
    and clearing the tree returns the pool to empty."""
    sched = _sched("on", n_slots=3, chunk=3)
    eng = sched.engine
    try:
        for i in range(3):
            r = sched.submit(list(range(1, 14)) + [60 + i], 0.5, 0.9, 4,
                             frozenset(), seed=i)
            list(r.tokens())
        assert not eng.active.any()
        st = eng.pool.stats()
        radix_pages = eng.radix_stats()["pages"]
        assert st["used"] == radix_pages > 0  # slots empty; tree is the cache
        assert sched.drain(5.0)
    finally:
        sched.shutdown()
    assert eng.pool.audit()["ok"]
    assert eng.radix.clear() == radix_pages
    assert eng.pool.stats()["used"] == 0

"""The expert layer's way from the router's choice to the kernel's tiles and
back (`ops/layers.expert_groups`, `expert_rows`, `moe_ffn`'s grouped route):
the layout against a plain NumPy statement of it at the expert cells' shapes,
what the traced function may not hold (a sort, a loop, a scatter, a lookup
over the padded order), and the grouped route against `dense` for the three
router forms with the device-side counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models.config import HiddenAct, LlamaConfig
from dllama_tpu.ops.layers import expert_groups, expert_rows, expert_tile_rows, moe_ffn
from dllama_tpu.ops.quant import QTensor

# name -> (tokens, choices a token, experts routed over, experts held, fill)
_LAYOUTS = {
    "laguna decode": (24, 8, 256, 64, "uniform"),
    "kimi-linear decode": (48, 8, 256, 64, "uniform"),
    "smallthinker decode": (16, 6, 64, 64, "uniform"),
    "a 256-row slice of a share": (256, 8, 256, 64, "uniform"),
    "a 512-row slice": (512, 6, 64, 64, "uniform"),
    "every row on one expert": (40, 1, 64, 64, "one"),
    "no row held": (24, 8, 256, 64, "none"),
    "the share's sentinel beside held rows": (6, 3, 8, 4, "uniform"),
}


def _choices(name):
    n, k, routed, held, fill = _LAYOUTS[name]
    rng = np.random.default_rng(len(name))
    if fill == "one":
        topi = np.full((n, k), 5)
    else:
        lo = held if fill == "none" else 0  # only other chips' experts
        topi = np.stack([lo + rng.permutation(routed - lo)[:k] for _ in range(n)])
    return np.where(topi < held, topi, held).astype(np.int32), held


def _plain_layout(topi, e, tm):
    """The layout in plain NumPy: the (token, choice) rows of each expert in
    token order, the groups in expert order, each padded to whole tiles."""
    n, k = topi.shape
    flat = topi.reshape(-1)
    sizes = np.bincount(flat[flat < e], minlength=e)
    pos = np.zeros(n * k, np.int64)
    tile_expert, at = [], 0
    for ex in range(e):
        rows = np.flatnonzero(flat == ex)  # ascending: token order
        pos[rows] = at * tm + np.arange(len(rows))
        tiles = -(-len(rows) // tm)
        tile_expert += [ex] * tiles
        at += tiles
    return pos.reshape(n, k), tile_expert, sizes


@pytest.mark.parametrize("name", list(_LAYOUTS))
def test_expert_groups_against_the_plain_layout(name):
    topi, e = _choices(name)
    n, k = topi.shape
    r = n * k
    tm = expert_tile_rows(r * e // _LAYOUTS[name][2], e)
    pos, tile_expert, tile_src, n_live, sizes = map(
        np.asarray, jax.jit(expert_groups, static_argnums=(1, 2))(jnp.asarray(topi), e, tm))
    want_pos, want_tiles, want_sizes = _plain_layout(topi, e, tm)
    held = topi < e
    live = len(want_tiles)
    assert sizes.tolist() == want_sizes.tolist()
    assert int(n_live) == live and len(tile_expert) == len(tile_src) == min(e, r) + r // tm
    assert live <= len(tile_expert)
    # every real row stands once, in its expert's tiles, in token order
    assert (pos[held] == want_pos[held]).all() and (pos[~held] == 0).all()
    assert len(set(pos[held].tolist())) == int(held.sum())
    assert (np.asarray(want_tiles)[pos[held] // tm] == topi[held]).all()
    # the maps: live tiles in order, the dead ones frozen at the last live one
    assert tile_expert[:live].tolist() == want_tiles
    assert tile_src[:live].tolist() == list(range(live))
    last = max(live - 1, 0)
    assert (tile_src[live:] == last).all()
    assert (tile_expert[live:] == (want_tiles[-1] if live else tile_expert[0])).all()
    # the rows the kernel reads: a token's row at each of its positions, and
    # no pad position is one of them
    h = jnp.arange(1, n + 1, dtype=jnp.float32)[:, None] * jnp.ones((1, 128))
    token = np.broadcast_to(np.asarray(h, np.float32)[:, :1], (n, k))
    pad = np.setdiff1d(np.arange(len(tile_src) * tm), pos[held])
    for by_dot in (True, False):  # placed by the dot, gathered
        xs = np.asarray(expert_rows(h, jnp.asarray(pos), jnp.asarray(held),
                                    len(tile_src) * tm, by_dot), np.float32)
        assert (xs[pos[held], 0] == token[held]).all() and (xs[:, 0] == xs[:, -1]).all()
        assert np.isfinite(xs).all() and len(set(xs[pad, 0].tolist())) <= 1


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("name", ["laguna decode", "kimi-linear decode",
                                  "smallthinker decode", "a 512-row slice"])
def test_expert_groups_holds_no_sort_loop_scatter_or_padded_lookup(name):
    topi, e = _choices(name)
    n, k = topi.shape
    tm = expert_tile_rows(n * k * e // _LAYOUTS[name][2], e)
    padded = (min(e, n * k) + n * k // tm) * tm
    layout = jax.make_jaxpr(lambda t: expert_groups(t, e, tm))(jnp.asarray(topi))
    names = [eqn.primitive.name for eqn in _primitives(layout.jaxpr)]
    assert not [p for p in names if p in ("sort", "while", "gather") or "scatter" in p], names
    # the rows: placed with no gather at all, or the one gather of the rows
    # themselves; never an index vector looked up over the padded order
    for by_dot in (True, False):
        rows = jax.make_jaxpr(
            lambda h, pos, held: expert_rows(h, pos, held, padded, by_dot))(
                jnp.zeros((n, 256), jnp.bfloat16), jnp.zeros((n, k), jnp.int32),
                jnp.ones((n, k), bool))
        gathers = []
        for eqn in _primitives(rows.jaxpr):
            name = eqn.primitive.name
            assert name not in ("sort", "while") and "scatter" not in name
            gathers += [eqn.outvars[0].aval] if name == "gather" else []
        assert [(g.shape, g.dtype) for g in gathers] == (
            [] if by_dot else [((padded, 256), jnp.bfloat16)])


@pytest.fixture(scope="module")
def stacks():
    rng = np.random.default_rng(3)
    d, f, e = 256, 256, 8

    def stack(k, n, count):
        one = lambda: QTensor.quantize(
            (rng.standard_normal((k, n)) * 0.05).astype(np.float32))
        layer = lambda: jax.tree.map(lambda *x: jnp.stack(x), *[one() for _ in range(count)])
        return jax.tree.map(lambda *x: jnp.stack(x), layer(), layer())

    return {count: (stack(d, f, count), stack(f, d, count), stack(d, f, count))
            for count in (e, 4)}, rng


_ROUTERS = {
    "softmax": dict(n_experts=8, n_active_experts=3, hidden_act=HiddenAct.RELU),
    "sigmoid + bias": dict(n_experts=8, n_active_experts=3, router_sigmoid=True,
                           routed_scale=2.5),
    "a held share": dict(n_experts=16, n_active_experts=4, router_sigmoid=True,
                         routed_scale=2.5, experts_held=4, expert_offset=8),
}


@pytest.mark.parametrize("rows", [(5, 1), (1, 48), (1, 160)],
                         ids=["a decode step", "a short slice", "a slice of tall tiles"])
@pytest.mark.parametrize("router", list(_ROUTERS))
def test_grouped_route_matches_dense_for_every_router_form(stacks, router, rows):
    ws, rng = stacks
    kw = _ROUTERS[router]
    cfg = LlamaConfig(dim=256, hidden_dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
                      vocab_size=64, seq_len=32, **kw)
    w1, w2, w3 = ws[cfg.experts_held or cfg.n_experts]
    h = jnp.asarray(rng.standard_normal((*rows, cfg.dim)), jnp.bfloat16)
    logits = jnp.asarray(rng.standard_normal((*rows, cfg.n_experts)), jnp.float32)
    bias = (jnp.asarray(rng.standard_normal(cfg.n_experts) * 0.3, jnp.float32)
            if cfg.router_sigmoid else None)
    stats0 = jnp.zeros(5 if cfg.experts_held else 4, jnp.uint32)
    got, stats = moe_ffn(cfg, h, None, w1, w2, w3, impl="grouped", logits=logits,
                         layer=1, stats=stats0, bias=bias)
    want, want_stats = moe_ffn(cfg, h, None, w1, w2, w3, impl="dense", logits=logits,
                               layer=1, stats=stats0, bias=bias)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max()
    assert err <= 0.02 * np.abs(np.asarray(want, np.float32)).max() + 1e-3
    assert stats.tolist() == want_stats.tolist()
    score = np.asarray(logits if bias is None else jax.nn.sigmoid(logits) + bias)
    chosen = np.argsort(-score, axis=-1, kind="stable")[..., :cfg.n_active_experts]
    e = cfg.experts_held or cfg.n_experts
    local = chosen - cfg.expert_offset
    sizes = np.bincount(local[(local >= 0) & (local < e)], minlength=e)
    assert stats.tolist()[:4] == [int(sizes.sum()), int((sizes > 0).sum()), 1, int(sizes.max())]
    assert stats.tolist()[4:] == (
        [rows[0] * rows[1] * cfg.n_active_experts] if cfg.experts_held else [])


@pytest.mark.parametrize("value", [np.nan, np.inf], ids=["a NaN", "an infinity"])
@pytest.mark.parametrize("rows", [(5, 1), (1, 48)],
                         ids=["a decode step (placed)", "a slice (gathered)"])
@pytest.mark.parametrize("router", ["softmax", "a held share"])
def test_a_row_that_is_not_finite_stays_alone_on_the_grouped_route(stacks, router, rows, value):
    """One sequence's poisoned hidden state (engine/batch.nonfinite fails that
    request alone) may not reach another's rows: the others' results are the
    clean run's to the bit, and the poisoned one's is not finite."""
    rng = np.random.default_rng(11)
    cfg = LlamaConfig(dim=256, hidden_dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
                      vocab_size=64, seq_len=32, **_ROUTERS[router])
    w1, w2, w3 = stacks[0][cfg.experts_held or cfg.n_experts]
    h = np.asarray(rng.standard_normal((*rows, cfg.dim)), np.float32)
    bad = 2
    logits = rng.standard_normal((rows[0] * rows[1], cfg.n_experts))
    logits[bad, cfg.expert_offset] += 10  # (of a share: it meets an expert held here)
    logits = jnp.asarray(logits.reshape(*rows, -1), jnp.float32)
    bias = jnp.zeros(cfg.n_experts, jnp.float32) if cfg.router_sigmoid else None
    poisoned = h.reshape(-1, cfg.dim).copy()
    poisoned[bad, 7] = value
    run = lambda x: np.asarray(moe_ffn(
        cfg, jnp.asarray(x.reshape(h.shape), jnp.bfloat16), None, w1, w2, w3, impl="grouped",
        logits=logits, layer=1, bias=bias), np.float32).reshape(-1, cfg.dim)
    clean, got = run(h), run(poisoned)
    others = np.arange(len(clean)) != bad
    assert np.isfinite(clean).all()
    assert (got[others] == clean[others]).all()
    assert not np.isfinite(got[bad]).any()

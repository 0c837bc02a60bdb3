"""SLO & saturation observability tests (ISSUE 7): the sliding-window
quantile estimator against exact sorted-list quantiles on adversarial
streams, the scheduler time ledger's partition invariant (pure state
machine AND through a real scheduler run with faults off), SLO policy
verdicts, the perf aggregator's goodput accounting, the one-definition-site
contract between the live cost model and experiments/hbm_traffic.py, and
the perfdiff regression-gate verdict logic.

Everything except the one real-scheduler run is pure host (no engine, no
compile) — this file sits in conftest's _RUN_FIRST band of the
time-budgeted tier-1 window."""

import math
import random

import jax
import numpy as np
import pytest

from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import perf


class FakeClock:
    """Injectable monotonic clock for deterministic window/ledger tests."""

    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ------------------------------------------------------- window quantiles

ADVERSARIAL_STREAMS = {
    "sorted": list(np.linspace(1.0, 500.0, 500)),
    "reversed": list(np.linspace(500.0, 1.0, 500)),
    "constant": [7.25] * 400,
    "bimodal": [0.001] * 250 + [10.0] * 250,
    "interleaved_bimodal": [0.001, 10.0] * 250,
    "single": [42.0],
}


@pytest.mark.parametrize("name", sorted(ADVERSARIAL_STREAMS))
def test_window_quantiles_match_exact_sorted_list(name):
    """Under the per-slice cap the estimator is EXACT: every queried
    quantile equals numpy.percentile's linear-interpolation answer on the
    full stream, for every adversarial ordering."""
    stream = ADVERSARIAL_STREAMS[name]
    clk = FakeClock()
    w = perf.WindowQuantiles(window_s=60.0, slices=6, cap=1000, now_fn=clk)
    for i, v in enumerate(stream):
        w.observe(v)
        if i % 50 == 49:
            clk.advance(1.0)  # spread across slices, all inside the window
    assert w.count() == len(stream)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        exact = float(np.percentile(stream, q * 100.0))
        got = w.quantile(q)
        assert got == pytest.approx(exact, rel=1e-12, abs=1e-12), (
            f"{name}: q={q} got {got} exact {exact}")
    snap = w.snapshot()
    assert snap["count"] == len(stream)
    for p, q in (("p50", 50), ("p95", 95), ("p99", 99)):
        assert snap[p] == pytest.approx(float(np.percentile(stream, q)),
                                        rel=1e-12, abs=1e-12)


def test_window_quantiles_slide_out_of_window():
    """Samples older than window_s leave the estimate: after the window
    passes, only the recent regime remains."""
    clk = FakeClock()
    w = perf.WindowQuantiles(window_s=60.0, slices=6, cap=128, now_fn=clk)
    for _ in range(100):
        w.observe(1.0)  # old regime
    clk.advance(61.0)
    for _ in range(50):
        w.observe(100.0)  # new regime, old slices expired
    assert w.count() == 50
    assert w.quantile(0.5) == pytest.approx(100.0)
    # empty window after everything expires
    clk.advance(120.0)
    assert w.count() == 0
    assert w.quantile(0.5) is None
    assert w.snapshot()["p99"] is None


def test_window_quantiles_reservoir_bounded_and_sane():
    """Past the cap the slice keeps a bounded uniform reservoir: memory
    stays <= cap per slice and the median of a known distribution stays
    close to truth (unbiased sampling, loose tolerance)."""
    random.seed(1234)
    clk = FakeClock()
    w = perf.WindowQuantiles(window_s=60.0, slices=2, cap=256, now_fn=clk)
    n = 20_000
    for i in range(n):
        w.observe(float(i % 1000))
    assert w.count() == n  # pre-reservoir count is the true count
    assert sum(len(s) for _, s, _ in w._ring) <= 2 * 256
    assert w.quantile(0.5) == pytest.approx(500.0, rel=0.15)


def test_window_quantiles_rejects_nan_and_validates_args():
    w = perf.WindowQuantiles(window_s=10.0)
    w.observe(float("nan"))
    assert w.count() == 0 and w.quantile(0.5) is None
    w.observe(3.0)
    assert w.quantile(0.0) == w.quantile(1.0) == 3.0
    with pytest.raises(ValueError):
        perf.WindowQuantiles(window_s=0.0)
    with pytest.raises(ValueError):
        perf.WindowQuantiles(cap=0)


def test_window_sums_totals_and_span():
    clk = FakeClock()
    s = perf.WindowSums(window_s=60.0, slices=6, now_fn=clk)
    s.add(tokens=5, bytes=100.0)
    clk.advance(30.0)
    s.add(tokens=7)
    t = s.totals()
    assert t == {"tokens": 12.0, "bytes": 100.0}
    # young window rates over its age, never the full window
    assert s.span_s() == pytest.approx(30.0)
    clk.advance(100.0)  # everything expires
    assert s.totals() == {}
    assert s.span_s() == pytest.approx(60.0)  # capped at the window


# ------------------------------------------------------------ time ledger


def test_time_ledger_partitions_wall_time_exactly():
    """The construction invariant, pure: every instant between start() and
    close() lands in exactly one state, so the totals sum to wall time to
    float precision — no 2% needed without a real clock."""
    clk = FakeClock()
    led = perf.TimeLedger(now_fn=clk)
    led.start("idle")
    clk.advance(1.5)
    led.transition("admission")
    clk.advance(0.25)
    led.transition("prefill")
    clk.advance(2.0)
    led.transition("decode_dispatch")
    clk.advance(0.125)
    led.transition("decode_wait")
    clk.advance(3.0)
    led.transition("emit")
    clk.advance(0.5)
    led.transition("idle")
    clk.advance(1.0)
    led.close()
    assert led.totals["idle"] == pytest.approx(2.5)
    assert led.totals["admission"] == pytest.approx(0.25)
    assert led.totals["prefill"] == pytest.approx(2.0)
    assert led.totals["decode_wait"] == pytest.approx(3.0)
    assert sum(led.totals.values()) == pytest.approx(led.wall_s())
    snap = led.snapshot()
    assert snap["covered_s"] == pytest.approx(snap["wall_s"])
    # fractions are display-rounded to 6 places; sum within that precision
    assert sum(snap["fractions"].values()) == pytest.approx(1.0, abs=1e-5)
    # closed ledger: wall frozen even as the clock runs on
    wall = led.wall_s()
    clk.advance(100.0)
    assert led.wall_s() == wall


def test_time_ledger_open_span_poke_and_reentrant_start():
    clk = FakeClock()
    led = perf.TimeLedger(now_fn=clk)
    led.start("idle")
    clk.advance(5.0)
    # snapshot bills the open span without mutating it
    assert led.snapshot()["seconds"]["idle"] == pytest.approx(5.0)
    assert led.totals["idle"] == pytest.approx(0.0)
    led.poke()  # poke DOES bill it (scrape freshness)
    assert led.totals["idle"] == pytest.approx(5.0)
    led.transition("decode_wait")
    clk.advance(1.0)
    led.close()
    wall1 = led.wall_s()
    # warm-restart re-entry: start() again accumulates, never resets
    clk.advance(2.0)  # down between close and restart — outside the ledger?
    led.start("restart_backoff")
    clk.advance(0.5)
    led.transition("idle")
    clk.advance(0.5)
    led.close()
    assert led.totals["decode_wait"] == pytest.approx(1.0)
    assert led.totals["restart_backoff"] == pytest.approx(0.5)
    assert led.wall_s() > wall1
    # NB: wall keeps counting from the FIRST start; the closed gap is the
    # only uncovered span and it reopens the partition — which is why the
    # real scheduler closes only at final worker death, not per restart
    assert led.wall_s() == pytest.approx(sum(led.totals.values()) + 2.0)


def test_time_ledger_rejects_unknown_state():
    led = perf.TimeLedger(now_fn=FakeClock())
    led.start("idle")
    with pytest.raises(ValueError, match="unknown ledger state"):
        led.transition("napping")


def test_time_ledger_feeds_the_counter_family():
    clk = FakeClock()
    led = perf.TimeLedger(counter=ins.SCHEDULER_TIME, now_fn=clk)
    base = {s: ins.SCHEDULER_TIME.labels(state=s).value()
            for s in perf.LEDGER_STATES}
    led.start("idle")
    clk.advance(2.0)
    led.transition("emit")
    clk.advance(4.0)
    led.close()
    assert (ins.SCHEDULER_TIME.labels(state="idle").value() - base["idle"]
            ) == pytest.approx(2.0)
    assert (ins.SCHEDULER_TIME.labels(state="emit").value() - base["emit"]
            ) == pytest.approx(4.0)


# ------------------------------------------------------------- SLO policy


def test_slo_policy_tristate_verdicts():
    p = perf.SloPolicy(ttft_ms=100.0, itl_ms=10.0)
    v = p.verdict(ttft_ms=80.0, itl_ms=12.5)
    assert v["ttft_ok"] is True and v["itl_ok"] is False
    assert v["ok"] is False
    assert v["violated_by_ms"] == {"ttft": None, "itl": 2.5}
    # unmeasured marks are unknowable, not violations
    v = p.verdict(ttft_ms=None, itl_ms=None)
    assert v["ttft_ok"] is None and v["itl_ok"] is None and v["ok"] is True
    # no targets configured: everything passes vacuously
    off = perf.SloPolicy()
    assert not off.enabled()
    assert off.verdict(1e9, 1e9)["ok"] is True


def test_slo_verdict_from_flight_recorder_marks():
    """The /debug/requests/{req_id} postmortem derivation: ITL from
    (e2e - ttft) / (decode_tokens - 1), same as Request.itl_ms."""
    p = perf.SloPolicy(ttft_ms=50.0, itl_ms=20.0)
    v = p.verdict_from_marks(ttft_ms=40.0, e2e_ms=400.0, decode_tokens=10)
    assert v["itl_ms"] == pytest.approx((400.0 - 40.0) / 9)
    assert v["ttft_ok"] is True and v["itl_ok"] is False
    assert v["targets"] == {"ttft_ms": 50.0, "itl_ms": 20.0}
    # a one-token request has no inter-token interval to judge
    v = p.verdict_from_marks(ttft_ms=40.0, e2e_ms=40.0, decode_tokens=1)
    assert v["itl_ok"] is None and "itl_ms" not in v


def test_perf_aggregator_goodput_vs_throughput():
    """Goodput counts only stop/length finishes inside every SLO; the
    violation burn counters move per kind."""
    clk = FakeClock()
    agg = perf.PerfAggregator(slo=perf.SloPolicy(ttft_ms=100.0, itl_ms=50.0),
                              now_fn=clk)
    base_ttft = ins.SLO_VIOLATIONS.labels(kind="ttft").value()
    base_itl = ins.SLO_VIOLATIONS.labels(kind="itl").value()
    # in-SLO success, out-of-SLO success, in-SLO error
    agg.observe_finish(finish_reason="stop", ttft_ms=50.0, itl_ms=10.0,
                       e2e_ms=500.0, tokens=40)
    agg.observe_finish(finish_reason="length", ttft_ms=500.0, itl_ms=10.0,
                       e2e_ms=900.0, tokens=40)
    agg.observe_finish(finish_reason="error", ttft_ms=50.0, itl_ms=10.0,
                       e2e_ms=100.0, tokens=40)
    clk.advance(10.0)
    assert ins.SLO_VIOLATIONS.labels(kind="ttft").value() - base_ttft == 1
    assert ins.SLO_VIOLATIONS.labels(kind="itl").value() - base_itl == 0
    slo = agg.slo_snapshot()
    assert slo["window_finished"] == 3
    assert slo["attainment"] == pytest.approx(2 / 3, abs=1e-4)
    roof = agg.roofline_snapshot()
    # 120 tokens finished, only the in-SLO stop's 40 are goodput
    assert roof["throughput_tok_s"] == pytest.approx(12.0)
    assert roof["goodput_tok_s"] == pytest.approx(4.0)
    win = agg.window_snapshot()
    assert win["ttft"]["count"] == 3 and win["ttft"]["p50"] == 50.0


def _tiny_cost_model():
    return perf.ChunkCostModel(n_layers=2, dim=64, hidden_dim=128, kv_dim=32,
                               head_size=16, n_kv_heads=2, vocab_size=96,
                               seq_len=64, weight_bytes=1_000_000)


def test_aggregator_prices_chunks_against_device_window():
    clk = FakeClock()
    cm = _tiny_cost_model()
    peak = perf.peak_hbm_gbs("TPU v5 lite")
    agg = perf.PerfAggregator(cost_model=cm, peak_gbs=peak, now_fn=clk)
    agg.observe_chunk(occupancy=2, live_rows=10.0, steps=4, tokens=8,
                      device_s=0.25)
    roof = agg.roofline_snapshot()
    expect = cm.step_bytes(2, 10.0) * 4
    assert roof["bytes"] == expect
    # snapshot values are display-rounded (3 / 6 places)
    assert roof["achieved_gbs"] == pytest.approx(expect / 0.25 / 1e9,
                                                 abs=5e-4)
    assert roof["peak_gbs"] == peak
    assert roof["bandwidth_attainment"] == pytest.approx(
        (expect / 0.25) / (peak * 1e9), abs=5e-7)
    # no cost model -> unpriced but still counted
    agg2 = perf.PerfAggregator(peak_gbs=peak, now_fn=clk)
    agg2.observe_chunk(occupancy=2, live_rows=10.0, steps=4, tokens=8,
                       device_s=0.25)
    r2 = agg2.roofline_snapshot()
    assert r2["priced"] is False and "bandwidth_attainment" not in r2
    assert r2["window_chunks"] == 1


def test_peak_table_knows_v5e_with_its_source():
    """The one chip this repo runs on is priced at Google Cloud's published
    "TPU v5e" figure, keyed by the device_kind jax reports for it."""
    assert perf.peak_hbm_gbs("TPU v5 lite") == 819.0
    assert perf.PEAK_HBM_GBS == {"TPU v5 lite": 819.0}


def test_unknown_device_kind_is_unpriced_never_defaulted():
    """A device the table does not list (the CPU backend here) exports no
    peak, no attainment field and no gauge sample — another chip's peak is
    never used as a default."""
    assert perf.peak_hbm_gbs(jax.devices()[0].device_kind) is None
    assert perf.peak_hbm_gbs("TPU v9 imaginary") is None
    clk = FakeClock()
    cm = _tiny_cost_model()
    agg = perf.PerfAggregator(cost_model=cm, peak_gbs=None, now_fn=clk)
    agg.observe_chunk(occupancy=2, live_rows=10.0, steps=4, tokens=8,
                      device_s=0.25)
    roof = agg.roofline_snapshot()
    assert roof["priced"] is False and roof["achieved_gbs"] is None
    assert "peak_gbs" not in roof and "bandwidth_attainment" not in roof
    assert roof["bytes"] > 0 and roof["window_chunks"] == 1  # still counted
    def rendered():
        out: list[str] = []
        ins.BW_ATTAINMENT.render(out)
        return out

    before = rendered()
    agg.refresh_gauges()
    assert rendered() == before  # the gauge gained no sample


def test_cost_model_single_definition_site():
    """experiments/hbm_traffic.batched_step_bytes must price EXACTLY what
    obs/perf.decode_step_bytes prices (the offline tables and the live
    gauge share one formula — the ISSUE 7 no-drift contract)."""
    hbm = pytest.importorskip("experiments.hbm_traffic")
    cfg = hbm.PRESETS["1b"]
    for slots, frac, paged, impl in ((8, 0.5, False, "kernel"),
                                     (32, 1.0, False, "kernel"),
                                     (8, 0.25, True, "kernel"),
                                     (96, 1.0, True, "kernel"),
                                     (8, 0.25, True, "gather"),
                                     (96, 1.0, True, "gather")):
        expect = perf.decode_step_bytes(
            n_layers=cfg.n_layers, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
            kv_dim=cfg.kv_dim, head_size=cfg.head_size,
            n_kv_heads=cfg.n_kv_heads, vocab_size=cfg.vocab_size,
            seq_len=cfg.seq_len, weight_bytes=hbm.q40_weight_bytes(cfg),
            slots=slots, live_rows=frac * cfg.seq_len, paged=paged,
            paged_impl=impl)
        assert hbm.batched_step_bytes(cfg, slots, live_frac=frac, paged=paged,
                                      paged_impl=impl) == expect
    assert hbm.V5E_HBM_GBS == perf.peak_hbm_gbs("TPU v5 lite")
    # the two paged routes price DIFFERENT traffic by design: the gather
    # fallback pays the re-materialized seq_len-row view (write + read, k+v,
    # per layer) the kernel route exists to remove
    kb = hbm.batched_step_bytes(cfg, 8, live_frac=0.25, paged=True,
                                paged_impl="kernel")
    gb = hbm.batched_step_bytes(cfg, 8, live_frac=0.25, paged=True,
                                paged_impl="gather")
    view = (2 * 8 * cfg.n_kv_heads * 2 * cfg.seq_len * cfg.head_size * 2
            * cfg.n_layers)
    table = 4 * 8 * (cfg.seq_len // 128) * cfg.n_layers
    assert gb - kb == view + table


# ------------------------------------------------- real-scheduler invariant


def test_scheduler_ledger_invariant_real_run():
    """ISSUE 7 acceptance: drive a REAL scheduler (tiny engine, faults off,
    default overlap) through a mixed workload and assert the ledger's
    partition invariant — per-state seconds sum to measured loop wall time
    within 2%, every state non-negative, nothing double-counted — plus the
    new tail-latency fields in latency_summary() and a populated roofline
    window."""
    import jax.numpy as jnp

    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import random_params
    from dllama_tpu.serve.scheduler import Scheduler

    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=96, seq_len=64)
    params = random_params(cfg, seed=5, dtype=jnp.float32, quantize=False)
    eng = BatchEngine(cfg, params, n_slots=3, cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=3, slo_ttft_ms=120_000.0,
                      slo_itl_ms=120_000.0)
    try:
        r1 = sched.submit([1, 2, 3], 0.0, 0.9, 10, frozenset(), seed=1)
        r2 = sched.submit([4, 5], 0.8, 0.9, 8, frozenset(), seed=2)
        assert len(list(r1.tokens())) == 10
        assert len(list(r2.tokens())) == 8
        summary = sched.latency_summary()
    finally:
        sched.shutdown()
    # shutdown joined the worker; run()'s finally closed the ledger
    led = sched.ledger.snapshot()
    assert led["state"] is None  # closed
    wall, covered = led["wall_s"], led["covered_s"]
    assert wall > 0
    assert abs(covered - wall) / wall <= 0.02, led
    assert set(led["seconds"]) == set(perf.LEDGER_STATES)
    assert all(v >= 0.0 for v in led["seconds"].values())
    # snapshot values are display-rounded to 6 places; 8 states of rounding
    assert math.fsum(led["seconds"].values()) == pytest.approx(covered,
                                                               abs=1e-5)
    # work happened: the decode path states actually accumulated time
    assert led["seconds"]["decode_wait"] > 0
    assert led["seconds"]["prefill"] > 0
    # tail-latency satellite: p50/p95 ride latency_summary now
    assert summary["ttft_ms_p50"] is not None
    assert summary["ttft_ms_p95"] >= summary["ttft_ms_p50"]
    assert summary["itl_ms_p50"] is not None
    # roofline window saw priced chunks (cost model built by the engine)
    # ... but this host's device (CPU) is not in the peak table, so the
    # window is counted without a rate against another chip's peak
    roof = sched.perf.roofline_snapshot()
    assert roof["window_chunks"] > 0
    assert roof["bytes"] > 0 and roof["device_s"] > 0
    assert roof["priced"] is False and "bandwidth_attainment" not in roof
    # with SLO targets this loose, both requests attained
    slo = sched.perf.slo_snapshot()
    assert slo["attainment"] == 1.0


# ---------------------------------------------------------------- perfdiff


def _perfdiff():
    import experiments.perfdiff as pd
    return pd


def test_perfdiff_self_diff_always_passes():
    pd = _perfdiff()
    rec = {"value": 46.9, "slo": {"ttft_ms_p95": 120.0,
                                  "ledger_residual_frac": 0.001},
           "presets": {"tiny": {"decode_tok_s": 15.7}}}
    v = pd.diff(rec, dict(rec))
    assert v["ok"] and not v["regressions"]
    assert v["checked"] >= 3


def test_perfdiff_catches_directional_regressions():
    pd = _perfdiff()
    old = {"value": 100.0, "slo": {"ttft_ms_p95": 100.0, "agg_tok_s": 50.0}}
    # tok/s halved (higher-better) AND p95 doubled (lower-better)
    new = {"value": 50.0, "slo": {"ttft_ms_p95": 200.0, "agg_tok_s": 50.0}}
    v = pd.diff(old, new)
    assert not v["ok"]
    bad = {r["metric"] for r in v["regressions"]}
    assert bad == {"value", "slo.ttft_ms_p95"}
    # an IMPROVEMENT in each direction never fails
    better = {"value": 200.0, "slo": {"ttft_ms_p95": 10.0,
                                      "agg_tok_s": 60.0}}
    v = pd.diff(old, better)
    assert v["ok"] and len(v["improvements"]) == 3


def test_perfdiff_tolerance_and_scale():
    pd = _perfdiff()
    old = {"value": 100.0}
    within = {"value": 90.0}   # -10% < 15% tolerance
    beyond = {"value": 80.0}   # -20% > 15% tolerance
    assert pd.diff(old, within)["ok"]
    assert not pd.diff(old, beyond)["ok"]
    assert pd.diff(old, beyond, scale=2.0)["ok"]  # 30% tolerance now


def test_perfdiff_ledger_ceiling_is_absolute_and_unscaled():
    pd = _perfdiff()
    old = {"slo": {"ledger_residual_frac": 0.001}}
    ok = {"slo": {"ledger_residual_frac": 0.019}}
    bad = {"slo": {"ledger_residual_frac": 0.05}}
    assert pd.diff(old, ok)["ok"]
    assert not pd.diff(old, bad)["ok"]
    assert not pd.diff(old, bad, scale=10.0)["ok"]  # invariants don't scale


def test_perfdiff_zero_baseline_never_gates():
    """A 0.0 baseline gives relative tolerance nothing to scale by: the
    move is reported (status zero_baseline) but must not fail the gate —
    in either direction."""
    pd = _perfdiff()
    old = {"slo": {"ttft_ms_p95": 0.0}, "value": 0.0}
    new = {"slo": {"ttft_ms_p95": 125.0}, "value": 0.0}
    v = pd.diff(old, new)
    assert v["ok"] and not v["regressions"]
    assert pd.diff(old, dict(old))["ok"]  # zero -> zero self-diff


def test_perfdiff_missing_and_info_fields_never_gate():
    pd = _perfdiff()
    old = {"value": 100.0, "paged": {"tok_s_ratio_paged_dense": 0.9},
           "setup_s": 1.0}
    new = {"value": 100.0, "setup_s": 99.0}  # info field exploded: fine
    v = pd.diff(old, new)
    assert v["ok"]
    assert "paged.tok_s_ratio_paged_dense" in v["only_old"]


def test_perfdiff_accepts_real_bench_wrapper(tmp_path):
    """End-to-end through main(): the committed BENCH_r02.json self-diffs
    to PASS (exit 0) and a synthetically degraded copy FAILS (exit 1) —
    the scripts/perf_gate.sh acceptance, without the subprocess."""
    import json
    import os

    pd = _perfdiff()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "BENCH_r02.json")
    assert pd.main([src, src]) == 0
    with open(src, encoding="utf-8") as f:
        doc = json.load(f)
    doc["parsed"]["value"] *= 0.5
    degraded = tmp_path / "degraded.json"
    degraded.write_text(json.dumps(doc))
    assert pd.main([src, str(degraded)]) == 1
    assert pd.main([src, str(degraded), "--json"]) == 1
    assert pd.main(["/nonexistent.json", src]) == 2


def test_refresh_gauges_drained_window_sets_nan_not_stale():
    """After the sliding window drains, the scrape-time refresh must push
    NaN (Prometheus 'no data'), never leave the last value standing — an
    idle server does not still carry its old p95."""
    clk = FakeClock()
    agg = perf.PerfAggregator(slo=perf.SloPolicy(ttft_ms=100.0),
                              cost_model=_tiny_cost_model(),
                              peak_gbs=perf.peak_hbm_gbs("TPU v5 lite"),
                              now_fn=clk)
    agg.observe_finish(finish_reason="stop", ttft_ms=50.0, itl_ms=5.0,
                       e2e_ms=100.0, tokens=4)
    agg.refresh_gauges()
    g = ins.LATENCY_WINDOW.labels(metric="ttft", quantile="p95")
    assert g.value() == pytest.approx(0.05)
    assert ins.SLO_ATTAINMENT.value() == 1.0
    clk.advance(3600.0)  # everything leaves the window
    agg.refresh_gauges()
    assert math.isnan(g.value())
    assert math.isnan(ins.SLO_ATTAINMENT.value())
    assert math.isnan(ins.BW_ATTAINMENT.value())
    # NaN renders as the exposition grammar's NaN token, not "nan"
    from dllama_tpu.obs import metrics
    assert metrics.format_value(g.value()) == "NaN"


# ------------------------------------------------- process self-metrics


def test_process_gauges_refresh():
    got = ins.refresh_process_gauges()
    assert got["uptime_s"] >= 0.0
    assert got["threads"] >= 1
    assert got["rss_bytes"] > 0  # linux CI: /proc/self/statm exists
    assert ins.PROCESS_THREADS.value() == got["threads"]
    assert ins.PROCESS_RSS.value() == got["rss_bytes"]

"""SLO & saturation observability tests (ISSUE 7): the sliding-window
quantile estimator against exact sorted-list quantiles on adversarial
streams, the scheduler time ledger's partition invariant (pure state
machine AND through a real scheduler run with faults off), SLO policy
verdicts, and the perf aggregator's goodput accounting.

Everything except the one real-scheduler run is pure host (no engine, no
compile): seconds of the tier-1 run."""

import math
import random

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


# ------------------------------------------------- real-scheduler invariant


def test_scheduler_ledger_invariant_real_run():
    """ISSUE 7 acceptance: drive a REAL scheduler (tiny engine, faults off,
    default overlap) through a mixed workload and assert the ledger's
    partition invariant — per-state seconds sum to measured loop wall time
    within 2%, every state non-negative, nothing double-counted — plus the
    new tail-latency fields in latency_summary() and a populated
    throughput/goodput window."""
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
    # both requests finished inside targets this loose: every finished
    # token is goodput, and the window prices nothing but tokens
    roof = sched.perf.roofline_snapshot()
    assert set(roof) == {"throughput_tok_s", "goodput_tok_s"}
    assert roof["goodput_tok_s"] == roof["throughput_tok_s"] > 0
    slo = sched.perf.slo_snapshot()
    assert slo["attainment"] == 1.0


def test_refresh_gauges_drained_window_sets_nan_not_stale():
    """After the sliding window drains, the scrape-time refresh must push
    NaN (Prometheus 'no data'), never leave the last value standing — an
    idle server does not still carry its old p95."""
    clk = FakeClock()
    agg = perf.PerfAggregator(slo=perf.SloPolicy(ttft_ms=100.0),
                              now_fn=clk)
    agg.observe_finish(finish_reason="stop", ttft_ms=50.0, itl_ms=5.0,
                       e2e_ms=100.0, tokens=4)
    clk.advance(1.0)
    agg.refresh_gauges()
    g = ins.LATENCY_WINDOW.labels(metric="ttft", quantile="p95")
    assert g.value() == pytest.approx(0.05)
    assert ins.SLO_ATTAINMENT.value() == 1.0
    assert ins.GOODPUT.value() == ins.THROUGHPUT.value() == pytest.approx(4.0)
    clk.advance(3600.0)  # everything leaves the window
    agg.refresh_gauges()
    assert math.isnan(g.value())
    assert math.isnan(ins.SLO_ATTAINMENT.value())
    # a rate over a drained window is a true zero, not "no data"
    assert ins.GOODPUT.value() == ins.THROUGHPUT.value() == 0.0
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

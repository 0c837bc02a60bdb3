"""The surfaces `benchmark/` takes from the program (PERF.md section 3: "a
refactor keeps them or brings a `benchmark` issue"), held on the CPU: a PR
that breaks one learns it here, not as a refused run on the chip.

The harness's own code is the reader wherever it can be: its command line
(`serve_child.serve_argv`), its client (`loadlib.stream_completion`), its
scraper (`loadlib.prometheus`), its engine drive (`check.engine_side`) and
its cost file for the `capture` block. One tiny model (the harness's
rehearsal size, its own writer) and one server serve every case.
"""

import functools
import glob
import json
import os
import re
import threading

import pytest

from benchmark import check, files, loadlib, serve_child
from benchmark.costs import paged_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL_CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))
#: where the harness names a Prometheus family it reads
METRIC_READERS = ("metrics", "reducers", "costs", "run.py")


def read_json(path):
    with open(path) as f:
        return json.load(f)


def scanned_metric_families() -> list:
    """Every `dllama_*` family the harness names, by scanning it (a new
    metric file adds its own case). A program name on the device plane
    (`jit_dllama_decode`) and the package's name are not families."""
    names = set()
    for entry in METRIC_READERS:
        path = os.path.join(BENCH, entry)
        paths = ([path] if os.path.isfile(path) else
                 glob.glob(os.path.join(path, "*.json"))
                 + glob.glob(os.path.join(path, "*.py")))
        for p in paths:
            with open(p) as f:
                names.update(re.findall(r"(?<![a-z_])dllama_[a-z_]+", f.read()))
    return sorted(names - {"dllama_tpu"})


# ------------------------------------------------------ the serve flags


#: flag -> (argparse dest, the value the harness passes for a configuration)
SERVE_FLAGS = {
    "--slots": ("slots", lambda c: int(c["serve"]["slots"])),
    "--max-seq-len": ("max_seq_len", lambda c: int(c["max_position_embeddings"])),
    "--page-size": ("page_size", lambda c: int(c["serve"]["page_size"])),
    "--kv-pages": ("kv_pages", lambda c: int(c["serve"]["kv_pages"])),
    "--warmup": ("warmup", lambda c: "auto"),
    "--cache-dtype": ("cache_dtype", lambda c: "f8"),  # the check's control
    "--port": ("port", lambda c: 9471),
}


@pytest.mark.parametrize("flag", sorted(SERVE_FLAGS))
def test_serve_flag_parses_as_the_harness_passes_it(flag):
    from dllama_tpu.cli.main import build_parser

    dest, expected = SERVE_FLAGS[flag]
    assert CELL_CONFIGS
    for path in CELL_CONFIGS:
        config = read_json(path)
        argv = serve_child.serve_argv(config, "m.m", "t.t", 9471,
                                      ["--cache-dtype", "f8"])
        assert flag in argv, (flag, path)
        args = build_parser().parse_args(argv)
        assert args.mode == "serve"
        assert getattr(args, dest) == expected(config), (flag, path)


# ------------------------------------------- one tiny model, one server


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The harness's rehearsal configuration, written by its own writer and
    loaded as the CLI loads it for `serve_argv`'s flags."""
    import jax.numpy as jnp

    from dllama_tpu.cli.main import build_parser
    from dllama_tpu.engine.loader import load_model

    config = read_json(os.path.join(BENCH, "tests", "tiny-llama.json"))
    out = tmp_path_factory.mktemp("surface")
    model, tok, _ = files.write_files(config, 3, str(out))
    args = build_parser().parse_args(
        serve_child.serve_argv(config, model, tok, 0, []))
    loaded = load_model(model, tok, max_seq_len=args.max_seq_len, mesh=None,
                        cache_dtype=jnp.bfloat16)
    return {"config": config, "args": args, "loaded": loaded, "dir": str(out)}


@pytest.fixture(scope="module")
def served(tiny):
    """The server `serve_argv`'s flags build (warm-up left off: it is
    minutes of CPU compiles and no surface), one streamed completion by the
    harness's client inside a profiler capture, then every document the
    harness fetches. The profiler is stubbed: the capture block is counter
    deltas between its begin and end, whatever the profiler wrote."""
    from dllama_tpu.serve.api import make_server
    from dllama_tpu.utils import profiling

    args = tiny["args"]
    httpd, api = make_server(tiny["loaded"], host="127.0.0.1", port=0,
                             n_slots=args.slots, kv_layout=args.kv_layout,
                             page_size=args.page_size, kv_pages=args.kv_pages)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    get = lambda path: loadlib.http_json("127.0.0.1", port, "GET", path)
    rec = loadlib.Record(
        shape=loadlib.Shape(prompt="abcdefgh", prompt_tokens=9, max_tokens=6),
        t_due=0.0)
    try:
        docs = {"/health/ready": get("/health/ready")}
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(profiling.jax.profiler, "start_trace",
                       lambda log_dir, **kw: None)
            mp.setattr(profiling.jax.profiler, "stop_trace", lambda: None)
            profile = loadlib.http_json(
                "127.0.0.1", port, "POST", "/debug/profile",
                {"dir": os.path.join(tiny["dir"], "trace"),
                 "duration_s": 30.0})
            try:
                loadlib.stream_completion("127.0.0.1", port, rec,
                                          threading.Event())
            finally:
                profiling._profiler_end()  # the capture's timer, early
        docs.update((path, get(path)) for path in
                    ("/health", "/metrics", "/debug/perf", "/debug/compile",
                     "/debug/kv"))
        yield {"docs": docs, "profile": profile, "rec": rec,
               "metrics": loadlib.prometheus(docs["/metrics"][1])}
    finally:
        api.scheduler.shutdown()
        httpd.shutdown()


def _health(s, tiny):
    st, doc = s["docs"]["/health"]
    assert st == 200
    # what run.py's `ready` line quotes
    assert "kernels" in doc["build"]
    assert doc["model_params_bytes"] > 0 and doc["kv_cache_bytes"] > 0


def _ready(s, tiny):
    assert s["docs"]["/health/ready"][0] == 200


def _metrics(s, tiny):
    st, text = s["docs"]["/metrics"]
    assert st == 200 and isinstance(text, str)
    assert s["metrics"]["dllama_requests_finished_total"] >= 1


def _perf(s, tiny):
    st, doc = s["docs"]["/debug/perf"]
    assert st == 200
    cap = doc["capture"]
    # what the accepted cells' cost files read, and since PR 36 beside it
    # what the windowed attention's and the expert layer's read
    assert {"launches", "slot_steps", "kv_rows", "prefill_rows", "seconds",
            "kv_rows_read", "moe_experts_touched", "moe_layer_steps",
            "moe_assignments"} <= set(cap)
    assert cap["seconds"] > 0 and cap["slot_steps"]["advanced"] >= 5
    # the harness's streams are greedy: the capture's launches ran the
    # sampler's argmax body alone (ISSUE 52)
    assert cap["sampler_launches"]["greedy"] >= 1
    assert not cap["sampler_launches"].get("nucleus")
    # the reader of the block: rows a decode step swept, for its cost file
    assert paged_attention.rows_per_step(cap, tiny["args"].slots) > 0
    # what host clocks are right for stays beside it, under its names (the
    # router federates `roofline`, `slo` and `window`)
    assert set(doc["roofline"]) == {"throughput_tok_s", "goodput_tok_s"}
    assert doc["roofline"]["goodput_tok_s"] > 0
    assert {"window", "slo", "ledger"} <= set(doc)
    # the latent sweep's plan is named where an engine has one (PR 48)
    assert doc["paged_latent_plan"] is None


def _compile(s, tiny):
    st, doc = s["docs"]["/debug/compile"]
    assert st == 200
    assert "warmup" in doc
    assert any(t["compiles"] > 0 for t in doc["totals"].values())


def _kv(s, tiny):
    st, doc = s["docs"]["/debug/kv"]
    assert st == 200 and doc["audit"]["ok"] is True


def _profile(s, tiny):
    st, doc = s["profile"]
    assert st == 200
    assert doc["profiling"]["dir"] == os.path.join(tiny["dir"], "trace")


def _completion(s, tiny):
    rec = s["rec"]
    assert rec.status == 200 and rec.error is None and rec.done
    assert rec.finish == "length"
    # frames carry their token ids (`include_token_ids`): exact counts
    assert 1 <= sum(k for _, k in rec.events) <= rec.shape.max_tokens
    assert rec.timings["decode_tokens"] == rec.shape.max_tokens
    assert rec.timings["queue_wait_ms"] >= 0


ENDPOINTS = {"/health": _health, "/health/ready": _ready,
             "/metrics": _metrics, "/debug/perf": _perf,
             "/debug/compile": _compile, "/debug/kv": _kv,
             "POST /debug/profile": _profile, "/v1/completions": _completion}


@pytest.mark.parametrize("endpoint", sorted(ENDPOINTS))
def test_endpoint_answers_what_the_harness_reads(endpoint, served, tiny):
    ENDPOINTS[endpoint](served, tiny)


@pytest.mark.parametrize("family", scanned_metric_families())
def test_metric_family_the_harness_names_is_exported(family, served):
    """After one request `/metrics` declares the family and carries it as
    the harness's own parser reads it (a histogram by its `_sum` / `_count`).
    Only a labelled counter none of whose series has moved may have no
    sample: `run.py` reads that absence as 0 (restarts, audit failures)."""
    declared = re.sub(r"_(sum|count)$", "", family)
    kind = re.search(rf"^# TYPE {declared} (\w+)$",
                     served["docs"]["/metrics"][1], re.M)
    assert kind, f"{declared} is not declared by /metrics"
    assert family in served["metrics"] or kind.group(1) == "counter"


#: the per-layer metrics that read the host's launch cycle (ISSUE 40):
#: `ratio_of_deltas` over series named WITH their labels
HOST_METRICS = ["commit_host_ms_per_commit", "device_wait_ms_per_launch",
                "emit_us_per_token", "host_gap_ms_per_launch",
                "host_work_ms_per_launch", "pipeline_drain_share",
                # ISSUE 52: the launches by the sampler body they ask for
                "sampler_greedy_launch_share"]


@pytest.mark.parametrize("metric", HOST_METRICS)
def test_host_metric_reads_series_the_program_exports(metric, served):
    """Every family AND label a new metric file's `params` name is a sample
    of the program's scrape as the harness's own parser keys it, and the
    harness's reducer turns the scrape into a number."""
    from benchmark.reducers import ratio_of_deltas

    spec = read_json(os.path.join(BENCH, "metrics", metric + ".json"))
    assert spec["reducer"] == "ratio_of_deltas"
    for key in spec["params"]["num"] + spec["params"]["den"]:
        assert key in served["metrics"], f"{metric}: no sample {key!r}"
    value = ratio_of_deltas.reduce(
        spec["params"], {"before": {"metrics": {}},
                         "after": {"metrics": served["metrics"]},
                         "config": {}})
    assert value is not None and value >= 0.0


def test_kv_rows_moved_metric_reads_what_the_paged_kernels_engine_counts():
    """`kv_rows_moved_per_needed` (ISSUE 53): on the paged kernel's route a
    decode launch moves `dllama_launch_kv_rows_moved_total` beside
    `dllama_launch_kv_rows_total`, the harness's parser keys both as the
    metric file names them, and the harness's reducer gives the ratio the
    kernel's own definition gives for the launch's positions; a scrape
    without the counter (the gather route of `served`) reads 0, which the
    metric file says is no measurement."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reducers import ratio_of_deltas
    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import random_params
    from dllama_tpu.obs import metrics
    from dllama_tpu.ops.pallas import paged_attention as pa

    spec = read_json(os.path.join(BENCH, "metrics",
                                  "kv_rows_moved_per_needed.json"))
    assert spec["reducer"] == "ratio_of_deltas"
    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=96, seq_len=256)
    eng = BatchEngine(cfg, random_params(cfg, seed=3, dtype=jnp.float32,
                                         quantize=False),
                      n_slots=2, cache_dtype=jnp.float32, kv_layout="paged",
                      page_size=64, attn_impl="flash")
    # (float32 tiles of 8 rows; 2 heads x 64 rows are far under the bytes
    # that pay an end's units, so every copy in is the page)
    assert eng.attn_route == "paged_kernel" and eng._paged_rows == (64, 4, 8, 64)
    eng.add(0, list(range(1, 40)), temperature=0.0, seed=0)
    eng.add(1, [9, 8, 7], temperature=0.0, seed=1)
    before = {"metrics": loadlib.prometheus(metrics.render())}
    start = eng.pos.copy()
    eng.decode(4)
    after = {"metrics": loadlib.prometheus(metrics.render())}
    for key in spec["params"]["num"] + spec["params"]["den"]:
        assert key in after["metrics"], f"no sample {key!r}"
    value = ratio_of_deltas.reduce(spec["params"], {
        "before": before, "after": after, "config": {}})
    at = (start[:, None] + np.arange(4)[None]).ravel()
    want = 100.0 * pa.rows_moved(at, 64, 4, 8, 64).sum() / (at + 1).sum()
    assert value == pytest.approx(want) and 100.0 < value < 400.0


def test_idle_by_phase_reads_the_capture_block_the_program_writes(served):
    """`idle_by_phase_ms_per_launch`'s host tables: the capture block's
    seconds by state and phase, drains, waits and host gap, and the
    window's from the scrape, under the names the reducer reads."""
    from benchmark.reducers import trace_idle_by_phase
    from dllama_tpu.obs import perf

    spec = read_json(os.path.join(BENCH, "metrics",
                                  "idle_by_phase_ms_per_launch.json"))
    assert spec["reducer"] == "trace_idle_by_phase"
    scrape = {"metrics": served["metrics"],
              "perf": served["docs"]["/debug/perf"][1]}
    tables = trace_idle_by_phase.host_tables(
        {"before": {"metrics": {}}, "after": scrape, "t0": 0.0, "t1": 1.0})
    for where in ("capture", "window"):
        t = tables[where]
        assert t["launches"] >= 2
        assert set(t["state_ms_per_launch"]) <= set(perf.LEDGER_STATES)
        assert t["state_ms_per_launch"]["decode_wait"] > 0
        assert set(t["phase_ms_per_launch"]) <= set(perf.PHASES)
        assert {"dispatch.call", "consume.wait", "emit.scan",
                "commit.activate"} <= set(t["phase_ms_per_launch"])
        assert set(t["drains"]) <= set(perf.DRAIN_REASONS)
        assert sum(t["launch_waits"].values()) >= 1
        assert t["host_gap_ms_per_launch"] >= 0.0
    # the phases under each state, beside the ledger's seconds
    ledger = scrape["perf"]["ledger"]
    assert set(ledger["phases"]) <= set(ledger["seconds"])


# -------------------------------------- the loaded model and the engine


@pytest.mark.parametrize("path", ["config", "engine.params",
                                  "engine.cache.k.dtype", "engine.seq_len"])
def test_loaded_model_has_what_the_check_reads(path, tiny):
    import jax

    value = functools.reduce(getattr, path.split("."), tiny["loaded"])
    if path == "config":
        assert value.vocab_size == tiny["config"]["vocab_size"]
    elif path == "engine.params":
        assert jax.tree_util.tree_leaves(value)
    elif path == "engine.cache.k.dtype":
        assert value == "bfloat16"  # the program's default, no --cache-dtype
    else:
        assert value == tiny["args"].max_seq_len


@pytest.fixture(scope="module")
def batch_engine(tiny):
    """Built as `check.engine_side` builds it."""
    from dllama_tpu.engine.batch import BatchEngine

    loaded = tiny["loaded"]
    return BatchEngine(loaded.config, loaded.engine.params,
                       cache_dtype=loaded.engine.cache.k.dtype,
                       max_seq_len=loaded.engine.seq_len,
                       **serve_child.engine_kwargs(tiny["config"]))


@pytest.mark.parametrize("name", ["add_begin", "add_step", "add_commit",
                                  "decode", "release"])
def test_batch_engine_method_the_check_calls(name, batch_engine):
    assert callable(getattr(batch_engine, name))


def test_batch_engine_names_its_backend(batch_engine):
    assert batch_engine.backend in ("pallas", "xla")


def test_batch_engine_names_its_attention_route(batch_engine, tiny):
    from dllama_tpu.engine.kernel_select import PAGED_ROUTES

    assert batch_engine.attn_route in PAGED_ROUTES
    route = f"{batch_engine.backend}/{batch_engine.attn_route}"
    assert route == tiny["config"]["expect"]["route"]


def test_check_drives_the_engine_end_to_end(tiny):
    """`check.engine_side` itself, short: prefill, commit, decode, release
    keeping rows, a tail chunk behind them, `Admission.logits` on the way."""
    config = tiny["config"]
    prompts, tails = check.sample_prompts(3, config["vocab_size"], [9, 40], 7)
    got = check.engine_side(tiny["loaded"], serve_child.engine_kwargs(config),
                            prompts, tails, decode_steps=8)
    assert got["route"] == config["expect"]["route"]
    assert got["decoded"].shape == (8, 2)
    assert [len(s) for s in got["sequences"]] == [9 + 1 + 8 + 7, 40 + 1 + 8 + 7]
    assert all(r.shape == (config["vocab_size"],)
               for r in got["prefill_rows"] + got["tail_rows"])


# ------------------------------------------- the Q40 kernel's traced call


@pytest.mark.parametrize("tier,m,k,n,layers", [
    ("_blockdot_call", 16, 512, 256, 3), ("_blockdot_call", 16, 256, 384, 1),
    # `q40_deq_roofline`: 48 slots (an n of 67 x 128, as Granite's in_proj) and a slice
    ("_deq_call", 48, 256, 8576, 2), ("_deq_call", 256, 1280, 512, 1)])
def test_q40_call_parses_as_the_cost_file_reads_it(tier, m, k, n, layers):
    """`q40_matmul_roofline` prices every traced `_blockdot_call`, and
    `q40_deq_roofline` every `_deq_call`, from the call's HLO text: a result
    f32[m, n] and the FIRST u8 operand, the packed array u8[layers, k/2, n]
    with the same n (`benchmark/costs/q40_matmul.py`; one call that does not
    parse turns the metric to null). Lowered for the TPU here, no chip and no
    compile: the custom call's own line."""
    import jax
    import jax.numpy as jnp

    from benchmark.costs import q40_matmul as cost
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    S = jax.ShapeDtypeStruct
    args = (S((1,), jnp.int32), S((m, k), jnp.bfloat16),
            S((layers, k // 2, n), jnp.uint8), S((layers, k // 32, n), jnp.uint16))
    call = getattr(qmod, tier)
    assert call.__name__ == tier  # the op's group
    hlo = jax.jit(lambda *a: call(*a)).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo")
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1, hlo
    assert cost.calls({}, {"hlo": calls[0]}) == cost.cost(m, k, n)


# ------------------------- the grouped expert kernel and the windowed sweep


@pytest.mark.parametrize("tm,tiles,k,n,layers,experts", [
    (16, 10, 256, 384, 2, 8), (32, 12, 512, 256, 1, 8)])
def test_expert_call_parses_as_the_cost_file_reads_it(tm, tiles, k, n, layers,
                                                      experts):
    """`moe_expert_roofline` finds the grouped expert kernel by the device
    op's group `_expert_call` and reads (experts, k, n) from the call's HLO
    text: the result f32[rows, n] and the 4-D packed operand u8[layers,
    experts, k/2, n] (`benchmark/costs/moe_experts.py`); the bytes come from
    the capture's device-side counts, never from all the experts."""
    import jax
    import jax.numpy as jnp

    from benchmark.costs import moe_experts as cost
    from dllama_tpu.ops.pallas import q40_matmul as qmod

    S = jax.ShapeDtypeStruct
    i32 = lambda n_: S((n_,), jnp.int32)
    args = (i32(1), i32(tiles), i32(tiles), i32(1), S((tiles * tm, k), jnp.bfloat16),
            S((layers, experts, k // 2, n), jnp.uint8),
            S((layers, experts, k // 32, n), jnp.uint16))
    assert qmod._expert_call.__name__ == "_expert_call"  # the op's group
    hlo = jax.jit(lambda *a: qmod._expert_call(*a, tm=tm)).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo")
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1, hlo
    assert cost.shape({"hlo": calls[0]}) == (experts, k, n)
    config = {"moe_num_primary_experts": experts}
    capture = {"moe_layer_steps": {"": 4.0}, "moe_experts_touched": {"": 20.0},
               "moe_assignments": {"": 48.0}}
    assert cost.calls(config, {"hlo": calls[0]}, capture) == cost.cost(5.0, 12.0, k, n)
    # never all of them on a guess: no counts, or impossible ones, price nothing
    assert cost.calls(config, {"hlo": calls[0]}, {}) is None
    assert cost.calls(config, {"hlo": calls[0]},
                      dict(capture, moe_experts_touched={"": 40.0})) is None


@pytest.mark.parametrize("window,group", [(None, "_paged_folded"),
                                          (16, "_paged_window")])
def test_paged_calls_of_both_pools_parse_as_the_cost_file_reads_them(window, group):
    """`swa_attn_roofline` tells a windowed layer's sweep from a global
    one's by the device op's group and prices each from its own pool's rows
    (`benchmark/costs/paged_attention_window.py`, capture keys "kind,pool")."""
    import jax
    import jax.numpy as jnp

    from benchmark.costs import paged_attention_window as cost
    from dllama_tpu.ops.pallas.paged_attention import paged_decode_attention

    slots, hkv, hq, hd, page, pages = 4, 2, 4, 128, 8, 9
    S = jax.ShapeDtypeStruct
    pool = S((3, pages, hkv, page, hd), jnp.bfloat16)
    new = S((slots, hkv, 1, hd), jnp.bfloat16)

    def call(q, k, v, tables, pos, nk, nv):
        return paged_decode_attention(q, k, v, tables, pos, nk, nv, None,
                                      layer=jnp.int32(1), window=window)

    hlo = jax.jit(call).trace(
        S((slots, 1, hq, hd), jnp.bfloat16), pool, pool,
        S((slots, 6), jnp.int32), S((slots,), jnp.int32), new, new).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo")
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    # the compiled instruction takes the jit's name (tests/test_chip_compile.py
    # holds both on the compiled programs)
    from dllama_tpu.ops.pallas import paged_attention as pmod

    assert getattr(pmod, group).__name__ == group
    config = {"serve": {"slots": slots}, "num_key_value_heads": hkv,
              "num_attention_heads": hq, "head_dim": hd}
    capture = {"launches": {"decode": 2.0}, "slot_steps": {"advanced": 32.0},
               "kv_rows_read": {"decode,global": 800.0, "decode,window": 512.0}}
    op = {"hlo": calls[0], "group": group}
    rows = {"_paged_folded": 100.0, "_paged_window": 64.0}[group]
    assert cost.calls(config, op, capture) == cost.base.cost(
        rows, slots, hq, hkv, hd, 2)
    assert cost.calls(config, op, {"launches": {"decode": 2.0},
                                   "slot_steps": {"advanced": 32.0}}) is None


_DECODE_OPS = [  # device-plane texts of the compiled decode program (v5e, 16 slots)
    ("_expert_call", "%_expert_call.73 = f32[1120,768]{1,0} custom-call(s32[1]{0} %a, s32[70]{0} %b, "
     "s32[70]{0} %c, s32[1]{0} %d, bf16[1120,2560]{1,0} %fusion.374, u8[24,64,1280,768]{3,2,1,0} %w, "
     "u16[24,64,80,768]{3,2,1,0} %s)", True),
    ("multiply_convert_fusion", "%multiply_convert_fusion.5 = bf16[1120,768]{1,0} fusion(f32[1120,768]{1,0} "
     "%_expert_call.72, f32[1120,768]{1,0} %_expert_call.73)", True),
    ("fusion", "%fusion.309 = f32[16,1,64]{0,2,1} fusion(bf16[16,2560]{1,0} %fusion.307, "
     "f32[24,2560,64]{2,1,0} %gate, s32[] %layer)", True),
    ("sort", "%sort.140 = (f32[16,1,64]{0,2,1}, s32[16,1,64]{0,2,1}) sort(f32[16,1,64]{0,2,1} %fusion.350, "
     "s32[16,1,64]{0,2,1} %iota.419)", True),
    ("divide_bitcast_fusion", "%divide_bitcast_fusion.5 = f32[16,6]{0,1} fusion(f32[16,1,64]{0,2,1} %g, "
     "f32[16]{0} %f, f32[16]{0} %r)", True),
    ("subtract_add_fusion", "%subtract_add_fusion.5 = s32[96]{0} fusion(s32[96]{0} %sort.144, s32[96]{0} %f1, "
     "s32[96]{0} %f2)", True),
    ("broadcast_minimum_fusion", "%broadcast_minimum_fusion.5 = s32[70]{0} fusion(s32[70]{0} %while.133)", True),
    ("multiply_reduce_fusion", "%multiply_reduce_fusion.5 = bf16[16,2560]{1,0} fusion(f32[16,6,2560]{2,1,0} "
     "%reshape.3335, f32[16,6]{1,0} %copy.323)", True),
    # rope tables at a head size of 128 are [rows, 64] too: not the router's
    ("subtract_convert_fusion", "%subtract_convert_fusion.4 = (bf16[16,1,28,64,1]{0,4,3,2,1}, "
     "bf16[16,1,28,64,1]{0,4,3,2,1}) fusion(f32[16,1,28,64,2]{0,4,3,2,1} %bitcast.1537, f32[16,64]{0,1} %cos, "
     "f32[16,64]{0,1} %sin)", False),
    ("while", "%while.318 = (s32[], bf16[16,1,2560]{2,0,1}, f32[24,2560,64]{2,1,0}, s32[96]{0}) "
     "while((s32[], bf16[16,1,2560]{2,0,1}, f32[24,2560,64]{2,1,0}, s32[96]{0}) %tuple.4)", False),
    ("_blockdot_call", "%_blockdot_call.12 = f32[16,3584]{1,0} custom-call(bf16[16,2560]{1,0} %x, "
     "u8[24,1280,3584]{2,1,0} %w)", False),
    ("_paged_window", "%_paged_window.3 = bf16[16,28,128]{2,1,0} custom-call(bf16[16,28,128]{2,1,0} %q, "
     "bf16[18,593,4,128,128]{4,3,2,1,0} %k)", False),
]


@pytest.mark.parametrize("tm,slice_rows", [(16, None), (32, 512), (32, 200)])
def test_expert_layer_ops_are_found_by_sizes_read_from_the_capture(tm, slice_rows):
    """`moe_busy_share` names no row count: the padded orders and tile maps
    come from the `_expert_call` instructions the capture holds, so a slice
    of another length (a prompt's last one) or another tile height is found
    by itself; rope's [rows, 64] tables are not the router's."""
    from benchmark.reducers import trace_kernel_layer_share as share

    metric = read_json(os.path.join(BENCH, "metrics", "moe_busy_share.json"))
    config = read_json(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json"))
    ops = [{"group": g, "hlo": h, "seconds": 1.0, "want": w} for g, h, w in _DECODE_OPS]
    if slice_rows:
        padded = slice_rows * 6 + 64 * tm  # every group up to a whole tile
        tiles = padded // tm
        ops += [{"group": "_expert_call", "seconds": 1.0, "want": True,
                 "hlo": f"%_expert_call.9 = f32[{padded},768]{{1,0}} custom-call(s32[1]{{0}} %a, "
                        f"s32[{tiles}]{{0}} %b, bf16[{padded},2560]{{1,0}} %x, u8[24,64,1280,768]{{3,2,1,0}} %w)"},
                {"group": "fusion", "seconds": 1.0, "want": True,
                 "hlo": f"%fusion.77 = bf16[{padded},2560]{{1,0}} fusion(bf16[{slice_rows},2560]{{1,0}} %b, "
                        f"s32[{padded}]{{0}} %order)"},
                {"group": "sort", "seconds": 1.0, "want": True,
                 "hlo": f"%sort.9 = (s32[{slice_rows * 6}]{{0}}, s32[{slice_rows * 6}]{{0}}) "
                        f"sort(s32[{slice_rows * 6}]{{0}} %ids, s32[{slice_rows * 6}]{{0}} %iota)"},
                {"group": "copy", "seconds": 1.0, "want": True,
                 "hlo": f"%copy.5 = s32[1,{slice_rows},6]{{2,1,0}} copy(s32[1,{slice_rows},6]{{1,2,0}} %topk)"},
                {"group": "fusion", "seconds": 1.0, "want": False,
                 "hlo": f"%fusion.78 = bf16[{slice_rows},3584]{{1,0}} fusion(f32[{slice_rows},3584]{{1,0}} %q)"}]
    picked = share.layer_ops(metric["params"], config, ops)
    assert [o["hlo"] for o in ops if o["want"]] == [o["hlo"] for o in ops if o in picked]
    run = {"config": config, "trace": {"busy_s": 2.0 * len(ops), "ops": ops}}
    assert share.reduce(metric["params"], run) == pytest.approx(
        100.0 * sum(o["want"] for o in ops) / (2.0 * len(ops)))
    # a program without the kernel reports nothing, and so does no trace
    rest = [o for o in ops if o["group"] != "_expert_call"]
    assert share.reduce(metric["params"], {"config": config, "trace": {"busy_s": 1.0, "ops": rest}}) is None
    assert share.reduce(metric["params"], {"config": config, "trace": None}) is None

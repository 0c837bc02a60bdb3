"""The layout of attention by layer kind over sigmoid-routed experts at a
tiny size (`tiny-laguna.json`): written from the plan, read back through the
layout, loaded by the program, the plain reference run on it, the new cell
resolved by name with every new metric's reducer and cost file found, and
the cell's files driven end to end on the CPU (`manifest-laguna.json`). CPU
rehearsal, not tier-1 (`tests/test_laguna.py` holds the serving path against
this reference in tier-1)."""

import hashlib
import importlib
import json
import os

import numpy as np
import pytest

from benchmark import files, run
from benchmark.layouts import laguna as layout

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW_METRICS = ("attn_global_roofline", "attn_window_roofline",
               "attn_kinds_busy_share", "attn_small_ops_busy_share",
               "moe_w512_roofline", "moe_w512_busy_share",
               "window_rows_walked_share", "w512_experts_touched_share",
               "w512_expert_load_skew")


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    with open(os.path.join(HERE, "tiny-laguna.json")) as f:
        config = json.load(f)
    path = str(tmp_path_factory.mktemp("laguna") / "tiny.m")
    size = files.write_model(path, config, 11)
    return config, path, size


@pytest.mark.parametrize("seed,sha", [(7, "f55c399c"), (2147483659, "4380d34e")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    with open(os.path.join(HERE, "tiny-laguna.json")) as f:
        config = json.load(f)
    path = str(tmp_path / "m.m")
    files.write_model(path, config, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


def test_written_from_the_plan_and_read_back(cell):
    config, path, size = cell
    s, views = layout.tensor_views(path)
    assert size == layout.read_header(path)[1] + sum(len(v[0]) for v in views.values())
    assert s["windowed"] == [0, 1, 1, 1] * 3 and s["dense_ffn"] == [1] + [0] * 11
    assert s["heads"] == [6, 8, 8, 8] * 3 and (s["n_heads"], s["window_heads"]) == (6, 8)
    assert (s["n_experts"], s["held"], s["expert_offset"]) == (16, 4, 4)
    assert s["g_rope"] == {"type": 4, "theta": 500000.0, "share": 0.5, "factor": 4.0,
                           "orig_len": 64, "beta_fast": 4.0, "beta_slow": 1.0,
                           "attn_factor": 1.138629}
    assert s == layout.shapes_of(config) | {"seq_len": s["seq_len"]}
    assert views["layers.0.wq"][1] == (6 * 32, 256) and "layers.0.wq_win" not in views
    assert views["layers.1.wq_win"][1] == (8 * 32, 256) and "layers.1.wq" not in views
    assert views["layers.1.wo_win"][1] == (256, 8 * 32)
    assert views["layers.0.attn_gate"][1] == (6, 256)
    assert views["layers.1.attn_gate_win"][1] == (8, 256)
    assert views["layers.1.q_norm_win"][1] == (32,)
    assert views["layers.0.w1"][1] == (512, 256) and "layers.0.moe_gate" not in views
    assert views["layers.1.moe_gate"][1] == (16, 256)
    assert views["layers.1.moe_w2"][1] == (4, 256, 256)


def test_token_dims_are_written_by_nothing_and_the_draws_show_what_they_should(cell):
    """`weights.router_dims`: the stream's last dims hold +-std with a sign a
    dim, every block's output rows there are zero, the attention norm's gain,
    the gate's and the router's rows live in them alone; the norm gains over
    the head are drawn away from 1."""
    from benchmark.reference import laguna as ref

    config, path, _ = cell
    rd = config["weights"]["router_dims"]
    _, views = layout.tensor_views(path)
    f32 = lambda v: np.asarray(v[0]).view(np.float32).reshape(v[1])
    emb = f32(views["embedding"])
    assert set(np.unique(emb[:, -rd:])) == {-1.0, 1.0} and abs(emb[:, -rd:].mean()) < 0.02
    gate = f32(views["layers.2.moe_gate"])
    assert not gate[:, :-rd].any() and abs(gate[:, -rd:].std() * np.sqrt(rd) - 1) < 0.1
    assert 0 < np.abs(f32(views["layers.2.moe_bias"])).max() <= config["weights"]["router_bias"]
    att = f32(views["layers.2.rms_att"])
    assert not att[:-rd].any() and (att[-rd:] == 1).all()
    assert (f32(views["layers.2.rms_ffn"]) == 1).all()
    for name in ("layers.0.attn_gate", "layers.1.attn_gate_win"):
        g = f32(views[name])
        assert not g[:, :-rd].any() and 0.7 < g[:, -rd:].std() * np.sqrt(rd) < 1.3
    for name in ("layers.0.q_norm", "layers.0.k_norm", "layers.1.q_norm_win"):
        n = f32(views[name])
        assert 0.75 <= n.min() < 1.0 < n.max() <= 1.75
    for name, index in (("layers.0.wo", None), ("layers.1.wo_win", None),
                        ("layers.0.w2", None), ("layers.2.shared_w2", None),
                        ("layers.2.moe_w2", 3)):
        w = np.asarray(ref._q40(views[name], index))
        assert w.shape[0] == 256 and not w[-rd:].any() and w[:-rd].any(), name


def test_the_program_plans_the_same_tensors(cell):
    from dllama_tpu.models import formats

    _, path, _ = cell
    cfg, header = formats.read_header(path)
    mine, size = layout.read_header(path)
    assert size == header
    _, views = layout.tensor_views(path)
    assert [(n, int(np.prod(sh))) for n, sh, _ in formats.tensor_plan(cfg)] == [
        (n, int(np.prod(v[1]))) for n, v in views.items()]


def test_the_reference_runs_and_its_window_forgets(cell):
    """Changing an early token moves the last position's logits through the
    global layers; the reference is finite."""
    config, path, _ = cell
    ref = importlib.import_module(config["reference"])
    seq = np.random.default_rng(0).integers(1, 250, 48).astype(np.int32)
    other = seq.copy()
    other[3] += 1
    a, b = (r[0] for r in ref.logits_at(path, [seq, other], [[47], [47]]))
    assert np.isfinite(a).all() and a.shape == (config["vocab_size"],)
    assert 0 < np.linalg.norm(a - b) / np.linalg.norm(a) < 1.0


def test_the_new_cell_resolves_by_name_and_every_new_metric_names_its_code():
    """BENCHMARK.json's new cell: its configuration, layout, reference and
    traffic are found by name, each new metric's file names a reducer that
    exists (and a cost file where it prices a kernel), and lists this cell
    alone; the published widths are the catalog's."""
    resolved = run.resolve("lagunaxs2.reason_long_closed", True,
                           os.path.join(ROOT, "BENCHMARK.json"))
    config, traffic = resolved["config"], resolved["traffic"]
    assert config["layout"] == "benchmark.layouts.laguna"
    importlib.import_module(config["reference"])
    importlib.import_module(f"benchmark.generators.{traffic['generator']}")
    assert (traffic["prompt_tokens"], traffic["max_tokens"]) == (
        {"dist": "uniform", "lo": 512, "hi": 1024},
        {"dist": "uniform", "lo": 2048, "hi": 3072})
    assert (traffic["clients"], traffic["pool"], traffic["ramp_seconds"],
            traffic["ramp_prompt_tokens"], traffic["shape_seed"]) == (
        "slots", 1024, 40, 0, 20261001)
    names = [m["name"] for m in resolved["metrics"]]
    assert set(NEW_METRICS) <= set(names)
    for m in resolved["metrics"]:
        importlib.import_module(f"benchmark.reducers.{m['reducer']}")
        if "cost" in m.get("params", {}):
            importlib.import_module(f"benchmark.costs.{m['params']['cost']}")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for entry in manifest["per_layer"]:
        if entry["name"] in NEW_METRICS:
            assert entry["workloads"] == ["lagunaxs2.reason_long_closed"]
    s = layout.shapes_of(config)
    assert (s["dim"], s["n_layers"], s["n_heads"], s["window_heads"],
            s["n_kv_heads"], s["head_size"], s["window"]) == (2048, 40, 48, 64, 8, 128, 512)
    assert (s["n_experts"], s["held"], s["n_active_experts"], s["moe_hidden_dim"],
            s["hidden_dim"], s["vocab_size"]) == (256, 64, 8, 512, 8192, 25088)
    assert sum(s["windowed"]) == 30 and s["dense_ffn"] == [1] + [0] * 39
    from benchmark.costs import paged_attention_kinds as kinds
    assert (kinds.heads_of(config, "global"), kinds.heads_of(config, "window")) == (48, 64)
    for key in ("reduced", "deployment", "assumed", "serve", "expect", "check",
                "tolerances"):
        assert key in config, key


def test_the_cell_at_a_tiny_size_runs_end_to_end():
    """`run.py` on `manifest-laguna.json`: the real CLI server, the scheduler
    and the hybrid launches over both page pools, the closed loop, the new
    counters through their reducers. No request fails, the audit is clean."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--manifest",
         os.path.join(HERE, "manifest-laguna.json"), "--workload",
         "tiny.attn_kinds_closed", "--seed", "1", "--seconds", "6", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compiles_in_window"] == 0
    # contexts of 8-40 rows against a 16-row window on 9 of 12 layers
    assert 50 < m["window_rows_walked_share"] < 80
    assert 0 < m["w512_experts_touched_share"] <= 100
    assert m["w512_expert_load_skew"] >= 1

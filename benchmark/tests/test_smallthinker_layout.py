"""The window-and-global, routed-expert layout at a tiny size
(`tiny-smallthinker.json`, beside `tiny-twokind.json`): written from the
plan, read back through the layout, loaded by the program, and the plain
reference run on it. CPU rehearsal, not tier-1 (`tests/test_window_moe.py`
holds the serving path against this reference in tier-1)."""

import json
import os

import numpy as np
import pytest

from benchmark import files
from benchmark.layouts import smallthinker as layout

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    with open(os.path.join(HERE, "tiny-smallthinker.json")) as f:
        config = json.load(f)
    path = str(tmp_path_factory.mktemp("smallthinker") / "tiny.m")
    size = files.write_model(path, config, 11)
    return config, path, size


def test_written_from_the_plan_and_read_back(cell):
    config, path, size = cell
    s, views = layout.tensor_views(path)
    assert size == layout.read_header(path)[1] + sum(len(v[0]) for v in views.values())
    assert (s["windowed"], s["rotates"]) == ([0, 1, 1, 1] * 2, [0, 1, 1, 1] * 2)
    assert (s["attn_dim"], s["dim"], s["window"]) == (384, 256, 16)
    assert views["layers.0.wq"][1] == (384, 256) and views["layers.0.wo"][1] == (256, 384)
    assert views["layers.3.moe_w2"][1] == (8, 256, 256)


def test_router_rows_have_the_configured_spread_and_no_favourite(cell):
    config, path, _ = cell
    _, views = layout.tensor_views(path)
    gate = np.asarray(views["layers.5.moe_gate"][0]).view(np.float32).reshape(8, 256)
    want = config["weights"]["router_gain"] / np.sqrt(256)
    assert abs(gate.std() / want - 1) < 0.1 and abs(gate.mean()) < 0.1 * want
    other = np.asarray(views["layers.6.moe_gate"][0]).view(np.float32)
    assert not np.array_equal(gate.reshape(-1), other)  # its own stream


def test_the_program_plans_the_same_tensors(cell):
    from dllama_tpu.models import formats

    _, path, _ = cell
    cfg, header = formats.read_header(path)
    mine, size = layout.read_header(path)
    assert size == header
    _, views = layout.tensor_views(path)
    assert [(n, tuple(sh)) for n, sh, _ in formats.tensor_plan(cfg)] == [
        (n, tuple(v[1])) for n, v in views.items()]


def test_the_reference_runs_and_sees_the_window(cell):
    """Changing a token more than a window behind the last position moves
    the logits only through the global layers; the reference is finite and
    the two sequences differ."""
    import importlib

    config, path, _ = cell
    ref = importlib.import_module(config["reference"])
    seq = np.random.default_rng(0).integers(1, 250, 48).astype(np.int32)
    other = seq.copy()
    other[3] += 1
    a, b = (r[0] for r in ref.logits_at(path, [seq, other], [[47], [47]]))
    assert np.isfinite(a).all() and a.shape == (config["vocab_size"],)
    assert 0 < np.linalg.norm(a - b) / np.linalg.norm(a) < 1.0


def test_the_cell_at_a_tiny_size_runs_end_to_end():
    """`run.py` on `manifest-smallthinker.json`: the real CLI server, the
    scheduler and the hybrid launches over a pool a kind, the closed loop,
    the new counters through their reducers. No request fails, the audit of
    both pools is clean, pages go back behind the window."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(HERE))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--manifest",
         os.path.join(HERE, "manifest-smallthinker.json"), "--workload",
         "tiny.window_moe_closed", "--seed", "1", "--seconds", "6", "--trace", "1"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compiles_in_window"] == 0
    assert m["kv_window_pages_released_per_s"] > 0
    # 8 experts read against the cell's 64: at most 12.5 here
    assert 0 < m["experts_touched_share"] <= 12.5


def test_router_dims_are_written_by_nothing_and_read_by_the_router(tmp_path):
    """`weights.router_dims`: the stream's last dims hold the token's own
    embedding (wo's and every w2's rows there are zero), the router's rows
    live in them alone, and nothing else of the file moves."""
    from benchmark.reference import smallthinker as ref

    with open(os.path.join(HERE, "tiny-smallthinker.json")) as f:
        config = json.load(f)
    plain = str(tmp_path / "plain.m")
    files.write_model(plain, config, 11)
    config["weights"] = dict(config["weights"], router_dims=64,
                             router_embedding_std=1.0)
    path = str(tmp_path / "kept.m")
    files.write_model(path, config, 11)
    s, views = layout.tensor_views(path)
    _, before = layout.tensor_views(plain)
    f32 = lambda v: np.asarray(v[0]).view(np.float32).reshape(v[1])
    gate, emb = f32(views["layers.2.moe_gate"]), f32(views["embedding"])
    assert not gate[:, :192].any() and abs(gate[:, 192:].std() * 8 - 1) < 0.1
    assert abs(emb[:, 192:].std() - 1) < 0.05 and emb[:, :192].std() < 0.02
    att = f32(views["layers.2.rms_att"])
    assert not att[:192].any() and (att[192:] == 1).all()
    assert (f32(views["layers.2.rms_ffn"]) == 1).all()
    wo = np.asarray(ref._q40(views["layers.2.wo"]))
    assert not wo[192:].any() and wo[:192].any()
    w2 = np.asarray(ref._q40(views["layers.2.moe_w2"], 5))
    assert w2.shape == (256, 256) and not w2[192:].any() and w2[:192].any()
    for name in ("layers.2.wq", "layers.2.moe_w1", "wcls"):
        assert bytes(views[name][0]) == bytes(before[name][0])

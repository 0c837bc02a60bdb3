"""The power-retention layout at a tiny size (`tiny-brumby.json`): written
from the plan, read back through the layout, loaded by the program, the
plain reference run on it, and the new cell's files driven end to end on the
CPU (`manifest-brumby.json`). CPU rehearsal, not tier-1
(`tests/test_retention.py` holds the serving path against this reference in
tier-1)."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import files
from benchmark.layouts import brumby as layout

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    with open(os.path.join(HERE, "tiny-brumby.json")) as f:
        config = json.load(f)
    path = str(tmp_path_factory.mktemp("brumby") / "tiny.m")
    size = files.write_model(path, config, 11)
    return config, path, size


def test_written_from_the_plan_and_read_back(cell):
    config, path, size = cell
    s, views = layout.tensor_views(path)
    assert size == layout.read_header(path)[1] + sum(len(v[0]) for v in views.values())
    assert (s["n_heads"], s["n_kv_heads"], s["head_size"]) == (10, 2, 16)
    assert (s["rope_theta"], s["norm_epsilon"]) == (10000.0, 1e-6)
    assert views["layers.0.wq"][1] == (160, 256) and views["layers.0.wo"][1] == (256, 160)
    assert views["layers.2.wk"][1] == (32, 256)
    assert views["layers.1.ret_gate"][1] == (2, 256)
    assert views["layers.1.ret_gate_bias"][1] == (2,)
    raw, _ = files.parse_header(path)
    assert [raw[1000 + i] for i in range(3)] == [4, 4, 4]
    assert (raw[161], raw[180], raw[181], raw[101]) == (1, 2, 1, 16) and 18 not in raw


@pytest.mark.parametrize("seed,sha", [(7, "eee012f9"), (2147483659, "a686a50a")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    with open(os.path.join(HERE, "tiny-brumby.json")) as f:
        config = json.load(f)
    path = str(tmp_path / "m.m")
    files.write_model(path, config, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


def test_token_dims_are_written_by_nothing_and_the_gate_spans_memories(cell):
    """`weights.token_dims`: the stream's last dims hold +-std with the
    signs of the successor's head row, every block's output rows there are
    zero and the final norm's gain there is the head's; the norms inside the
    layers read every dim; the gate's bias is the logit of a decay whose
    memory lies between the two taus."""
    from benchmark.reference import brumby as ref

    config, path, _ = cell
    w = config["weights"]
    td = w["token_dims"]
    _, views = layout.tensor_views(path)
    f32 = lambda v: np.asarray(v[0]).view(np.float32).reshape(v[1])
    emb = f32(views["embedding"])
    assert set(np.unique(emb[:, -td:])) == {-1.0, 1.0} and abs(emb[:, -td:].mean()) < 0.02
    assert np.abs(emb[:, :-td]).max() <= 0.02
    for name in ("layers.1.rms_att", "layers.1.rms_ffn", "layers.1.q_norm",
                 "layers.1.k_norm"):
        assert (f32(views[name]) == 1).all(), name
    final = f32(views["final_norm"])
    assert (final[:-td] == 1).all() and (final[-td:] == w["head_token_gain"]).all()
    for name in ("layers.0.wo", "layers.2.w2"):
        m = np.asarray(ref._q40(views[name]))
        assert m.shape[0] == 256 and not m[-td:].any() and m[:-td].any(), name
    bias = np.concatenate([f32(views[f"layers.{i}.ret_gate_bias"]) for i in range(3)])
    tau = -1.0 / np.log(1.0 / (1.0 + np.exp(-bias.astype(np.float64))))
    assert (tau >= w["gate_tau_lo"] * 0.999).all() and (tau <= w["gate_tau_hi"] * 1.001).all()
    gate = f32(views["layers.0.ret_gate"])
    assert abs(gate.std() * np.sqrt(256) / w["gate_gain"] - 1) < 0.15
    # the walk: a token's sign vector is its successor's head row's signs
    head = np.asarray(ref._q40(views["wcls"]))[:, -td:]
    nxt = layout.successor(np.arange(300, 330), config["vocab_size"])
    agree = (np.sign(head[nxt]) == emb[300:330, -td:]) | (head[nxt] == 0)
    assert agree.all()


def test_the_program_plans_the_same_tensors(cell):
    from dllama_tpu.models import formats

    _, path, _ = cell
    cfg, header = formats.read_header(path)
    mine, size = layout.read_header(path)
    assert size == header
    _, views = layout.tensor_views(path)
    assert [(n, int(np.prod(sh))) for n, sh, _ in formats.tensor_plan(cfg)] == [
        (n, int(np.prod(v[1]))) for n, v in views.items()]


def test_the_reference_runs_and_sees_positions_and_far_rows(cell):
    """Changing an early token moves the last position's logits (through
    the decayed weights of 44 rows back), and so does SHIFTING the same
    tokens by one position (through the rotation): the reference is finite,
    remembers, and is not NoPE."""
    import importlib

    config, path, _ = cell
    ref = importlib.import_module(config["reference"])
    seq = np.random.default_rng(0).integers(1, 250, 48).astype(np.int32)
    other = seq.copy()
    other[3] += 1
    shifted = np.concatenate([seq[:1], seq])  # every later token one row on
    a, b, c = (r[0] for r in ref.logits_at(
        path, [seq, other, shifted], [[47], [47], [48]]))
    assert np.isfinite(a).all() and a.shape == (config["vocab_size"],)
    assert 0 < np.linalg.norm(a - b) / np.linalg.norm(a) < 1.0
    assert 0 < np.linalg.norm(a - c) / np.linalg.norm(a) < 1.0


def test_the_cell_at_a_tiny_size_runs_end_to_end():
    """`run.py` on `manifest-brumby.json`: the real CLI server, the scheduler
    and the hybrid launches of a model with no cache rows, the closed loop,
    the new counter through its reducer. No request fails, the audit of the
    (empty) pool is clean, and the slices' state traffic is counted."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(HERE))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--manifest",
         os.path.join(HERE, "manifest-brumby.json"), "--workload",
         "tiny.retention_closed", "--seed", "1", "--seconds", "6", "--trace", "1"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compiles_in_window"] == 0
    assert m["state_slice_gb_per_s"] > 0
    assert 0 < m["batch_occupancy"] <= 100

"""The rotated-latent-attention layout over group-limited sigmoid-routed
experts at a tiny size (`tiny-axk1.json`): written from the plan, read back
through the layout, loaded by the program, the plain reference run on it,
and the new cell's files driven end to end on the CPU
(`manifest-axk1.json`). CPU rehearsal, not tier-1
(`tests/test_latent_rope_groups.py` holds the serving path against this
reference in tier-1)."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import files
from benchmark.layouts import axk1 as layout

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    with open(os.path.join(HERE, "tiny-axk1.json")) as f:
        config = json.load(f)
    path = str(tmp_path_factory.mktemp("axk1") / "tiny.m")
    size = files.write_model(path, config, 11)
    return config, path, size


def test_written_from_the_plan_and_read_back(cell):
    config, path, size = cell
    s, views = layout.tensor_views(path)
    assert size == layout.read_header(path)[1] + sum(len(v[0]) for v in views.values())
    assert s["dense_ffn"] == [1, 0, 0, 0, 0]
    assert (s["n_experts"], s["held"], s["expert_offset"]) == (16, 4, 4)
    assert (s["n_groups"], s["groups_kept"], s["q_rank"]) == (4, 2, 64)
    assert s["rope"] == {"type": layout.ROPE_YARN, "theta": 10000.0, "share": 1.0,
                         "factor": 8.0, "orig_len": 32, "beta_fast": 32.0,
                         "beta_slow": 1.0, "attn_factor": 1.0}
    m = layout.mscale(8.0, 1.0)
    assert abs(s["attn_scale"] - 64 ** -0.5 * m * m) < 1e-6
    assert views["layers.0.mla_qa"][1] == (64, 256)
    assert views["layers.0.mla_qb"][1] == (8 * (32 + 32), 64)
    assert views["layers.0.w1"][1] == (512, 256) and "layers.0.moe_gate" not in views
    assert views["layers.1.moe_gate"][1] == (16, 256)
    assert views["layers.1.moe_w2"][1] == (4, 256, 256)
    assert views["layers.3.mla_kva"][1] == (64 + 32, 256)
    assert views["layers.3.mla_kvb"][1] == (8 * (32 + 32), 64)


@pytest.mark.parametrize("seed,sha", [(7, "22bad9ba"), (2147483659, "b7a3628d")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    with open(os.path.join(HERE, "tiny-axk1.json")) as f:
        config = json.load(f)
    path = str(tmp_path / "m.m")
    files.write_model(path, config, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


def test_token_dims_are_written_by_nothing_and_read_by_mixers_and_router(cell):
    """`weights.router_dims`: the stream's last dims hold +-std with a sign a
    dim, every block's output rows there are zero, the mixer norm's gain and
    the router's rows live in them alone."""
    from benchmark.reference import axk1 as ref

    config, path, _ = cell
    rd = config["weights"]["router_dims"]
    _, views = layout.tensor_views(path)
    f32 = lambda v: np.asarray(v[0]).view(np.float32).reshape(v[1])
    emb = f32(views["embedding"])
    assert set(np.unique(emb[:, -rd:])) == {-1.0, 1.0} and abs(emb[:, -rd:].mean()) < 0.02
    assert np.abs(emb[:, :-rd]).max() <= 0.02
    gate = f32(views["layers.2.moe_gate"])
    assert not gate[:, :-rd].any() and abs(gate[:, -rd:].std() * np.sqrt(rd) - 1) < 0.1
    bias = f32(views["layers.2.moe_bias"])
    assert 0 < np.abs(bias).max() <= config["weights"]["router_bias"]
    att = f32(views["layers.2.rms_att"])
    assert not att[:-rd].any() and (att[-rd:] == 1).all()
    assert (f32(views["layers.2.rms_ffn"]) == 1).all()
    assert (f32(views["layers.2.mla_q_norm"]) == 1).all()
    for name, index in (("layers.3.mla_o", None), ("layers.0.w2", None),
                        ("layers.2.shared_w2", None), ("layers.2.moe_w2", 3)):
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


def test_the_reference_runs_and_sees_positions(cell):
    """Changing an early token moves the last position's logits (through
    the latent rows), and so does SHIFTING the same tokens by one position
    (through the rotation): the reference is finite and not NoPE."""
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
    """`run.py` on `manifest-axk1.json`: the real CLI server, the scheduler
    and the hybrid launches over the latent pool of rotated rows, the closed
    loop, the new counters through their reducers. No request fails, the
    audit is clean, half the tokens keep the held group (2 of 4 kept) and a
    quarter of the routed rows land on the held quarter of the experts."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(HERE))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--manifest",
         os.path.join(HERE, "manifest-axk1.json"), "--workload",
         "tiny.rot_latent_closed", "--seed", "1", "--seconds", "6", "--trace", "1"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compiles_in_window"] == 0
    assert 30 < m["group_kept_token_share"] < 70  # 2 of 4 groups kept: 50
    assert 10 < m["group_rows_share"] < 40  # 4 of 16 held: 25 +- the tiny sample
    assert 0 < m["group_experts_touched_share"] <= 100
    assert m["group_expert_load_skew"] >= 1
    # the accepted metrics the cell lists beside its own: the counter one
    # reads here too (no slot waits for a page); the trace ones need a chip
    assert m["starved_slot_step_share"] == 0

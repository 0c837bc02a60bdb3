"""An architecture is new files: a layout module, a configuration that
names it, and (for a cell) a reference. Proved here with a test-only second
layout (`layout_twokind.py`, `tiny-twokind.json`, `manifest-twokind.json`:
layers of two kinds, f32 tensors with their own distributions, a Q40 matrix
with gains by block of output rows, a tied head, header keys of its own),
and guarded on the other side: the Llama files of a seed are the bytes the
writer made before the split (PR 27's `benchmark/files.py`). numpy only."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

from benchmark import files

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "manifest-twokind.json")

# sha256 of tiny-llama's `.m` by seed and of its `.t`, recorded from the
# parent commit's benchmark/files.py (b13e018, before the layout moved out)
PARENT_M = {3: "18a7b7953e59982d039caedb21ab2ae117869629911dcd9f48c975c27a623ac1",
            2147483659: "dafd6b0ae173c3a22a2bb798c8619ba0d965b40801aa0942066499a404bce4a7"}
PARENT_T = "1fdc226b8039eccf7e5c672d9b8c2fbdc49666f478e20ee948ba3682f56852be"


def sha256(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def dequant(view):
    raw, (n_out, k_in), _ = view
    rec = np.asarray(raw).reshape(n_out, k_in // files.Q_BLOCK, files.Q40_BLOCK_BYTES)
    scale = rec[..., :2].copy().view(np.float16)[..., 0].astype(np.float32)
    lo = (rec[..., 2:] & 0x0F).astype(np.int32) - 8
    hi = (rec[..., 2:] >> 4).astype(np.int32) - 8
    codes = np.concatenate([lo, hi], axis=-1).astype(np.float32)
    return (codes * scale[..., None]).reshape(n_out, k_in), scale


def f32(view):
    raw, shape, _ = view
    return np.asarray(raw).view(np.float32).reshape(shape)


@pytest.fixture(scope="module")
def cell():
    from benchmark import run as harness

    return harness.resolve("twokind.files", False, MANIFEST)


# ------------------------------------------- the baseline's bytes stand


@pytest.mark.parametrize("seed", sorted(PARENT_M))
def test_llama_files_are_the_parents_bytes(tmp_path, seed):
    with open(os.path.join(HERE, "tiny-llama.json")) as f:
        config = json.load(f)
    model, tok, size = files.write_files(config, seed, str(tmp_path))
    assert sha256(model) == PARENT_M[seed]
    assert sha256(tok) == PARENT_T
    assert size == os.path.getsize(model)


# ----------------------------------------- the second architecture's files


def test_resolved_by_name_and_written_from_the_plan(cell, tmp_path):
    config = cell["config"]
    layout = files.layout_of(config)
    assert layout.__name__ == "benchmark.tests.layout_twokind"
    a, b, c = (str(tmp_path / n) for n in ("a.m", "b.m", "c.m"))
    size = files.write_model(a, config, 5)
    files.write_model(b, config, 5, workers=1)
    files.write_model(c, config, 2**31 + 9)
    assert sha256(a) == sha256(b) != sha256(c)
    s, views = layout.tensor_views(a)  # raises unless the bytes add up
    header_bytes = layout.read_header(a)[1]
    assert size == header_bytes + sum(len(v[0]) for v in views.values())
    assert s["kinds"] == list("ssass")
    assert (s["embedding_multiplier"], s["logits_divisor"]) == (12.0, 8.0)
    kinds = {li: {n.split(".")[2] for n in views if n.startswith(f"layers.{li}.")}
             for li in range(5)}
    assert "in_proj" in kinds[0] and "wq" not in kinds[0]
    assert "wq" in kinds[2] and "in_proj" not in kinds[2]
    assert {"w1", "w2", "w3"} <= kinds[0] & kinds[2]
    with pytest.raises(ValueError):  # the other layout refuses the file
        from benchmark.layouts import llama
        llama.read_header(a)


def test_f32_tensors_have_their_own_distributions(cell, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, cell["config"], 11)
    _, views = files.layout_of(cell["config"]).tensor_views(path)
    a = np.exp(f32(views["layers.0.a_log"]))
    assert 1.0 <= a.min() < a.max() <= 16.0 and a.std() > 1.0
    dt = np.log1p(np.exp(f32(views["layers.1.dt_bias"]).astype(np.float64)))
    assert 1e-3 * 0.99 <= dt.min() < dt.max() <= 1e-1 * 1.01
    conv = f32(views["layers.0.conv"])
    assert conv.shape == (256 + 64, 4) and 0.4 < conv.std() < 0.6
    assert abs(conv.mean()) < 0.1
    assert (f32(views["layers.0.norm"]) == 1.0).all()
    # each entry has its own stream: two layers' tensors of one kind differ
    assert not np.array_equal(f32(views["layers.0.a_log"]), f32(views["layers.1.a_log"]))


def test_gains_go_by_block_of_output_rows(cell, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, cell["config"], 11)
    _, views = files.layout_of(cell["config"]).tensor_views(path)
    _, scale = dequant(views["layers.0.in_proj"])  # [608, 4] block scales
    unit = 1.0 / np.sqrt(files.NIBBLE_VARIANCE * 128)
    mean = lambda lo, hi: float(scale[lo:hi].mean()) / unit
    # rows z 0..256 | x ..512 | B ..544 | C ..576 | dt ..608; a scale is
    # gain * unit * uniform(0.5, 1.5)
    assert mean(0, 256) == pytest.approx(1.0, rel=0.05)
    assert mean(256, 512) == pytest.approx(1.0, rel=0.05)
    assert mean(512, 544) == pytest.approx(0.5, rel=0.1)
    assert mean(544, 576) == pytest.approx(1.0, rel=0.1)
    assert mean(576, 608) == pytest.approx(4.0, rel=0.1)
    assert float(scale[512:544].max()) < 0.75 * unit * 1.01
    assert float(scale[576:608].min()) > 2.0 * unit * 0.99
    _, wq = dequant(views["layers.2.wq"])
    assert float(wq.mean()) / unit == pytest.approx(2.0, rel=0.05)
    w, _ = dequant(views["layers.0.out_proj"])  # symmetric, unit variance a row
    assert abs(float(w.mean())) < 0.01 * float(w.std())
    assert float((w * w).sum(axis=1).mean()) == pytest.approx(1.0 * 13 / 12, rel=0.1)


def test_a_derived_head_is_the_embedding_quantised(cell, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, cell["config"], 11)
    _, views = files.layout_of(cell["config"]).tensor_views(path)
    emb = f32(views["embedding"])
    head, scale = dequant(views["wcls"])
    assert head.shape == emb.shape
    # a nibble step is the block's largest magnitude over 8: a weight is
    # within half a step of the embedding's value (and a hair for the f16),
    # but one near the far end of the block's range, where nibbles stop at
    # +7 steps: that one is within a whole step
    step = np.repeat(np.abs(scale), files.Q_BLOCK, axis=1)
    err = np.abs(head - emb)
    assert (err <= step * 1.01 + 1e-7).all()
    inner = np.abs(emb) <= 7.4 * step
    assert (err[inner] <= 0.5 * step[inner] * 1.01 + 1e-7).all()
    assert err.max() > 0  # it IS quantised
    rel = np.linalg.norm(head - emb) / np.linalg.norm(emb)
    assert rel < 0.1


def test_a_plan_whose_gains_miss_rows_is_refused():
    bad = files.Entry("m", (64, 32), "q40", gain=((32, 1.0), (16, 2.0)))
    with pytest.raises(ValueError, match="48 rows of 64"):
        files._block_gains(bad)


def test_a_configuration_names_its_layout_or_fails_by_name(tmp_path):
    import subprocess

    with open(os.path.join(HERE, "tiny-llama.json")) as f:
        config = json.load(f)
    with open(MANIFEST) as f:
        manifest = json.load(f)
    for label, edit, needle in (
            ("none", lambda c: c.pop("layout"), "names no `layout`"),
            ("missing", lambda c: c.update(layout="benchmark.layouts.no_such"),
             "benchmark.layouts.no_such")):
        cfg = dict(config)
        edit(cfg)
        cfg_path = str(tmp_path / f"{label}.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        man = dict(manifest, configs=[{**manifest["configs"][0], "file": cfg_path}])
        man_path = str(tmp_path / f"{label}-manifest.json")
        with open(man_path, "w") as f:
            json.dump(man, f)
        out = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", man_path,
             "--workload", "twokind.files", "--seed", "1", "--seconds", "1"],
            cwd=ROOT, capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode != 0 and needle in out.stderr, out.stderr[-500:]
        assert '"correct"' not in out.stdout

"""chip_smoke.py's contract, checked without a chip.

The script can only pass on a TPU, so these tests hold it to the rest of its
contract on the CPU: it fails (non-zero, `"ok": false` on the last line)
where JAX finds no TPU; its last line has exactly the keys the driver reads;
the `.m` it writes is a file the repo's own reader accepts, deterministic in
--seed. The `slow` test is the first rehearsal of the on-chip-measurement
guide: the script's whole control flow at a tiny width, interpret-mode
kernels and the real CLI server on the CPU backend, steered from here.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (the script lives at the repo root)

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _tiny_cfg():
    from dllama_tpu.models.config import LlamaConfig

    return LlamaConfig(dim=256, hidden_dim=512, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=1024, seq_len=4096)


def test_fails_without_a_tpu():
    """`JAX_PLATFORMS=cpu python chip_smoke.py`: non-zero, no pass line —
    and quickly: the device gate runs before anything is built."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": None}
    assert "needs a TPU" in p.stderr
    assert '"phase": "build"' not in p.stdout


@pytest.mark.parametrize("argv,device,ok", [
    ([], TPU, True),
    (["--chips", "4"], dict(TPU, count=4), True),
    ([], dict(TPU, platform="cpu", kind="cpu"), False),  # wrong platform
    ([], dict(TPU, count=4), False),  # the driver's run needs ONE chip
    (["--chips", "4"], TPU, False),
])
def test_last_line_schema_and_verdict(monkeypatch, capsys, tmp_path, argv,
                                      device, ok):
    """The last stdout line is one JSON object with exactly `ok` and
    `device.{platform,kind,count}` — the device as the serving child
    reported it — and `ok`/the exit code follow the platform and count."""
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "entry").write_text("x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.setattr(chip_smoke, "build_phase", lambda seed: ("m", "t"))
    monkeypatch.setattr(chip_smoke, "serve_phase", lambda m, t: device)
    monkeypatch.setattr(chip_smoke, "run_child",
                        lambda call, timeout_s: json.dumps({"device": device}))
    try:
        rc = chip_smoke.main(argv)
    except SystemExit as e:  # a failed check unwinds past the last line
        rc = e.code
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"}
    assert last["ok"] is ok and (rc == 0) is ok
    if ok:
        assert set(last["device"]) == {"platform", "kind", "count"}
        assert last["device"] == device


def test_model_writer_round_trips_and_is_seeded(tmp_path):
    """write_model emits a file formats.read_header/load_params accept —
    header, tensor order and Q40 block layout per formats.tensor_plan —
    with finite weights, identical for one seed and different for two."""
    from dllama_tpu.models import formats
    from dllama_tpu.ops.quant import QTensor

    cfg = _tiny_cfg()
    paths = [str(tmp_path / f"{n}.m") for n in ("a", "b", "c")]
    for path, seed in zip(paths, (7, 7, 8)):
        chip_smoke.write_model(path, cfg, seed)
    same, again, other = (open(p, "rb").read() for p in paths)
    assert same == again and same != other
    cfg2, header_size = formats.read_header(paths[0])
    assert (cfg2.dim, cfg2.n_layers, cfg2.vocab_size, cfg2.seq_len) == (
        cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.seq_len)
    params = formats.load_params(paths[0], cfg2, header_size)
    w1 = params["layers"]["w1"]
    assert isinstance(w1, QTensor)
    assert w1.packed.shape == (cfg.n_layers, cfg.dim // 2, cfg.hidden_dim)
    dense = np.asarray(w1.dequantize(np.float32))
    assert np.isfinite(dense).all() and 0.2 < dense.std() * np.sqrt(cfg.dim) < 2.0
    assert params["embedding"].shape == (cfg.vocab_size, cfg.dim)


def test_tokenizer_covers_the_model_vocabulary(tmp_path):
    """Every id the random weights can emit decodes, the llama3 chat
    template is detected, and a prompt encodes byte-level."""
    from dllama_tpu.tokenizer.chat import ChatTemplate, ChatTemplateType
    from dllama_tpu.tokenizer.tokenizer import Tokenizer

    path = str(tmp_path / "t.t")
    chip_smoke.write_tokenizer(path, 1024)
    tok = Tokenizer.load(path)
    assert len(tok.vocab) == 1024 and tok.bos_id == 768
    assert ChatTemplate(ChatTemplateType.UNKNOWN, tok.chat_template, "").type \
        == ChatTemplateType.LLAMA3
    ids = tok.encode("<|start_header_id|>hello")
    assert ids == [768, 768 + 6] + list(b"hello")
    assert all(isinstance(tok.piece(i), str) for i in range(1024))


@pytest.mark.slow
def test_cpu_rehearsal_of_the_whole_flow(monkeypatch, capsys):
    """Rehearsal 1 of the on-chip-measurement guide: every phase of the
    script at a tiny width on the CPU backend. The steering lives here, not
    in the script: a tiny config, the device gate answered, pallas_call
    forced into interpret mode BELOW the script's own interpret=False
    check, and the server expected on cpu."""
    import jax
    from jax.experimental import pallas as pl

    monkeypatch.setattr(chip_smoke, "llama_3_2_1b", _tiny_cfg)
    cpu = {"platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 1}
    monkeypatch.setattr(chip_smoke, "_require_tpu", lambda: cpu)
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "EXPECT_ROUTE", "xla/paged_gather")
    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call", lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    # production precision: conftest's true-f32 dots would skew the parity
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        chip_smoke.kernels_phase(0)
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    monkeypatch.setattr(pl, "pallas_call", real)
    # the server child inherits this environment: one CPU device, like the
    # one chip (conftest's 8 virtual devices would make it build a mesh)
    monkeypatch.delenv("XLA_FLAGS")
    model, tok = chip_smoke.build_phase(0)
    device = chip_smoke.serve_phase(model, tok)
    assert device["platform"] == "cpu" and device["count"] >= 1
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    phases = [l.get("phase") for l in lines]
    assert phases == ["kernels", "build", "serve.boot", "serve.requests", "serve"]
    req = lines[3]
    assert req["compile"]["unexpected"] == 0 and req["radix_hit_tokens"] > 0


_FOUR_CHIP_STEER = """
import sys
sys.path.insert(0, {repo!r})
import jax
from jax.experimental import pallas as pl
import chip_smoke
from dllama_tpu.ops import matmul as mmod

# four virtual CPU devices stand in for the 2x2 host: the platform steer
# makes kernels=auto resolve the shard_map'd Pallas path, and pallas_call
# is forced into interpret mode below that resolution
mmod.device_platform = lambda: "tpu"
real = pl.pallas_call
pl.pallas_call = lambda *a, **kw: real(*a, **{{**kw, "interpret": True}})
chip_smoke._require_tpu = lambda: {{
    "platform": "cpu", "kind": jax.devices()[0].device_kind,
    "count": len(jax.devices())}}
chip_smoke.sharded_phase({model!r}, {tok!r})
"""


@pytest.mark.slow
def test_cpu_rehearsal_of_the_four_chip_phase(tmp_path):
    """Rehearsal 2 of the guide: `--chips 4`'s child on four virtual CPU
    devices at a tiny width — mesh, sharding rules, placement checks and
    the tp=4 vs one-device logits comparison."""
    from dllama_tpu.models.config import LlamaConfig

    cfg = LlamaConfig(dim=512, hidden_dim=1024, n_layers=2, n_heads=8,
                      n_kv_heads=4, vocab_size=1024, seq_len=4096)
    model, tok = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    chip_smoke.write_model(model, cfg, 0)
    chip_smoke.write_tokenizer(tok, cfg.vocab_size)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c",
         _FOUR_CHIP_STEER.format(repo=REPO, model=model, tok=tok)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    report, last = lines[-2], lines[-1]
    assert report["phase"] == "sharded"
    assert report["weight_share_per_device"] == [0.25] * 4
    assert report["kv_cache_share_per_device"] == [0.25] * 4
    assert report["logits_max_rel_err"] <= report["tolerance_rel"]
    assert last["device"]["count"] == 4

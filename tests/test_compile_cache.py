"""Where the persistent compile cache lives (obs/compile.place_compile_cache).

The path is part of the cache key, so a directory that moves never hits:
placed from outside through JAX_COMPILATION_CACHE_DIR the program sets no
directory of its own; otherwise it is ONE fixed git-ignored path inside the
checkout, whatever the cwd or pid. Each case runs in a fresh interpreter —
the setting is process-global, and this pytest process must keep compiling
cold (the compile-ledger tests count real compiles).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXED = os.path.join(REPO, "experiments", "jax_cache")

_PROBE = """
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
updates = []
real_update = jax.config.update
jax.config.update = lambda name, value: (updates.append(name), real_update(name, value))
env_before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
from dllama_tpu.obs.compile import place_compile_cache
returned = place_compile_cache()
print(json.dumps({{
    "returned": returned, "updates": updates,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "env_untouched": os.environ.get("JAX_COMPILATION_CACHE_DIR") == env_before,
    "pid": os.getpid()}}))
"""


def _probe(cwd, cache_env=None):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    p = subprocess.run([sys.executable, "-c", _PROBE.format(repo=REPO)],
                       cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself; the program
    neither sets a directory in code nor touches the variable."""
    got = _probe(REPO, cache_env=str(tmp_path / "placed"))
    assert "jax_compilation_cache_dir" not in got["updates"]
    assert got["returned"] == got["config_dir"] == str(tmp_path / "placed")
    assert got["env_untouched"]


def test_unplaced_cache_goes_to_the_fixed_checkout_path():
    got = _probe(REPO)
    assert got["returned"] == got["config_dir"] == FIXED
    assert got["env_untouched"]  # the variable is never set from code
    # small decode-bucket programs are cached too (jax's floor is 1 s)
    assert got["min_secs"] == 0.0
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "experiments/jax_cache/" in f.read().split()


def test_same_path_from_any_cwd_and_pid(tmp_path):
    """Never derived from the cwd, a temp name, the pid or the time."""
    a, b = _probe(REPO), _probe(str(tmp_path))
    assert a["pid"] != b["pid"]
    assert a["returned"] == b["returned"] == FIXED


def test_cli_writes_its_cache_where_the_environment_says(tmp_path):
    """End to end through the real entry point: `python -m dllama_tpu`
    places the cache before anything jits, so a run leaves entries in the
    directory the environment named — and none are asked of the checkout."""
    sys.path.insert(0, REPO)
    import chip_smoke
    from dllama_tpu.models.config import LlamaConfig

    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=1024, seq_len=64)
    model, tok = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    chip_smoke.write_model(model, cfg, 0)
    chip_smoke.write_tokenizer(tok, cfg.vocab_size)
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "dllama_tpu", "inference", "--model", model,
         "--tokenizer", tok, "--prompt", "hi", "--steps", "4",
         "--temperature", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert len(os.listdir(cache)) > 0

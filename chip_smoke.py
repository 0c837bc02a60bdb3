"""chip_smoke.py — prove the serving path runs, compiled, on the attached TPU.

    python chip_smoke.py             # one chip: kernels, then the real server
    python chip_smoke.py --chips 4   # four chips: the tensor-parallel path only

The quickest proof that the system still starts on the chip. It drives the
main path once through the entry points a user would call, at the full width
AND depth of Llama-3.2-1B (Q40 weights random, made from --seed), and checks
what comes out by the repo's own means. It claims no speed: the figures it
prints are smoke figures of one cold run.

WARNING, a named debt (ROADMAP, Design): `write_model` below still writes
the BLIND weights. Its nibbles are uniform in [-8, 7], so every block has a
mean of -0.5 and the residual stream collapses onto one direction; the
serving path then agrees with the reference to a rel-L2 of 0.0009-0.002
with or without an f8 KV cache (PERF.md section 4), which means the parity
this smoke prints on the serve path cannot tell a sound cache from a lossy
one. It proves that the path starts, compiles and drains, and no more. The
cure is symmetric nibbles with `wq` 1.5 times larger, as
`benchmark/layouts/llama.py` writes them, or retiring this file in favour
of `benchmark/drill.py`; neither is done here.

This process NEVER initialises a JAX backend (a chip belongs to one process
at a time): it writes the files with numpy and talks HTTP. Each phase is a
child process, one at a time, each the only holder of the chip:

  kernels  on the chip, compiled (interpret=False is checked at pallas_call):
           q40_matmul, flash_gqa_attention and paged_decode_attention
           (fused scatter, t=1 and t=K+1) against the repo's jnp references
           at 1B width. It is also the device gate: it fails at once when
           JAX finds no TPU, before anything is built.
  build    a full .m (random packed Q40 blocks + f16 scales written per
           formats.tensor_plan — no 5 GB float pass) and a byte-level .t
           under chip_smoke_out/ (git-ignored).
  serve    `python -m dllama_tpu serve --slots 8 --max-seq-len 2048
           --spec-k 4`, every other option at its default; the parent sends
           a completion (twice: greedy must repeat), an SSE stream, and
           eight concurrent requests (shared system prompt, mixed spec_k,
           one ~1,500-token prompt), then reads /health, /metrics and
           /debug/compile, SIGTERMs and waits for a clean drain.

--chips 4 runs none of that: one child loads the same file through
engine/loader.load_model twice — mesh="tp=4" and mesh=None — and compares
logits and placement (see sharded_phase).

Earlier stdout lines are one JSON object each (phase seconds and smoke
figures). The LAST line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
as the serving (or sharded) child reported its devices; any failed check, a
child that exits non-zero, or a platform other than "tpu" makes it
"ok": false and the exit code non-zero.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chip_smoke_out")

#: `serve` flags of the smoke (everything else stays at its default)
SLOTS, MAX_SEQ_LEN, SPEC_K = 8, 2048, 4
#: what the server must report it runs on (its /health `build` object). The
#: CPU rehearsal in tests/test_chip_smoke.py swaps these — in the test, so
#: the script itself has no way to pass anywhere but on the chip.
EXPECT_PLATFORM, EXPECT_ROUTE = "tpu", "pallas/paged_kernel"

SYSTEM_PROMPT = (
    "You are the smoke test of a serving stack. Answer briefly and plainly. "
    "The same instructions open every conversation so that the prefix cache "
    "has something to share between requests.")


def say(**record) -> None:
    """One free-form JSON line (never the last line)."""
    print(json.dumps(record), flush=True)


def llama_3_2_1b():
    """Published Llama-3.2-1B shapes (HF config.json): hidden 2048,
    intermediate 8192, 16 layers, 32 query / 8 kv heads (head size 64),
    vocab 128256, 131072 positions, rope theta 500000 with llama3 scaling.
    Width and depth are uncut; the server clamps the context to
    --max-seq-len."""
    from dllama_tpu.models.config import LlamaConfig, RopeType

    return LlamaConfig(
        dim=2048, hidden_dim=8192, n_layers=16, n_heads=32, n_kv_heads=8,
        vocab_size=128256, seq_len=131072, rope_theta=500000.0,
        rope_type=RopeType.LLAMA3_1, rope_scaling_factor=32.0,
        rope_scaling_low_freq_factor=1.0, rope_scaling_high_freq_factor=4.0,
        rope_scaling_orig_max_seq_len=8192)


# ------------------------------------------------------------------- build


def write_model(path: str, cfg, seed: int) -> None:
    """A complete `.m` file of random weights, deterministic in `seed`: the
    file-level analogue of models/llama.random_params_fast. Q40 tensors are
    written as what they are on disk — per 32-weight block an f16 scale and
    16 bytes of packed nibbles — straight from the generator, so no float
    copy of the model is ever made. Scales are sized so every matmul keeps
    its input's magnitude (nibbles are uniform in [-8, 7], variance 21.25)."""
    from dllama_tpu.models import formats
    from dllama_tpu.ops.quant import FloatType, Q_BLOCK

    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        formats.write_header(f, cfg)
        for name, shape, ft in formats.tensor_plan(cfg):
            n = int(np.prod(shape))
            if ft == FloatType.F32:
                if name == "embedding":
                    x = (rng.random(n, np.float32) - 0.5) * 0.04
                else:  # rms norm gains
                    x = np.ones(n, np.float32)
                f.write(x.tobytes())
            elif ft == FloatType.Q40:
                blocks = n // Q_BLOCK
                rec = np.empty((blocks, 2 + Q_BLOCK // 2), np.uint8)
                scale = 1.0 / np.sqrt(21.25 * shape[-1])  # shape = [out, in]
                scales = (scale * (0.5 + rng.random(blocks, np.float32)))
                rec[:, :2] = scales.astype(np.float16).view(np.uint8).reshape(blocks, 2)
                rec[:, 2:] = rng.integers(0, 256, (blocks, Q_BLOCK // 2), np.uint8)
                f.write(rec.tobytes())
            else:
                raise ValueError(f"write_model handles F32/Q40 plans, got {ft}")


def write_tokenizer(path: str, vocab_size: int) -> None:
    """A byte-level `.t` in the llama3 layout that covers the whole model
    vocabulary (the server decodes whatever id the random weights emit):
    ids 0-255 are the bytes, then printable filler pieces, then the special
    tail from <|begin_of_text|> (vocab_size - 256) on."""
    from dllama_tpu.tokenizer.tokenizer import Tokenizer

    bos = vocab_size - 256
    vocab = [bytes([i]) for i in range(256)]
    vocab += [b" t%d" % i for i in range(256, bos)]
    special = {0: b"<|begin_of_text|>", 1: b"<|end_of_text|>",
               6: b"<|start_header_id|>", 7: b"<|end_header_id|>",
               9: b"<|eot_id|>"}
    vocab += [special.get(i, b"<|reserved_special_token_%d|>" % i)
              for i in range(256)]
    # filler pieces score below every byte so BPE never merges into them
    scores = [0.0] * 256 + [-1e6] * (bos - 256) + [0.0] * 256
    Tokenizer(vocab, scores, bos, [bos + 1, bos + 9],
              chat_template="{{ '<|start_header_id|>' }}").save(path)


def build_phase(seed: int) -> tuple[str, str]:
    os.makedirs(OUT_DIR, exist_ok=True)
    cfg = llama_3_2_1b()
    model = os.path.join(OUT_DIR, f"llama-3.2-1b-q40-seed{seed}.m")
    tok = os.path.join(OUT_DIR, "bytes-llama3.t")
    t0 = time.monotonic()
    write_model(model + ".tmp", cfg, seed)
    os.replace(model + ".tmp", model)
    write_tokenizer(tok, cfg.vocab_size)
    from dllama_tpu.utils import native

    # built from native/dllama_native.cpp here, in the parent, so that no
    # two children race to build it; says which tokenizer path will serve
    say(phase="build", seconds=round(time.monotonic() - t0, 1), seed=seed,
        model=os.path.relpath(model, REPO),
        model_bytes=os.path.getsize(model),
        tokenizer_path="native" if native.available() else "python")
    return model, tok


# ------------------------------------------------------------ child phases


def _require_tpu():
    """Fail (non-zero, before any work) unless JAX runs on a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; jax found {dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def kernels_phase(seed: int) -> None:
    """CHILD: each Pallas kernel of the serving path against its jnp
    reference at 1B width, compiled. Parity is relative to the largest
    reference magnitude: bf16 operands, f32 accumulation on both sides."""
    from dllama_tpu.obs.compile import place_compile_cache

    place_compile_cache()
    device = _require_tpu()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from dllama_tpu.models.llama import _paged_cache_update
    from dllama_tpu.ops.layers import gqa_attention, paged_gqa_attention
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention
    from dllama_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        pool_lanes,
    )
    from dllama_tpu.ops.pallas.q40_matmul import q40_matmul
    from dllama_tpu.ops.quant import Q_BLOCK, QTensor

    # every kernel launch must reach pallas_call compiled
    interpret_seen: list[bool] = []
    real_pallas_call = pl.pallas_call

    def recording_pallas_call(*args, **kw):
        interpret_seen.append(bool(kw.get("interpret", False)))
        return real_pallas_call(*args, **kw)

    pl.pallas_call = recording_pallas_call

    cfg = llama_3_2_1b()
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    rng = np.random.default_rng(seed)
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    checks: dict[str, float] = {}
    tol = 2e-2

    def check(name, got, want):
        err = _rel_err(got, want)
        checks[name] = round(err, 5)
        if not np.isfinite(np.asarray(got, np.float32)).all() or err > tol:
            raise SystemExit(f"kernels: {name} rel err {err:.4g} > {tol}")

    t0 = time.monotonic()
    for wname, k, n in (("w1", cfg.dim, cfg.hidden_dim),
                        ("w2", cfg.hidden_dim, cfg.dim),
                        ("wcls", cfg.dim, cfg.vocab_size)):
        w = QTensor(
            jnp.asarray(rng.integers(0, 256, (k // 2, n), np.uint8)),
            jnp.asarray((rng.random((k // Q_BLOCK, n), np.float32) * 0.02
                         + 1e-3).astype(np.float16)))
        wd = w.dequantize(jnp.bfloat16)
        for m in (SLOTS, 256):  # decode rows = slots; the prefill chunk cap
            x = bf16(m, k)
            check(f"q40_matmul m={m} {wname}({k}x{n})",
                  q40_matmul(x, w, interpret=False),
                  jnp.dot(x, wd, preferred_element_type=jnp.float32))
        del w, wd

    seq = MAX_SEQ_LEN
    k_cache, v_cache = bf16(1, hkv, seq, hd), bf16(1, hkv, seq, hd)
    for t, pos in ((256, 512), (1, 1500)):
        q = bf16(1, t, hq, hd)
        check(f"flash_gqa_attention t={t} pos={pos} S={seq} hd={hd}",
              flash_gqa_attention(q, k_cache, v_cache, jnp.int32(pos),
                                  interpret=False),
              gqa_attention(q, k_cache, v_cache, jnp.int32(pos)))

    # paged decode with the fused scatter, on a pool as the engine
    # allocates it on this route (rows pool_lanes(hd) wide, pad lanes zero);
    # the reference scatters into the unpadded rows and gathers them
    page, nb = 128, seq // 128
    lanes = pool_lanes(hd)
    tables = jnp.asarray(
        rng.permutation(SLOTS * nb).reshape(SLOTS, nb), jnp.int32)
    pos = jnp.asarray(rng.integers(1, seq - SPEC_K - 1, SLOTS), jnp.int32)
    active = jnp.asarray([True] * (SLOTS - 1) + [False])
    pools = [jnp.pad(bf16(SLOTS * nb + 1, hkv, page, hd),
                     ((0, 0),) * 3 + ((0, lanes - hd),)) for _ in range(2)]
    same = lambda a, b: np.array_equal(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
    # plain decode; the K+1-wide spec verify; a prefill slice long enough
    # to take the XLA pre-scatter (page by page) instead of the fused one
    for t in (1, SPEC_K + 1, 64):
        pos = jnp.minimum(pos, seq - t)
        q, nk, nv = bf16(SLOTS, t, hq, hd), bf16(SLOTS, hkv, t, hd), bf16(SLOTS, hkv, t, hd)
        out, kp, vp = paged_decode_attention(
            q, pools[0], pools[1], tables, pos, nk, nv, active,
            interpret=False)
        # the call as the decoder's layer scan makes it: the layer-stacked
        # pool whole, the layer as data. Bit-equal to the call on the
        # layer's slice, and the other layer is not touched
        stack = [jnp.stack([p[::-1], p]) for p in pools]
        out_l, ks, vs = paged_decode_attention(
            q, stack[0], stack[1], tables, pos, nk, nv, active,
            layer=jnp.int32(1), interpret=False)
        if not (same(out_l, out) and same(ks[1], kp) and same(vs[1], vp)
                and same(ks[0], stack[0][0]) and same(vs[0], stack[1][0])):
            raise SystemExit(f"kernels: paged_decode_attention t={t}: the "
                             "layer-indexed call differs from the call on "
                             "the layer's slice")
        k_ref = _paged_cache_update(pools[0][..., :hd], nk, tables, pos, active)
        v_ref = _paged_cache_update(pools[1][..., :hd], nv, tables, pos, active)
        name = f"paged_decode_attention t={t} page={page} hd={hd}"
        # inactive rows attend to stale rows by design: compare the live ones
        check(name, out[:-1], paged_gqa_attention(q, k_ref, v_ref, tables, pos)[:-1])
        trash = kp.shape[0] - 1  # inactive rows scatter to the trash page
        for got, want in ((kp, k_ref), (vp, v_ref)):
            if not (np.array_equal(np.asarray(got[:trash, ..., :hd], np.float32),
                                   np.asarray(want[:trash], np.float32))
                    and not np.asarray(got[..., hd:], np.float32).any()):
                raise SystemExit(f"kernels: {name}: fused scatter differs "
                                 "from the XLA scatter reference")
    if not interpret_seen or any(interpret_seen):
        raise SystemExit(f"kernels: interpret=True reached pallas_call "
                         f"({sum(interpret_seen)} of {len(interpret_seen)})")
    say(phase="kernels", seconds=round(time.monotonic() - t0, 1),
        device=device, pallas_calls_compiled=len(interpret_seen),
        rel_err=checks, tolerance=tol, jax=jax.__version__)


def sharded_phase(model: str, tok: str) -> None:
    """CHILD (--chips 4): the reference's reason to exist is tensor
    parallelism. Load the same file through engine/loader.load_model with
    mesh="tp=4" (shard_map'd Pallas matmuls + head-sharded flash) and with
    mesh=None (one chip), in this one process driving all four devices;
    compare the logits of one 256-token prefill and 16 teacher-forced
    decode steps, and check from addressable_shards that every device holds
    its quarter of the layer weights and of the KV cache."""
    from dllama_tpu.obs.compile import place_compile_cache

    place_compile_cache()
    device = _require_tpu()
    import jax

    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.ops.quant import QTensor

    if device["count"] != 4:
        raise SystemExit(f"--chips 4 needs four devices, jax found {device['count']}")
    t0 = time.monotonic()
    tp = load_model(model, tok, max_seq_len=MAX_SEQ_LEN, mesh="tp=4")
    one = load_model(model, tok, max_seq_len=MAX_SEQ_LEN, mesh=None)
    load_s = time.monotonic() - t0
    routes = {"tp=4": tp.engine.kernel_route, "one chip": one.engine.kernel_route}
    if routes != {"tp=4": "pallas/sharded_flash", "one chip": "pallas/flash"}:
        raise SystemExit(f"sharded: unexpected kernel routes {routes}")

    # ---- placement, from the arrays themselves
    devices = jax.devices()

    def shares(tree) -> list[float]:
        """Each device's share of the tree's bytes (addressable shards)."""
        held = dict.fromkeys(devices, 0)
        total = 0
        for leaf in jax.tree.leaves(tree):
            total += leaf.nbytes
            for sh in leaf.addressable_shards:
                held[sh.device] += sh.data.nbytes
        return [round(held[d] / total, 4) for d in devices]

    layers = tp.engine.params["layers"]
    sharded_w = {k: v for k, v in layers.items() if isinstance(v, QTensor)}
    weight_shares = shares(sharded_w)
    cache_shares = shares((tp.engine.cache.k, tp.engine.cache.v))
    replicated = {"embedding": shares(tp.engine.params["embedding"]),
                  "norms": shares((layers["rms_att"], layers["rms_ffn"],
                                   tp.engine.params["final_norm"])),
                  "rope": shares(tp.engine.rope_cache)}
    for what, got in (("layer weights", weight_shares), ("kv cache", cache_shares)):
        if any(abs(s - 0.25) > 0.01 for s in got):
            raise SystemExit(f"sharded: {what} not quartered across devices: {got}")
    state = {"params": tp.engine.params, "rope": tp.engine.rope_cache,
             "cache": (tp.engine.cache.k, tp.engine.cache.v)}
    only_dev0 = [jax.tree_util.keystr(path)
                 for path, leaf in jax.tree_util.tree_leaves_with_path(state)
                 if {sh.device for sh in leaf.addressable_shards} == {devices[0]}]
    if only_dev0:
        raise SystemExit(f"sharded: arrays living only on device 0: {only_dev0}")

    # ---- numerics: tp=4 vs one chip on the same tokens
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, (1, 256)).astype(np.int32)
    t0 = time.monotonic()
    errs = []
    steps = [prompt] + [None] * 16
    for i in range(len(steps)):
        a = np.asarray(tp.engine.step(steps[i]), np.float32)
        b = np.asarray(one.engine.step(steps[i]), np.float32)
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            raise SystemExit(f"sharded: non-finite logits at step {i}")
        errs.append((float(np.max(np.abs(a - b))), _rel_err(a, b)))
        if i + 1 < len(steps):  # teacher forcing: both see the one-chip argmax
            steps[i + 1] = np.argmax(b, axis=-1).astype(np.int32)[:, None]
    max_abs = max(e[0] for e in errs)
    max_rel = max(e[1] for e in errs)
    # bf16 activations; tp=4 sums wo/w2 partials in another order (psum of
    # four f32 partials rounded to bf16) through 16 layers
    tol = 5e-2
    say(phase="sharded", device=device, routes=routes,
        load_seconds=round(load_s, 1),
        run_seconds=round(time.monotonic() - t0, 1),
        logits_max_abs_err=round(max_abs, 5), logits_max_rel_err=round(max_rel, 5),
        tolerance_rel=tol, prefill_rel_err=round(errs[0][1], 5),
        weight_share_per_device=weight_shares,
        kv_cache_share_per_device=cache_shares,
        replicated_share_per_device=replicated)
    if max_rel > tol:
        raise SystemExit(f"sharded: logits differ, max rel err {max_rel:.4g} > {tol}")
    print(json.dumps({"device": device}), flush=True)  # the parent's verdict


def run_child(call: str, timeout_s: float) -> str:
    """Run `chip_smoke.<call>` in a fresh interpreter that may hold the
    chip; its stdout passes through and is returned. Raises on exit != 0."""
    proc = subprocess.run(
        [sys.executable, "-c", f"import chip_smoke; chip_smoke.{call}"],
        cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=timeout_s)
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    if proc.returncode != 0:
        raise SystemExit(f"child `{call}` exited {proc.returncode}")
    return proc.stdout


# -------------------------------------------------------------- serve phase


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, method: str, path: str, body=None, timeout=900.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _chat_body(user: str, **extra) -> dict:
    return {"messages": [{"role": "system", "content": SYSTEM_PROMPT},
                         {"role": "user", "content": user}],
            "temperature": 0.0, "max_tokens": 24, **extra}


def _complete(port: int, body: dict) -> dict:
    """One non-stream completion -> the facts the smoke checks."""
    t0 = time.monotonic()
    status, data = _http(port, "POST", "/v1/chat/completions", body)
    wall = time.monotonic() - t0
    if status != 200:
        raise SystemExit(f"serve: HTTP {status}: {data[:300]!r}")
    doc = json.loads(data)
    choice = doc["choices"][0]
    return {"finish": choice["finish_reason"],
            "content": choice["message"]["content"],
            "tokens": doc["usage"]["completion_tokens"],
            "prompt_tokens": doc["usage"]["prompt_tokens"],
            "ttft_ms": doc["timings"].get("ttft_ms"), "wall_s": wall}


def _stream(port: int, body: dict) -> dict:
    """One SSE completion, parsed frame by frame."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900.0)
    try:
        conn.request("POST", "/v1/chat/completions",
                     json.dumps({**body, "stream": True, "include_token_ids": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SystemExit(f"serve: stream HTTP {resp.status}")
        ids, text, finish, done = [], [], None, False
        for raw in resp:
            line = raw.strip()
            if not line.startswith(b"data:"):
                continue  # blank separators, `: keep-alive` comments
            payload = line[5:].strip()
            if payload == b"[DONE]":
                done = True
                break
            frame = json.loads(payload)
            if "error" in frame:
                raise SystemExit(f"serve: in-band stream error {frame['error']}")
            choice = frame["choices"][0]
            text.append(choice["delta"].get("content") or "")
            ids += frame.get("token_ids", [])
            finish = choice["finish_reason"] or finish
        if not done:
            raise SystemExit("serve: SSE stream ended without [DONE]")
        return {"finish": finish, "content": "".join(text), "tokens": len(ids)}
    finally:
        conn.close()


def _metric(text: str, name: str) -> float:
    """Sum of a family's samples in a Prometheus exposition (0 if none)."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def serve_phase(model: str, tok: str) -> dict:
    """Boot the real CLI server, drive it over HTTP only, drain it.
    Returns the device the SERVER reported (for the last line)."""
    port = _free_port()
    argv = [sys.executable, "-m", "dllama_tpu", "serve", "--model", model,
            "--tokenizer", tok, "--slots", str(SLOTS),
            "--max-seq-len", str(MAX_SEQ_LEN), "--spec-k", str(SPEC_K),
            "--port", str(port)]
    t_boot = time.monotonic()
    log_path = os.path.join(OUT_DIR, "serve.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
    try:
        while True:  # ready = weights loaded, engine built, socket bound
            if proc.poll() is not None:
                raise SystemExit(f"serve: server exited {proc.returncode} "
                                 f"during boot (see {log_path})")
            if time.monotonic() - t_boot > 600:
                raise SystemExit("serve: not ready after 600 s")
            try:
                if _http(port, "GET", "/health/ready", timeout=5)[0] == 200:
                    break
            except OSError:
                pass
            time.sleep(0.5)
        load_s = time.monotonic() - t_boot
        health = json.loads(_http(port, "GET", "/health")[1])
        build = health["build"]
        device = {"platform": build["backend"], "kind": build["device_kind"],
                  "count": int(build["device_count"])}
        say(phase="serve.boot", load_seconds=round(load_s, 1), build=build,
            model_params_bytes=health["model_params_bytes"],
            kv_cache_bytes=health["kv_cache_bytes"])
        if device["platform"] != EXPECT_PLATFORM:
            raise SystemExit(f"serve: server runs on {device['platform']!r}")
        if build["kernels"] != EXPECT_ROUTE:
            raise SystemExit(f"serve: resolved route {build['kernels']!r}, "
                             f"expected {EXPECT_ROUTE}")

        results = []
        # (a) one greedy completion, twice: the second must repeat the first
        a1 = _complete(port, _chat_body("Say something about the sea."))
        a2 = _complete(port, _chat_body("Say something about the sea."))
        results += [a1, a2]
        if (a1["content"], a1["tokens"]) != (a2["content"], a2["tokens"]):
            raise SystemExit("serve: greedy request did not repeat: "
                             f"{a1['content']!r} vs {a2['content']!r}")
        # (b) one SSE stream
        b = _stream(port, _chat_body("And something about the sky."))
        results.append(b)
        # (c) eight at once: six opt out of speculation, two ride the
        # server's K; one carries a ~1,500-token prompt, so chunked/hybrid
        # prefill runs beside the others' decode
        long_user = "Summarise this: " + "the tide comes in and goes out. " * 48
        bodies = [_chat_body(f"Question {i}: what comes after {i}?", spec_k=0)
                  for i in range(5)]
        bodies.append(_chat_body(long_user, spec_k=0))
        bodies += [_chat_body("Repeat: la la la la la la la la la la la la."),
                   _chat_body("Count: one two one two one two one two.")]
        batch: list = [None] * len(bodies)

        def worker(i):
            batch[i] = _complete(port, bodies[i])

        t0 = time.monotonic()
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(bodies))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(900)
        batch_s = time.monotonic() - t0
        if any(r is None for r in batch):
            raise SystemExit("serve: a concurrent request did not return")
        results += batch
        for r in results:
            if r["finish"] not in ("stop", "length") or r["tokens"] < 1:
                raise SystemExit(f"serve: bad completion {r}")
        if max(r["prompt_tokens"] for r in batch) < 1400:
            raise SystemExit("serve: the long prompt is not ~1,500 tokens")

        metrics = _http(port, "GET", "/metrics")[1].decode()
        comp = json.loads(_http(port, "GET", "/debug/compile")[1])
        restarts = _metric(metrics, "dllama_engine_restarts_total")
        recovered = _metric(metrics, "dllama_requests_recovered_total")
        radix_hits = _metric(metrics, "dllama_radix_hit_tokens_total")
        say(phase="serve.requests",
            smoke_figures={
                "first_request_ttft_ms_cold": a1["ttft_ms"],
                "repeat_request_ttft_ms": a2["ttft_ms"],
                "repeat_request_tok_s": round(a2["tokens"] / a2["wall_s"], 1),
                "batch8_seconds": round(batch_s, 2),
                "batch8_tok_s": round(sum(r["tokens"] for r in batch) / batch_s, 1),
                "batch8_ttft_ms_max": max(r["ttft_ms"] or 0.0 for r in batch)},
            compile={"seconds": comp["seconds"], "compiles": comp["compiles"],
                     "unexpected": comp["unexpected"],
                     "buckets_seen": sum(len(v) for v in comp["seen"].values())},
            device_memory=comp["device_memory"],
            radix_hit_tokens=radix_hits,
            spec_cycles=_metric(metrics, "dllama_spec_cycles_total"),
            engine_restarts=restarts, requests_recovered=recovered)
        if restarts or recovered:
            raise SystemExit("serve: the engine restarted under the smoke")
        if comp["unexpected"]:
            raise SystemExit(f"serve: {comp['unexpected']} unexpected compiles")
        if radix_hits <= 0:
            raise SystemExit("serve: the radix cache shared no prefix")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(120)
        if rc != 0:
            raise SystemExit(f"serve: server exited {rc} after SIGTERM")
        say(phase="serve", seconds=round(time.monotonic() - t_boot, 1),
            drained_clean=True)
        return device
    except BaseException:
        # the server's own account of what went wrong, before the traceback
        with open(log_path, errors="replace") as log:
            sys.stderr.write("---- tail of serve.log ----\n" + log.read()[-6000:] + "\n")
        raise
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# -------------------------------------------------------------------- main


def _cache_entries() -> dict:
    # where the children's place_compile_cache() put it: jax itself honours
    # JAX_COMPILATION_CACHE_DIR, otherwise the one fixed path
    from dllama_tpu.obs.compile import COMPILE_CACHE_DIR

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    n = len(os.listdir(path)) if os.path.isdir(path) else 0
    return {"compile_cache": path, "compile_cache_entries": n}


def _deadline(signum, frame):
    # SIGTERM from outside, or the run's own alarm: unwind through the
    # `finally` blocks so that no child outlives this process
    raise SystemExit(f"chip_smoke: stopped by signal {signum}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel path and what it "
                         "is compared with (the builder's four-chip host)")
    args = ap.parse_args(argv)
    signal.signal(signal.SIGTERM, _deadline)
    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(1150)  # the whole run, compilation included, ends in time
    verdict = {"ok": False, "device": None}
    try:
        t0 = time.monotonic()
        if args.chips == 4:
            model, tok = build_phase(args.seed)
            out = run_child(f"sharded_phase({model!r}, {tok!r})", 1100)
            device = json.loads(out.strip().splitlines()[-1])["device"]
        else:
            run_child(f"kernels_phase({args.seed})", 600)
            model, tok = build_phase(args.seed)
            device = serve_phase(model, tok)
        from importlib.metadata import version

        cache = _cache_entries()
        say(phase="all", seconds=round(time.monotonic() - t0, 1), **cache,
            jax=version("jax"), libtpu=version("libtpu"))
        if device["count"] != args.chips:
            raise SystemExit(f"ran on {device['count']} device(s), not {args.chips}")
        if cache["compile_cache_entries"] <= 0:
            raise SystemExit(f"compile cache {cache['compile_cache']} is empty")
        verdict = {"ok": device["platform"] == "tpu", "device": device}
    finally:
        # the last line, whatever happened (an exception still propagates)
        print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

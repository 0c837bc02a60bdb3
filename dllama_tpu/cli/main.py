"""CLI frontend — the analog of the reference's `dllama` binary
(dllama.cpp:207-229, app.cpp:21-110).

Modes:
  inference  one-shot generation from --prompt, with per-token timing and the
             tok/s summary (dllama.cpp:10-105's report shape)
  chat       REPL with chat template + streaming EOS detection
             (dllama.cpp:121-205)
  serve      OpenAI-compatible HTTP server (the `dllama-api` binary's role)
  router     multi-replica front: one address over N `serve` replicas with
             config handshake, health/drain polling, prefix-affinity
             routing, and failover (the reference ROOT node's role over
             its worker mesh, serve/router.py)
  info       print the model header (llm.cpp:100-123's dump)

There is no `worker` mode: the reference needs one process per node because
its nodes are TCP peers; here multi-chip is a jax.sharding.Mesh inside one
process (use --mesh tp=8 etc.), and multi-host runs launch the same command
on every host via jax.distributed.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dllama-tpu",
        description="TPU-native distributed-llama: tensor/sequence/data-parallel LLM inference",
    )
    p.add_argument("mode", choices=["inference", "chat", "serve", "info",
                                    "router"])
    # required for every mode except `router` (which owns no engine —
    # replicas own their weights); main() enforces it per mode
    p.add_argument("--model", default=None, help=".m model file "
                   "(required for every mode except router)")
    p.add_argument("--tokenizer", help=".t tokenizer file")
    p.add_argument("--prompt", help="prompt text (inference mode)")
    p.add_argument("--steps", type=int, default=64, help="max tokens to generate")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--presence-penalty", type=float, default=0.0,
                   help="subtract this from logits of any already-seen token "
                        "(OpenAI presence_penalty semantics)")
    p.add_argument("--frequency-penalty", type=float, default=0.0,
                   help="subtract count*this from logits per occurrence "
                        "(OpenAI frequency_penalty semantics)")
    p.add_argument("--exact-topp", action="store_true",
                   help="reference-exact nucleus: full-vocab sort per step instead "
                        "of the approx-top-256 candidate set (slower on big vocabs)")
    p.add_argument("--seed", type=int, default=None, help="sampler seed (default: time)")
    p.add_argument("--spec", type=int, default=0, metavar="K",
                   help="prompt-lookup speculative decoding with K-token drafts "
                        "(greedy runs only — bit-identical output, fewer forwards "
                        "on repetitive text; 0 = off). In serve mode this is the "
                        "legacy alias for --spec-k")
    p.add_argument("--spec-k", type=int, default=None, metavar="K",
                   help="serve mode, needs --slots > 0: per-request speculative "
                        "decoding capacity AND default — the engine compiles a "
                        "K-draft verify cycle, every request speculates at K "
                        "unless its body passes its own spec_k (0..K; 0 opts "
                        "out). Greedy token streams are BIT-IDENTICAL spec on "
                        "or off; sampled/penalized requests ride the cycles "
                        "one exact token at a time, so mixed traffic batches "
                        "together. Telemetry: dllama_spec_* series, spec "
                        "objects in timings//debug/perf (default: --spec, "
                        "else 0 = off)")
    p.add_argument("--max-seq-len", type=int, default=None, help="clamp context length (RAM cap)")
    p.add_argument(
        "--mesh",
        default="auto",
        help="device mesh spec 'tp=4,dp=2,sp=1' or 'auto' (all devices on tp)",
    )
    p.add_argument("--no-mesh", action="store_true", help="single-device even if more exist")
    p.add_argument("--cache-dtype", choices=["bf16", "f32", "f8"], default="bf16",
                   help="KV cache element type; f8 (e4m3) halves cache HBM "
                        "traffic/footprint — 2x the slots or context per chip "
                        "at a small accuracy cost")
    p.add_argument("--kv-layout", choices=["auto", "dense", "paged"],
                   default="auto",
                   help="serve mode, needs --slots > 0: KV cache layout. "
                        "'paged' backs slots with a refcounted page pool + "
                        "block tables instead of a full per-slot context "
                        "reservation — bit-exact token streams, prefix reuse "
                        "shares pages copy-free, and admission becomes "
                        "capacity-aware (defers when the pool can't cover "
                        "prompt + one decode page). 'auto' (default) picks "
                        "'paged' on unsharded engines where the paged "
                        "flash-decode kernel's capability check passes "
                        "(any 8-row-aligned page size; f8 caches and "
                        "meshes stay 'dense'). Pin 'dense' to opt out, or "
                        "'paged' to force the layout regardless of kernel "
                        "capability (see MIGRATION.md)")
    p.add_argument("--page-size", type=int, default=128,
                   help="paged KV cache: rows per page (must divide the "
                        "context length; kv-layout auto shrinks it to "
                        "gcd(page-size, context) so short contexts stay "
                        "paged; any multiple of 8 rides the Pallas paged "
                        "kernel — no 64-row tileability requirement)")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="paged KV cache: pool size in pages; 0 = full "
                        "coverage (slots x context / page-size — same "
                        "capacity as dense). Smaller pools overcommit "
                        "capacity: more slots than HBM could densely hold, "
                        "admission-gated by actual page demand")
    p.add_argument("--kv-host-pages", type=int, default=0,
                   help="paged KV cache + radix cache: host-RAM spill tier "
                        "size in pages (0 = off). Radix LRU eviction swaps "
                        "cold pages device-to-host instead of discarding; a "
                        "returning prompt re-uploads them at admission and "
                        "re-prefills only what the tiers can't cover. "
                        "Transfers are billed (dllama_kv_spill_total, "
                        "kv_spill/kv_restore transfer sites); occupancy at "
                        "dllama_kv_host_pages_{total,used}")
    p.add_argument("--radix-cache", choices=["auto", "on", "off"],
                   default="auto",
                   help="serve mode, needs --slots > 0: cross-request radix "
                        "prefix cache over the paged KV pool — a global tree "
                        "keyed on token ids whose nodes hold refcounted page "
                        "references; admissions map the longest shared "
                        "prefix for free (shared system prompts, few-shot "
                        "templates, multi-turn chat become O(new tokens) "
                        "prefill), LRU leaves are reclaimed under capacity "
                        "pressure. 'auto' (default) = on whenever the KV "
                        "layout is paged; token streams are bit-exact on or "
                        "off. Telemetry: dllama_radix_* series, "
                        "GET /debug/radix")
    p.add_argument("--max-prefill-chunk", type=int, default=256,
                   help="prefill chunk cap (pow-2 chunks; larger = better MXU "
                        "utilization, more HBM for activations)")
    p.add_argument("--dequantize", action="store_true", help="load Q40 weights as bf16 (faster prefill, 4x HBM)")
    p.add_argument("--port", type=int, default=None,
                   help="HTTP port (default: 9990 in serve mode, 9980 in "
                        "router mode)")
    p.add_argument("--host", default="127.0.0.1",
                   help="HTTP bind address (serve/router modes)")
    p.add_argument("--frontend", choices=["aio", "threads"], default="aio",
                   help="serve mode: connection transport. 'aio' (default) "
                        "multiplexes every connection — accept, parse, SSE "
                        "fan-out, disconnect detection — on one selectors "
                        "event loop with a small fixed worker pool and one "
                        "SSE pump thread, so thousands of streams cost "
                        "thousands of sockets, not thousands of threads "
                        "(dllama_process_threads stays flat). 'threads' "
                        "keeps the thread-per-connection stdlib server as "
                        "the A/B baseline. Routes and HTTP semantics are "
                        "identical")
    p.add_argument("--aio-workers", type=int, default=0,
                   help="serve mode, --frontend aio: request-handling "
                        "worker threads (0 = min(8, cores); streams don't "
                        "occupy workers — only non-streaming completions "
                        "and probe/debug endpoints do)")
    p.add_argument("--sse-heartbeat-s", type=float, default=15.0,
                   help="serve mode: emit a `: keep-alive` SSE comment "
                        "frame on streams idle this long, so router/LB "
                        "idle timeouts can't kill a slow-decode stream "
                        "(0 = off; default 15)")
    p.add_argument("--replica-id", default=None,
                   help="serve mode: identity stamped on every response "
                        "(X-Replica-Id header + timings.replica) for "
                        "end-to-end attribution through the router "
                        "(default: host:port of the bound socket)")
    p.add_argument("--replica", action="append", default=None,
                   metavar="HOST:PORT",
                   help="router mode (repeatable, at least one): an engine "
                        "replica to front — a normal `dllama-tpu serve` "
                        "process; the router handshakes its config, polls "
                        "its health, and routes/fails-over across the set")
    p.add_argument("--affinity", choices=["on", "off"], default="on",
                   help="router mode: prefix-affinity routing — pin each "
                        "request's prefix fingerprint (shared system "
                        "prompt / leading prompt bytes) to the replica "
                        "that served it last, so the radix prefix cache "
                        "is warm (off = pure least-loaded, the A/B "
                        "baseline)")
    p.add_argument("--poll-s", type=float, default=0.5,
                   help="router mode: replica /health poll cadence in "
                        "seconds")
    p.add_argument("--router-workers", type=int, default=16,
                   help="router mode: worker threads (each in-flight "
                        "proxied request occupies one for its upstream "
                        "I/O)")
    p.add_argument("--failover-max", type=int, default=2,
                   help="router mode: mid-stream failover budget — resume "
                        "attempts per journaled stream when its replica "
                        "dies mid-SSE (capped exponential backoff with "
                        "jitter; 0 = fail the stream exactly once with "
                        "finish_reason=error, the pre-failover contract)")
    p.add_argument("--fleet-obs", choices=["on", "off"], default="on",
                   help="router mode: the mesh observability plane — "
                        "distributed trace propagation (X-Dllama-Trace hop "
                        "header + router-side spans), per-replica clock-"
                        "offset estimation, and the /router/trace|metrics|"
                        "fleet|requests/{id} fleet endpoints stay up but "
                        "empty of router spans when off (the bench A/B "
                        "baseline)")
    p.add_argument("--slots", type=int, default=0,
                   help="serve mode: continuous-batching slots (0 = single-request + prefix cache)")
    p.add_argument("--overlap", choices=["on", "off"], default="on",
                   help="serve mode, needs --slots > 0: overlapped decode "
                        "pipeline — dispatch chunk N+1 off device-resident "
                        "state before chunk N's tokens are consumed, so host "
                        "scheduling runs concurrently with device compute "
                        "(token-level stops lag at most one chunk; overrun "
                        "tokens are discarded). 'off' restores the lockstep "
                        "loop for A/B — token streams are identical")
    p.add_argument("--admit-budget-ms", type=float, default=None,
                   help="serve mode, needs --slots > 0: LEGACY phase-split "
                        "admission only (--prefill-budget 0): max decode "
                        "stall (ms) a joining prompt's prefill may insert per "
                        "visit (default 250; 0 = strict one-chunk-per-decode "
                        "interleaving). With the hybrid step (the default) "
                        "admissions ride the decode chunks and this knob is "
                        "inert")
    p.add_argument("--prefill-budget", default="auto", metavar="{auto,N,0}",
                   help="serve mode, needs --slots > 0: hybrid chunked "
                        "prefill — each fused decode chunk co-processes up "
                        "to this many prompt tokens of an admitting request "
                        "in the SAME device launch, so a long prompt never "
                        "stalls running streams. 'auto' (default) steers the "
                        "budget online from the windowed ITL headroom "
                        "against --slo-itl-ms (holds 64 with no target); an "
                        "integer pins it; 0 restores the legacy phase-split "
                        "admission (the A/B baseline). Token streams are "
                        "bit-exact across all settings")
    p.add_argument("--preempt", choices=["auto", "on", "off"], default="auto",
                   help="serve mode, needs --slots > 0: preempt-to-pages — "
                        "a running lower-priority request may be suspended "
                        "at a chunk boundary when a strictly higher-priority "
                        "request is blocked (no free slot / KV capacity); "
                        "its pages stay referenced (radix tree) and the "
                        "stream later resumes byte-identical with near-zero "
                        "recompute. auto = on (default)")
    p.add_argument("--tenant-weight", action="append", default=None,
                   metavar="NAME=W",
                   help="serve mode, needs --slots > 0: weighted fair "
                        "queueing across tenants (the `tenant` request body "
                        "field) within each priority class — repeatable, "
                        "e.g. --tenant-weight paid=4 --tenant-weight free=1; "
                        "unlisted tenants weigh 1")
    p.add_argument("--warmup", choices=["auto", "off"], default="off",
                   help="serve mode, needs --slots > 0: precompile the "
                        "declared compiled-shape universe at boot (decode/"
                        "spec scans, pow2 prefill chunks, pow2 hybrid "
                        "budget slices, the commit sample — each x plain/"
                        "penalized) BEFORE the scheduler takes traffic, so "
                        "the first real request pays zero XLA compile. "
                        "Coverage + timings at GET /debug/compile; default "
                        "off (opt-in — boot takes the compile time instead)")
    p.add_argument("--transfer-guard", choices=["off", "log", "strict"],
                   default="off",
                   help="serve mode, needs --slots > 0: guard the steady-"
                        "state decode/spec dispatch window with "
                        "jax.transfer_guard — every operand there is a "
                        "device-resident carry, so 'strict' turns an "
                        "unexpected implicit host->device upload (the PR 3 "
                        "invariant breaking) into an error instead of a "
                        "silently serialized pipeline; 'log' logs them. "
                        "Transfer accounting (dllama_transfers_total) is "
                        "always on regardless")
    p.add_argument("--admit-ttft-deadline-ms", type=float, default=None,
                   help="serve mode, needs --slots > 0: joiners older than this "
                        "pump their prefill to completion despite the stall "
                        "budget (hard TTFT bound; default off)")
    p.add_argument("--max-queue", type=int, default=0,
                   help="serve mode, needs --slots > 0: bound the admission "
                        "queue — requests beyond this depth are shed with "
                        "HTTP 429 + Retry-After (0 = unbounded)")
    p.add_argument("--stall-deadline-s", type=float, default=0.0,
                   help="serve mode, needs --slots > 0: watchdog deadline — a "
                        "device chunk silent for longer flips /health to "
                        "unhealthy (0 = watchdog off)")
    p.add_argument("--restart-max", type=int, default=0,
                   help="serve mode, needs --slots > 0: self-healing — on a "
                        "worker crash, warm-restart the engine in-process "
                        "(decode state + KV pool rebuilt against resident "
                        "weights, NO model reload; queued requests survive, "
                        "in-flight ones resume bit-exact) at most this many "
                        "times per --restart-window-s, with exponential "
                        "backoff. 0 = any crash is permanently unhealthy "
                        "(external supervisor owns the restart)")
    p.add_argument("--restart-window-s", type=float, default=60.0,
                   help="serve mode: the sliding window the --restart-max "
                        "budget counts warm restarts in; budget exhausted "
                        "within the window = stay down (default 60)")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="serve mode: TTFT SLO target in ms — terminal "
                        "requests over it burn dllama_slo_violations_total"
                        "{kind=ttft} and drop out of goodput; windowed "
                        "attainment at /debug/perf and "
                        "dllama_slo_attainment. Router mode: same target "
                        "judged from the CLIENT's seat (failover gaps "
                        "included) into dllama_router_slo_attainment and "
                        "GET /router/fleet (default: no target)")
    p.add_argument("--slo-itl-ms", type=float, default=None,
                   help="serve AND router mode: inter-token-latency SLO "
                        "target in ms "
                        "(mean ITL per request, same derivation as the "
                        "itl_ms metrics); violations burn "
                        "dllama_slo_violations_total{kind=itl} "
                        "(default: no target)")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="serve mode: on SIGTERM, stop admission (503) and "
                        "give in-flight requests this long to finish before "
                        "shutting down")
    p.add_argument("--faults", default=None, metavar="SPEC",
                   help="arm deterministic fault injection (testing/drills): "
                        "comma-separated point:action[:k=v...] clauses, e.g. "
                        "'engine.decode:raise:after=2' — see "
                        "dllama_tpu/utils/faults.py (also: $DLLAMA_FAULTS)")
    p.add_argument("--kernels", choices=["auto", "pallas", "xla"], default="auto")
    p.add_argument("--fuse-weights", action="store_true",
                   help="fused wqkv/w13 kernel launches (single-device engines; "
                        "ignored on a mesh)")
    p.add_argument("--moe", choices=["auto", "dispatch", "sort", "dense"], default="auto",
                   help="MoE compute: capacity-bucketed dispatch (O(k) FLOPs, rare "
                        "capacity drops), sort (grouped-GEMM ragged segments — "
                        "O(k) FLOPs AND exact), or exact dense all-experts")
    p.add_argument("--sync", choices=["bf16", "q80", "auto"], default="bf16",
                   help="tp activation exchange: bf16 (exact, default), q80 "
                        "(the reference's quantized payload), or auto — the "
                        "measured recommendation: q80 only at tp=2, where it "
                        "wins on BOTH byte accountings; at tp>=4 the compiled "
                        "HLO says the gather formulation costs more "
                        "(COLLECTIVES.md)")
    p.add_argument("--distributed", action="store_true",
                   help="multi-host: jax.distributed.initialize (run the same command on every host)")
    p.add_argument("--coordinator", default=None, help="host:port rendezvous (omit on TPU pods)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--log-format", choices=["text", "json"], default="text",
                   help="log line format: text (human) or json (one structured "
                        "object per line — request_id and other context as "
                        "fields; see dllama_tpu/utils/logs.py for the schema)")
    p.add_argument("--trace", metavar="DIR", help="write a jax.profiler trace "
                   "(XProf/TensorBoard; serve mode can instead capture on "
                   "demand via POST /debug/profile)")
    p.add_argument("--trace-buffer", type=int, default=2048, metavar="N",
                   help="request-flow span tracer: ring capacity in events "
                        "(serve mode exports it at GET /debug/trace — loads "
                        "in Perfetto — and GET /debug/requests, the "
                        "per-request flight recorder). 0 disables tracing "
                        "entirely: a no-op tracer, nothing recorded or "
                        "allocated (default 2048)")
    p.add_argument("--report", action="store_true",
                   help="print memory + per-token latency + collective-payload report")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def _load(args):
    import jax.numpy as jnp

    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.ops import matmul

    if args.distributed:
        from dllama_tpu.parallel.multihost import initialize

        initialize(args.coordinator, args.num_processes, args.process_id)
    matmul.BACKEND = args.kernels
    if args.exact_topp:
        # must land before the first sampler trace — NUCLEUS_K is a
        # trace-time constant of the fused decode step
        from dllama_tpu.engine import sampling

        sampling.NUCLEUS_K = None
    return load_model(
        args.model,
        args.tokenizer,
        max_seq_len=args.max_seq_len,
        mesh=None if args.no_mesh else args.mesh,
        cache_dtype={"bf16": jnp.bfloat16, "f32": jnp.float32,
                     "f8": jnp.float8_e4m3fn}[args.cache_dtype],
        dequantize=args.dequantize,
        max_prefill_chunk=args.max_prefill_chunk,
        sync=args.sync,
        kernels=args.kernels,
        moe_impl=args.moe,
        fuse_weights=args.fuse_weights,
    )


def cmd_info(args) -> int:
    from dllama_tpu.models.formats import read_header, tensor_plan

    cfg, header_size = read_header(args.model, args.max_seq_len)
    print(cfg.describe())
    total = sum(
        cfg.weight_type.nbytes(int(np.prod(shape))) if ft == cfg.weight_type else ft.nbytes(int(np.prod(shape)))
        for _, shape, ft in tensor_plan(cfg)
    )
    print(f"header: {header_size} B, weights: {total / 1e9:.2f} GB on disk")
    # what would actually run here: resolved matmul backend / attention impl
    # (the reference prints its CPU features at startup, nn-cpu-ops.cpp:
    # 1276-1294 — this is the TPU-side equivalent)
    try:
        from dllama_tpu.engine.kernel_select import resolve_kernels

        sel = resolve_kernels(cfg, cfg.seq_len, 1, args.kernels)
        attn = "flash" if sel.attn_fn is not None else "jnp"
        import jax

        print(f"this host: {len(jax.devices())}x {jax.devices()[0].platform}; "
              f"kernels={sel.backend} attention={attn}")
    except Exception as e:  # info must never fail on backend trouble
        print(f"this host: backend unavailable ({e!r})"[:120])
    return 0


def cmd_inference(args) -> int:
    from dllama_tpu.engine.engine import GenerationStats
    from dllama_tpu.engine.sampling import Sampler

    if not args.prompt:
        print("inference mode requires --prompt", file=sys.stderr)
        return 1
    if not args.tokenizer:
        print("inference mode requires --tokenizer", file=sys.stderr)
        return 1
    m = _load(args)
    tok = m.tokenizer
    sampler = Sampler(args.temperature, args.topp,
                      args.seed if args.seed is not None else int(time.time()),
                      presence=args.presence_penalty, frequency=args.frequency_penalty)
    prompt_tokens = tok.encode(args.prompt, add_bos=True)
    max_tokens = min(args.steps, m.engine.seq_len - len(prompt_tokens))
    stats = GenerationStats()

    from dllama_tpu.utils import profiling

    timer = profiling.TokenTimer()
    tok.reset_decoder()
    with profiling.trace(args.trace):
        timer.start()
        for t in m.engine.generate(
            prompt_tokens, max_tokens, sampler, stop_fn=tok.is_eos, stats=stats,
            spec=args.spec,
        ):
            timer.stop()
            piece = tok.decode(t)
            if piece:
                print(piece, end="", flush=True)
            timer.start()
    print()
    print(stats.summary(), file=sys.stderr)
    if args.report:
        print(profiling.memory_report(m.config, m.engine.params, m.engine.cache), file=sys.stderr)
        print(f"⏱  {timer.summary()}", file=sys.stderr)
        shape = dict(m.shardings.mesh.shape) if m.shardings else {}
        tp, sp = shape.get("tp", 1), shape.get("sp", 1)
        est = profiling.collective_bytes_per_token(m.config, tp=tp, sp=sp)
        print(
            f"🔗 est. inter-chip payload: {est['kb_per_token_per_chip']:.0f} kB/token/chip "
            f"(tp={tp} sp={sp})",
            file=sys.stderr,
        )
        # measured counterpart: the collective ops in the compiled step
        # (nn-network.cpp:483-492 counts real socket bytes; this counts the
        # real HLO collectives — scan bodies once per trip, see docstring)
        meas = m.engine.measured_collective_report()
        ops = ", ".join(f"{k}={v / 1024:.1f}kB" for k, v in meas["per_op"].items()) or "none"
        print(
            f"🔗 measured in compiled step: {meas['total_bytes'] / 1024:.1f} kB ({ops})",
            file=sys.stderr,
        )
    return 0


def cmd_chat(args) -> int:
    from dllama_tpu.engine.sampling import Sampler
    from dllama_tpu.tokenizer.chat import (
        ChatItem,
        ChatTemplate,
        ChatTemplateType,
        EosDetector,
        EosResult,
        chat_stops,
    )

    if not args.tokenizer:
        print("chat mode requires --tokenizer", file=sys.stderr)
        return 1
    m = _load(args)
    tok = m.tokenizer
    template = ChatTemplate(ChatTemplateType.UNKNOWN, tok.chat_template, "")
    stops = chat_stops(tok)
    sampler = Sampler(args.temperature, args.topp,
                      args.seed if args.seed is not None else int(time.time()),
                      presence=args.presence_penalty, frequency=args.frequency_penalty)

    print("💬 chat mode — empty line or Ctrl-D to exit")
    try:
        system = input("📢 system: ").strip()
    except EOFError:
        return 0
    items: list[ChatItem] = []
    if system:
        items.append(ChatItem("system", system))

    first = True
    while True:
        try:
            user = input("👱 user: ").strip()
        except EOFError:
            break
        if not user:
            break
        items.append(ChatItem("user", user))
        generated = template.generate(items, append_generation_prompt=True)
        # feed only the delta since the engine's KV cache holds the history
        prompt_tokens = tok.encode(generated.content, add_bos=first)
        items = []  # history lives in the KV cache from here on
        first = False
        if generated.public_prompt:
            print(generated.public_prompt, end="")

        detector = EosDetector(tok.eos_ids, stops, padding_left=2, padding_right=2)
        tok.reset_decoder()
        print("🤖 assistant: ", end="", flush=True)
        budget = m.engine.seq_len - m.engine.pos - len(prompt_tokens) - 1
        if budget <= 0:
            print("(context window exhausted)")
            break
        for t in m.engine.generate(prompt_tokens, budget, sampler, spec=args.spec):
            piece = tok.decode(t)
            res = detector.append(t, piece)
            delta = detector.get_delta()
            if delta:
                print(delta, end="", flush=True)
            if res == EosResult.EOS:
                break
        else:
            delta = detector.flush()
            if delta:
                print(delta, end="", flush=True)
        print()
    return 0


def _parse_tenant_weights(specs) -> dict[str, float] | None:
    """--tenant-weight NAME=W (repeatable) -> {name: weight}; malformed
    specs fail startup with a clear message instead of silently weighing 1."""
    if not specs:
        return None
    import math

    out: dict[str, float] = {}
    for spec in specs:
        name, sep, w = str(spec).partition("=")
        try:
            weight = float(w)
        except ValueError:
            weight = 0.0
        # non-finite weights corrupt the fair queue silently (NaN poisons
        # every tag comparison, inf zeroes a tenant's cost and starves the
        # rest) — reject them with the same startup error as w <= 0
        if not sep or not name or not math.isfinite(weight) or weight <= 0:
            raise SystemExit(
                f"--tenant-weight {spec!r}: expected NAME=W with finite "
                "W > 0")
        out[name] = weight
    return out


def cmd_serve(args) -> int:
    from dllama_tpu.serve.api import run_server

    m = _load(args)
    if m.tokenizer is None:
        print("serve mode requires --tokenizer", file=sys.stderr)
        return 1
    prefill_budget = args.prefill_budget
    if prefill_budget != "auto":
        try:
            prefill_budget = int(prefill_budget)
        except ValueError:
            print(f"--prefill-budget must be 'auto' or an integer, got "
                  f"{prefill_budget!r}", file=sys.stderr)
            return 1
        if prefill_budget < 0:
            print("--prefill-budget must be >= 0", file=sys.stderr)
            return 1
    return run_server(
        m,
        host=args.host,
        port=args.port if args.port is not None else 9990,
        n_slots=args.slots,
        default_temperature=args.temperature,
        default_topp=args.topp,
        # --spec-k is the serving-tier knob; --spec remains the legacy
        # alias (and the single-engine tier's greedy spec toggle)
        spec=args.spec_k if args.spec_k is not None else args.spec,
        default_seed=args.seed,
        admit_stall_budget_ms=args.admit_budget_ms,
        admit_ttft_deadline_ms=args.admit_ttft_deadline_ms,
        max_queue=args.max_queue,
        stall_deadline_s=args.stall_deadline_s,
        restart_max=args.restart_max,
        restart_window_s=args.restart_window_s,
        drain_timeout_s=args.drain_timeout_s,
        slo_ttft_ms=args.slo_ttft_ms,
        slo_itl_ms=args.slo_itl_ms,
        overlap=args.overlap == "on",
        kv_layout=args.kv_layout,
        page_size=args.page_size,
        kv_pages=args.kv_pages,
        kv_host_pages=args.kv_host_pages,
        radix_cache=args.radix_cache,
        prefill_budget=prefill_budget,
        preempt=args.preempt,
        tenant_weights=_parse_tenant_weights(args.tenant_weight),
        warmup=args.warmup,
        transfer_guard=args.transfer_guard,
        frontend=args.frontend,
        aio_workers=args.aio_workers,
        sse_heartbeat_s=args.sse_heartbeat_s,
        replica_id=args.replica_id,
    )


def cmd_router(args) -> int:
    from dllama_tpu.serve.router import run_router

    if not args.replica:
        print("router mode requires at least one --replica HOST:PORT",
              file=sys.stderr)
        return 1
    port = args.port if args.port is not None else 9980  # router's default
    return run_router(
        args.replica,
        host=args.host,
        port=port,
        poll_s=args.poll_s,
        affinity=args.affinity == "on",
        workers=args.router_workers,
        drain_timeout_s=args.drain_timeout_s,
        failover_max=args.failover_max,
        fleet_obs=args.fleet_obs == "on",
        trace_capacity=args.trace_buffer,
        slo_ttft_ms=args.slo_ttft_ms,
        slo_itl_ms=args.slo_itl_ms,
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.mode != "router" and not args.model:
        print(f"{args.mode} mode requires --model", file=sys.stderr)
        return 1
    from dllama_tpu.utils.logs import setup_logging

    # shared logger setup (utils/logs.py): --log-format json switches every
    # line to one structured object with request_id/fault_point/... fields
    setup_logging(fmt=args.log_format, verbose=args.verbose)
    # request-flow tracing rides every mode (serve exposes it over /debug/*;
    # inference/chat record into the same in-process ring) — configured
    # before anything that could emit a span
    from dllama_tpu.obs import trace

    trace.configure(args.trace_buffer)
    if args.mode != "router":  # the router owns no engine: nothing jits
        from dllama_tpu.obs.compile import place_compile_cache

        place_compile_cache()
    from dllama_tpu.utils import faults

    # $DLLAMA_FAULTS first, --faults wins when both are set; a bad spec
    # fails startup here, not by silently never firing
    faults.configure_from_env()
    if args.faults:
        faults.configure(args.faults)
    return {
        "info": cmd_info,
        "inference": cmd_inference,
        "chat": cmd_chat,
        "serve": cmd_serve,
        "router": cmd_router,
    }[args.mode](args)


if __name__ == "__main__":
    raise SystemExit(main())

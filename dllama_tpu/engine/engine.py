"""Inference engine: compiled prefill/decode steps + host-side driver.

Replaces the reference's executor/step-list machinery and RootLlmInference
driver (nn-executor.cpp, app.cpp:131-195): XLA *is* the executor here — one
jitted step function with a donated KV cache, driven by a host loop. The
reference's per-forward control packet broadcast (app.cpp:161-173) has no
analog: a pjit'd step over a mesh launches on all chips from one host call.

Prefill is chunked in power-of-two widths so a prompt of any length compiles
at most log2(max_chunk)+1 step variants (the reference instead fixes
nBatches=32 and pads the final chunk; we never compute padded positions).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.engine.sampling import Sampler
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import KVCache, forward
from dllama_tpu.obs import compile as compile_obs
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops.layers import build_rope_cache


def pow2_chunk(remaining: int, max_chunk: int) -> int:
    """Largest power-of-two width <= min(max_chunk, remaining): prompts of
    any length compile at most log2(max_chunk)+1 prefill step variants
    (shared by InferenceEngine.prefill and BatchEngine.add_step)."""
    c = min(max_chunk, 1 << (remaining - 1).bit_length())
    while c > remaining:
        c //= 2
    return c


@dataclass
class GenerationStats:
    """Per-token timing in the reference's report shape (dllama.cpp:93-104)."""

    prefill_tokens: int = 0
    prefill_s: float = 0.0
    decode_tokens: int = 0
    decode_s: float = 0.0

    @property
    def prefill_tok_s(self) -> float:
        return self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    def summary(self) -> str:
        return (
            f"Prefill: {self.prefill_tokens} tokens in {self.prefill_s*1000:.0f} ms "
            f"({self.prefill_tok_s:.1f} tok/s)\n"
            f"Decode:  {self.decode_tokens} tokens in {self.decode_s*1000:.0f} ms "
            f"({self.decode_tok_s:.1f} tok/s, {1000*self.decode_s/max(1,self.decode_tokens):.2f} ms/token)"
        )


class InferenceEngine:
    """Owns params + KV cache + compiled steps for one model replica.

    `shardings` (optional, from parallel/sharding.py) carries the mesh and the
    in/out shardings for the step function; without it everything runs on the
    default device (single chip — the reference's `--workers`-less mode).
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        params,
        batch: int = 1,
        cache_dtype=jnp.bfloat16,
        max_seq_len: int | None = None,
        max_prefill_chunk: int = 256,
        shardings=None,
        donate_cache: bool = True,
        attn_impl: str = "auto",  # 'auto' | 'jnp' | 'flash' (Pallas online-softmax)
        layer_unroll: int | bool = 1,  # lax.scan unroll over layers
        sync: str = "bf16",  # 'bf16' (exact, default) | 'q80' (quantized
        # exchange) | 'auto' (the data-earned policy: q80 iff tp=2 —
        # parallel/collectives.resolve_sync has the numbers)
        kernels: str = "auto",  # 'auto' | 'pallas' | 'xla' matmul backend
        moe_impl: str = "auto",  # 'auto' | 'dispatch' | 'sort' | 'dense' (ops.layers.moe_ffn)
        pp_micro: int = 1,  # GPipe microbatches on pp meshes (batch % pp_micro == 0)
        fuse_weights: bool = False,  # wqkv/w13 fused launches (unsharded only;
        # concatenates copies on device — caller keeps the originals alive)
    ):
        self.cfg = cfg
        self.params = params
        if cfg.recurrent and shardings is not None:
            raise ValueError(
                "a model with per-sequence recurrent state runs on one "
                "device: the state has no sharding under a mesh yet")
        if fuse_weights:
            if shardings is not None:
                raise ValueError("fuse_weights requires an unsharded engine "
                                 "(tp shards q and kv blocks at different granularity)")
            from dllama_tpu.models.llama import fuse_layer_weights

            # session fingerprint must hash the CALLER's layout — a session
            # saved unfused must resume on a fused engine and vice versa
            self._params_digest()
            self.params = dict(params, layers=fuse_layer_weights(params["layers"]))
        self.batch = batch
        self.seq_len = min(max_seq_len or cfg.seq_len, cfg.seq_len)
        self.max_prefill_chunk = max_prefill_chunk
        self.shardings = shardings
        self.rope_cache = build_rope_cache(cfg, self.seq_len)
        self.cache = KVCache.create(cfg, batch, cache_dtype, self.seq_len,
                                    conv_dtype=params["embedding"].dtype)
        self.pos = 0

        if shardings is not None:
            self.params = shardings.put_params(self.params)
            self.cache = shardings.put_cache(self.cache)
            self.rope_cache = shardings.put_replicated(self.rope_cache)

        # matmul + attention kernels resolved ONCE at construction (per-engine,
        # not a process-global read at trace time); gating rules shared with
        # BatchEngine via engine/kernel_select.py.
        from dllama_tpu.engine.kernel_select import (
            resolve_kernels,
            resolve_moe_impl,
        )

        moe_impl = resolve_moe_impl(moe_impl, shardings, cfg, self.params,
                                    kernels)
        sel = resolve_kernels(cfg, self.seq_len, batch, kernels, attn_impl,
                              shardings, moe_impl=moe_impl)
        mm, mm_in, attn_fn = sel.mm, sel.mm_in, sel.attn_fn
        self.backend = sel.backend
        self.kernel_route = sel.bucket_tag()
        if self.cache.state is not None:
            # the state carries the decode step the selection resolved
            self.cache = dataclasses.replace(
                self.cache, state=dataclasses.replace(
                    self.cache.state, step=sel.state_step))
        from dllama_tpu.parallel.collectives import resolve_sync

        self.sync = sync = resolve_sync(sync, shardings)
        col_fn = None
        if sync == "q80":
            # the reference's Q80 ZQ-pipe exchange as an ICI option: wo/w2
            # partial sums ride quantized (parallel/collectives.py). Only
            # meaningful with a tp axis; silently native otherwise.
            if shardings is not None and shardings.mesh.shape["tp"] > 1:
                from dllama_tpu.parallel.collectives import make_q80_col_matmul

                col_fn = make_q80_col_matmul(shardings.mesh)

        if shardings is not None and shardings.mesh.shape["pp"] > 1:
            # stage-split forward: GPipe shard_map over 'pp' (manual axis),
            # tp/dp composed by GSPMD inside each stage (parallel/pipeline.py).
            # pp_micro > 1 splits the batch into GPipe microbatches so prefill
            # and batched decode fill the pipeline bubble (B=1 decode keeps
            # pp_micro=1: pure sequential layer split). layer_unroll does not
            # apply (the stage schedule replaces the layer scan).
            if col_fn is not None:
                raise ValueError("--sync q80 is not supported on pp meshes yet")
            if pp_micro < 1 or batch % pp_micro != 0:
                raise ValueError(
                    f"pp_micro must be >= 1 and divide batch; got pp_micro={pp_micro} "
                    f"batch={batch}"
                )
            from dllama_tpu.parallel.pipeline import make_pp_forward

            pp_fwd = make_pp_forward(cfg, shardings.mesh, n_micro=pp_micro,
                                     attn_fn=attn_fn, mm=mm)

            def fwd(params, cache, tokens, pos, rope_cache, last_only=False):
                # pp computes all positions (stage schedule); callers slice
                logits, cache = pp_fwd(params, tokens, pos, cache, rope_cache)
                return (logits[:, -1:] if last_only else logits), cache
        else:
            def fwd(params, cache, tokens, pos, rope_cache, last_only=False):
                return forward(cfg, params, tokens, pos, cache, rope_cache, attn_fn,
                               unroll=layer_unroll, col_fn=col_fn, mm=mm, mm_in=mm_in,
                               moe_impl=moe_impl, last_only=last_only)

        donate = (1,) if donate_cache else ()
        self._donate_cache = donate_cache
        self._fwd = fwd  # speculative decoder builds on the same closure
        self._spec_decoders: dict = {}
        self._spec_h = None  # (device h, pos, cur): chunked-call history reuse
        self._step = jax.jit(partial(self._step_impl, fwd), donate_argnums=donate)
        self._decode_n = jax.jit(
            partial(self._decode_n_impl, fwd),
            static_argnums=(5,),
            donate_argnums=donate,
        )
        self._decode_sample_n = jax.jit(
            partial(self._decode_sample_n_impl, fwd),
            static_argnums=(6,),
            donate_argnums=donate,
        )
        self._decode_penalized_n = jax.jit(
            partial(self._decode_penalized_n_impl, fwd),
            static_argnums=(6,),
            donate_argnums=donate,
        )

    @staticmethod
    def _step_impl(fwd, params, cache, tokens, pos, rope_cache):
        logits, cache = fwd(params, cache, tokens, pos, rope_cache, last_only=True)
        return logits[:, -1], cache

    @staticmethod
    def _decode_n_impl(fwd, params, cache, token, pos, rope_cache, n):
        """n greedy decode steps fused into one device program (lax.scan) —
        no host roundtrip per token. The whole reference decode loop
        (dllama.cpp:69-88: control packet + forward + sample per token)
        collapses into a single XLA while-loop on chip."""

        def body(carry, _):
            token, cache, p = carry
            logits, cache = fwd(params, cache, token, p, rope_cache, last_only=True)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            return (nxt, cache, p + 1), nxt[:, 0]

        (_, cache, _), toks = jax.lax.scan(body, (token, cache, pos), None, length=n)
        return toks, cache

    @staticmethod
    def _decode_sample_n_impl(fwd, params, cache, token, pos, rope_cache,
                              key, n, temperature, topp):
        """n *sampled* decode steps fused on device — the sampler runs inside
        the scan (temperature/topp traced, sampling.sample_logits), so
        non-greedy generation also avoids the per-token host roundtrip the
        reference's decode loop pays (dllama.cpp:69-88)."""
        from dllama_tpu.engine.sampling import sample_logits

        def body(carry, _):
            token, cache, p, key = carry
            logits, cache = fwd(params, cache, token, p, rope_cache, last_only=True)
            key, sub = jax.random.split(key)
            nxt = sample_logits(logits[:, -1], sub, temperature, topp)[:, None]
            return (nxt, cache, p + 1, key), nxt[:, 0]

        (_, cache, _, _), toks = jax.lax.scan(body, (token, cache, pos, key), None, length=n)
        return toks, cache

    @staticmethod
    def _decode_penalized_n_impl(fwd, params, cache, token, pos, rope_cache,
                                 key, n, temperature, topp, counts,
                                 presence, frequency):
        """The sampled scan with OpenAI-style repetition penalties: token
        occurrence counts ride the scan carry (each fed token is counted
        before its successor is sampled), so penalized generation keeps the
        one-host-roundtrip-per-chunk property. Separate jit from the
        penalty-free scan — requests without penalties pay zero extra."""
        from dllama_tpu.engine.sampling import apply_penalties, sample_logits

        def body(carry, _):
            token, cache, p, key, counts = carry
            counts = counts.at[jnp.arange(counts.shape[0]), token[:, 0]].add(1)
            logits, cache = fwd(params, cache, token, p, rope_cache, last_only=True)
            key, sub = jax.random.split(key)
            pen = apply_penalties(logits[:, -1], counts, presence, frequency)
            nxt = sample_logits(pen, sub, temperature, topp)[:, None]
            return (nxt, cache, p + 1, key, counts), nxt[:, 0]

        (_, cache, _, _, _), toks = jax.lax.scan(
            body, (token, cache, pos, key, counts), None, length=n)
        return toks, cache

    # ------------------------------------------------------------------ core

    def step(self, tokens: np.ndarray) -> jax.Array:
        """Run T tokens at the current position; returns last-pos logits [B, V]."""
        t = tokens.shape[1]
        if self.pos + t > self.seq_len:
            raise ValueError(f"position {self.pos}+{t} exceeds seq_len {self.seq_len}")
        # compile attribution (ISSUE 14): the single-engine tier's jit
        # dispatches are ledger-scoped like the batched tier's, so its
        # compiles land under labeled fns instead of "untracked"
        toks_dev = jnp.asarray(tokens, jnp.int32)
        with compile_obs.LEDGER.scope(
                "single_step", f"m{t}",
                sig=lambda: compile_obs.sig_of(toks_dev)):
            logits, self.cache = self._step(
                self.params, self.cache, toks_dev, jnp.int32(self.pos),
                self.rope_cache
            )
        self.pos += t
        return logits

    def can_resume_at(self, pos: int) -> bool:
        """Can the next tokens be fed at row `pos`? Any row the KV cache
        holds, for a KV-only model; with recurrent state (which follows
        `self.pos` and cannot be rewound) only row 0 — the forward zeroes
        the state there — or where it stands."""
        return not self.cfg.recurrent or pos in (0, self.pos)

    def reset(self, pos: int = 0) -> None:
        """Rewind to `pos` (prefix-cache reuse keeps cache contents ≤ pos valid)."""
        if not self.can_resume_at(pos):
            raise ValueError(
                f"cannot rewind to row {pos}: the recurrent state stands at "
                f"row {self.pos}; reset(0) and recompute")
        self.pos = pos

    def measured_collective_report(self) -> dict:
        """Collective bytes MEASURED from the compiled decode step's HLO (the
        ops XLA actually emitted after SPMD partitioning), vs the analytic
        model in utils.profiling.collective_bytes_per_token. Collectives
        inside the layer scan are counted once per loop trip — construct the
        engine with layer_unroll=True for exact per-token totals.

        Costs one extra AOT compile of the T=1 step on first call (lower().
        compile() does not reuse the jit executable cache); memoized after."""
        if not hasattr(self, "_collective_report"):
            from dllama_tpu.utils.profiling import measured_collective_bytes

            tokens = jnp.zeros((self.batch, 1), jnp.int32)
            lowered = self._step.lower(
                self.params, self.cache, tokens, jnp.int32(0), self.rope_cache
            )
            self._collective_report = measured_collective_bytes(
                lowered.compile().as_text()
            )
        return self._collective_report

    # ------------------------------------------------------------- checkpoint

    def _session_fingerprint(self) -> str:
        c = self.cfg
        return (
            f"{c.dim}:{c.n_layers}:{c.n_kv_heads}:{c.head_size}:"
            f"{self.seq_len}:{self.batch}:{self.cache.k.dtype}:{self._params_digest()}"
        )

    def _params_digest(self) -> str:
        """Cheap weight-identity hash so a session saved against one checkpoint
        refuses to resume on a different model with the same geometry (ADVICE
        r1): leaf shapes/dtypes plus a few sampled values from each of up to 8
        leaves — O(bytes of a handful of scalars), not a full-weights hash."""
        if not hasattr(self, "_digest"):
            import hashlib

            h = hashlib.sha1()
            leaves = jax.tree.leaves(self.params)
            for leaf in leaves:
                h.update(f"{getattr(leaf, 'shape', ())}{getattr(leaf, 'dtype', '')}".encode())
            step = max(1, len(leaves) // 8)
            for leaf in leaves[::step]:
                sample = np.asarray(jax.device_get(jnp.ravel(leaf)[:4]))
                h.update(sample.tobytes())
            self._digest = h.hexdigest()[:16]
        return self._digest

    def save_session(self, path: str) -> None:
        """Persist the KV cache + position — resume a long conversation across
        process restarts. The reference has no checkpointing at all (SURVEY.md
        §5.4: its NaiveCache prefix reuse is in-memory only); this is the
        durable version of that capability."""
        import numpy as np

        k = np.asarray(self.cache.k)
        v = np.asarray(self.cache.v)
        # npz cannot represent ml_dtypes elements (an f8 cache loads back as
        # raw void): persist the BYTES plus the dtype name and re-view on load
        extra = {}
        if self.cache.state is not None:  # the recurrent state stands at pos
            conv = np.asarray(self.cache.state.conv)
            extra = dict(state_s=np.asarray(self.cache.state.s, np.float32),
                         state_conv=conv.view(np.uint8),
                         state_conv_dtype=str(conv.dtype))
        np.savez_compressed(
            path,
            fingerprint=self._session_fingerprint(),
            cache_dtype=str(k.dtype),
            pos=self.pos,
            k=k.view(np.uint8),
            v=v.view(np.uint8),
            **extra,
        )

    def load_session(self, path: str) -> None:
        """Restore a saved session (re-places the cache with the current mesh
        shardings, so a session saved single-chip resumes on a mesh and vice
        versa — device placement is orthogonal to the session state)."""
        import numpy as np

        with np.load(path) as data:
            fp = str(data["fingerprint"])
            if fp != self._session_fingerprint():
                raise ValueError(
                    f"session file does not match this engine: {fp!r} != "
                    f"{self._session_fingerprint()!r}"
                )
            if "cache_dtype" in data:  # bytes + dtype-name format
                dt = jnp.dtype(str(data["cache_dtype"]))
                k = data["k"].view(dt)
                v = data["v"].view(dt)
            else:
                # legacy format stored typed arrays directly; npz turns
                # ml_dtypes elements (bf16) into raw void — re-view them as
                # the engine dtype (the fingerprint already pinned it)
                k, v = data["k"], data["v"]
                if k.dtype.kind == "V":
                    dt = self.cache.k.dtype
                    k = k.view(np.uint8).view(dt).reshape(self.cache.k.shape)
                    v = v.view(np.uint8).view(dt).reshape(self.cache.v.shape)
            state = self.cache.state
            if state is not None:
                from dllama_tpu.models.llama import RecurrentState

                conv = data["state_conv"].view(
                    jnp.dtype(str(data["state_conv_dtype"])))
                state = RecurrentState(
                    jnp.asarray(data["state_s"], state.s.dtype),
                    jnp.asarray(conv.reshape(state.conv.shape)),
                    step=state.step)
            cache = KVCache(jnp.asarray(k), jnp.asarray(v), state,
                            self.cache.moe_stats)
            if self.shardings is not None:
                cache = self.shardings.put_cache(cache)
            self.cache = cache
            self.pos = int(data["pos"])

    def prefill(self, tokens: np.ndarray) -> jax.Array:
        """Chunked prefill; returns logits after the last token."""
        tokens = np.atleast_2d(np.asarray(tokens, dtype=np.int32))
        n = tokens.shape[1]
        if n == 0:
            raise ValueError("prompt must be non-empty")
        logits = None
        off = 0
        while off < n:
            chunk = pow2_chunk(n - off, self.max_prefill_chunk)
            logits = self.step(tokens[:, off : off + chunk])
            off += chunk
        return logits

    def decode_step(self, tokens: np.ndarray) -> jax.Array:
        return self.step(np.asarray(tokens, dtype=np.int32).reshape(self.batch, 1))

    def decode_greedy_n(self, token: np.ndarray, n: int) -> np.ndarray:
        """Fused n-step greedy decode on device; returns tokens [n, B]."""
        if self.pos + n > self.seq_len:
            raise ValueError(f"position {self.pos}+{n} exceeds seq_len {self.seq_len}")
        tok_dev = jnp.asarray(token, jnp.int32).reshape(self.batch, 1)
        with compile_obs.LEDGER.scope(
                "single_decode", f"n{n}",
                sig=lambda: compile_obs.sig_of(tok_dev)):
            toks, self.cache = self._decode_n(
                self.params,
                self.cache,
                tok_dev,
                jnp.int32(self.pos),
                self.rope_cache,
                n,
            )
        self.pos += n
        return np.asarray(toks)

    def decode_spec_greedy_n(self, history, token: int, n: int, k: int = 8,
                             ngram: int = 2) -> np.ndarray:
        """n exact-greedy tokens via prompt-lookup speculative decoding
        (engine/speculative.py): up to k tokens drafted from the sequence's
        own n-gram statistics are verified per forward, so repetitive text
        decodes several tokens per weight sweep. Output is bit-identical to
        decode_greedy_n; only the forward count changes.

        ``history``: the tokens already FED, MOST RECENT last — the full
        prompt+continuation, or any suffix of it (a chat turn's delta: tokens
        at earlier positions are marked unknown and simply can't be drafted
        from). ``token``: the last sampled, not-yet-fed token. B=1 engines
        only. self._spec_stats records {emitted, cycles} of the last call
        (emitted/cycles = realized speedup). Consecutive calls that continue
        exactly where the last one stopped reuse the on-device history — no
        per-chunk host rebuild (generate's chunked loop hits this path)."""
        if self.batch != 1:
            # a clean, actionable error instead of the old bare assert: the
            # batched serving tier has its own speculation (per-slot
            # accept/reject vectors, per-request spec_k) — point there
            raise ValueError(
                f"decode_spec_greedy_n drives a single sequence (batch==1, "
                f"got batch={self.batch}); for batched speculation use "
                "BatchEngine(spec=K) — its spec cycles serve every slot "
                "with per-request spec_k (serve --spec-k / body spec_k)")
        if self.pos + n > self.seq_len:
            raise ValueError(f"position {self.pos}+{n} exceeds seq_len {self.seq_len}")
        key = (k, ngram)
        if key not in self._spec_decoders:
            from dllama_tpu.engine.speculative import make_spec_decode

            self._spec_decoders[key] = make_spec_decode(
                self._fwd, self.seq_len, k, ngram, donate=self._donate_cache
            )
        cached = self._spec_h
        if cached is not None and cached[1] == self.pos and cached[2] == token:
            h = cached[0]  # continue the device-resident history
        else:
            hist = np.asarray(history, np.int32).reshape(-1)
            if hist.shape[0] > self.pos:
                raise ValueError(f"history length {hist.shape[0]} > pos {self.pos}")
            # unknown earlier positions hold -1: no real token id equals -1,
            # so the n-gram matcher can never draft across the unknown region
            h = np.full(self.seq_len + 1, -1, np.int32)
            h[self.pos - hist.shape[0] : self.pos] = hist
            h[self.pos] = token
            h = jnp.asarray(h)
        with compile_obs.LEDGER.scope(
                "single_spec", f"n{n}",
                sig=lambda: compile_obs.sig_of(h)):
            out, cnt, cyc, self.cache, h_out, pos = self._spec_decoders[key](
                self.params, self.cache, h, jnp.int32(token),
                jnp.int32(self.pos), self.rope_cache, n,
            )
        cnt = int(cnt)
        m = min(n, cnt)
        toks = np.asarray(out)[:m]
        # overshoot rewind: emitted tokens beyond n were fed rows we do not
        # keep (same stale-row invariant as generate's mid-chunk rewind).
        # h_out stays valid for the rewound position: index pos+m holds
        # out[m-1], the new unfed token.
        self.pos = int(pos) - (cnt - m)
        self._spec_stats = {"emitted": cnt, "cycles": int(cyc)}
        self._spec_h = (h_out, self.pos, int(toks[-1])) if m else None
        return toks

    def decode_sample_n(self, token: np.ndarray, n: int, sampler: Sampler,
                        counts: np.ndarray | None = None) -> np.ndarray:
        """Fused n-step sampled decode on device; returns tokens [n, B].
        Advances the sampler's PRNG key once per call. ``counts`` ([B, V]
        occurrence counts of the text so far, EXCLUDING the unfed ``token`` —
        it is counted in-scan) routes through the penalized scan when the
        sampler carries presence/frequency penalties."""
        if self.pos + n > self.seq_len:
            raise ValueError(f"position {self.pos}+{n} exceeds seq_len {self.seq_len}")
        sampler.key, sub = jax.random.split(sampler.key)
        args = (
            self.params,
            self.cache,
            jnp.asarray(token, jnp.int32).reshape(self.batch, 1),
            jnp.int32(self.pos),
            self.rope_cache,
            sub,
            n,
            jnp.float32(sampler.temperature),
            jnp.float32(sampler.topp),
        )
        with compile_obs.LEDGER.scope(
                "single_decode", f"n{n}",
                sig=lambda: compile_obs.sig_of(args[2])):
            if counts is not None and sampler.has_penalties:
                toks, self.cache = self._decode_penalized_n(
                    *args,
                    jnp.asarray(counts, jnp.int32).reshape(self.batch, -1),
                    jnp.float32(sampler.presence),
                    jnp.float32(sampler.frequency))
            else:
                toks, self.cache = self._decode_sample_n(*args)
        self.pos += n
        return np.asarray(toks)

    # ------------------------------------------------------------- generation

    def generate(
        self,
        prompt_tokens: list[int],
        max_tokens: int,
        sampler: Sampler,
        stop_fn: Callable[[int], bool] | None = None,
        stats: GenerationStats | None = None,
        chunk: int = 8,
        spec: int = 0,
    ) -> Iterator[int]:
        """Host generation loop: prefill the prompt, then decode in fused
        device chunks of up to `chunk` tokens (sampling included on device —
        one host roundtrip per chunk instead of per token; chunk=1 recovers
        token-at-a-time). Yields each token id; stops at max_tokens, seq_len,
        or when `stop_fn(token)` returns True. On an early stop mid-chunk the
        engine position is rewound so the KV cache stays prefix-consistent
        (cache rows past pos are masked, so over-decoded rows are harmless).

        ``spec`` > 0 enables prompt-lookup speculative decoding with that
        draft length for GREEDY runs (temperature 0) — bit-identical output,
        fewer forwards on repetitive text (decode_spec_greedy_n); sampled
        runs ignore it.
        """
        assert self.batch == 1, "generate() drives a single sequence; use step() for batches"
        if self.cfg.recurrent:
            # a chunk decoded past a stop is undone by rewinding the rows;
            # recurrent state cannot be rewound, so nothing is decoded that
            # might have to be: one token a launch, no draft window
            chunk, spec = 1, 0
        # penalized greedy is argmax of MODIFIED logits: speculative drafting
        # verifies against raw argmax, so penalties force the plain scan
        use_spec = spec > 0 and sampler.temperature == 0.0 and not sampler.has_penalties
        penalized = sampler.has_penalties
        t0 = time.perf_counter()
        logits = self.prefill(np.asarray([prompt_tokens], dtype=np.int32))
        if penalized:
            # OpenAI semantics: counts cover tokens SAMPLED in this
            # completion only — the prompt (and any KV-cached earlier turns)
            # carries no penalty, so output is independent of prefix-cache
            # state. No sampled tokens exist yet: the first token is
            # penalty-free by the same formula (all counts zero).
            v = logits.shape[-1]
            text: list[int] = []  # tokens sampled so far
        token = int(sampler(logits)[0])
        jax.block_until_ready(logits)
        t1 = time.perf_counter()
        if stats is not None:
            stats.prefill_tokens += len(prompt_tokens)
            stats.prefill_s += t1 - t0
        # registry mirror of the stats marks (one sample for the whole
        # chunked prefill — the block_until_ready above makes it device-real)
        ins.PREFILL_CHUNK_SECONDS.observe(t1 - t0)
        ins.PREFILL_TOKENS.inc(len(prompt_tokens))
        ins.TOKENS_GENERATED.inc()  # the prefill-sampled first token

        fed = list(prompt_tokens) if use_spec else None
        produced = 0
        yield token
        produced += 1
        if stop_fn is not None and stop_fn(token):
            return
        while produced < max_tokens and self.pos < self.seq_len:
            c = min(chunk, max_tokens - produced, self.seq_len - self.pos)
            start_pos = self.pos
            t2 = time.perf_counter()
            if use_spec:
                if self.pos + c + spec + 1 > self.seq_len:
                    use_spec = False  # no head-room for a draft window
                    toks = self.decode_sample_n(np.array([[token]]), c, sampler)
                else:
                    flat = self.decode_spec_greedy_n(fed, token, c, k=spec)
                    c = len(flat)
                    if c == 0:
                        break
                    fed.extend([token] + [int(t) for t in flat[:-1]])
                    toks = flat[:, None]
            elif penalized:
                # counts of the text so far EXCLUDING the unfed token (the
                # scan counts it before its successor is sampled); rebuilt
                # from host history per chunk — one [1, V] ship per chunk
                counts = np.bincount(text, minlength=v)[None, :v]
                toks = self.decode_sample_n(np.array([[token]]), c, sampler,
                                            counts=counts)
                text.append(token)
                text.extend(int(t) for t in toks[:-1, 0])
            else:
                toks = self.decode_sample_n(np.array([[token]]), c, sampler)
            if stats is not None:
                stats.decode_tokens += c
                stats.decode_s += time.perf_counter() - t2
            ins.DECODE_CHUNK_SECONDS.observe(time.perf_counter() - t2)
            for i in range(c):
                token = int(toks[i, 0])
                # counted at hand-off (the next() that returns this token):
                # after the yield it would never run for the final token of a
                # stop-terminated iteration, whose consumer breaks and leaves
                # the generator suspended
                ins.TOKENS_GENERATED.inc()
                yield token
                produced += 1
                stopped = stop_fn is not None and stop_fn(token)
                if stopped or produced >= max_tokens:
                    if i + 1 < c:
                        # rewind over-decoded rows (valid prefix ends after
                        # the row written when sampling this token)
                        self.reset(start_pos + i + 1)
                    if stopped:
                        return
                    break

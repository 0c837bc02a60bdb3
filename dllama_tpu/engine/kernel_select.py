"""Per-engine kernel selection — the ONE place the (kernels flag, attn_impl,
shardings, platform) tuple turns into concrete matmul/attention callables.

InferenceEngine and BatchEngine both construct their compiled steps from this
resolution, so the gating rules (sharded => shard_map'd Pallas or XLA, flash
only where pallas_call can lower, interpret off-TPU) can never diverge
between the latency and serving tiers.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Callable

from dllama_tpu.models.config import LlamaConfig, RopeType


#: Paged-layout attention routes (documented in the README "Paged KV cache"
#: routing table — scripts/checks.sh asserts the two stay in sync):
#: ``paged_kernel`` = the any-page-size Pallas flash-decode kernel with the
#: fused KV scatter (ops/pallas/paged_attention), ``paged_gather`` = the jnp
#: block-table gather fallback (ops/layers.paged_gqa_attention).
PAGED_ROUTES = ("paged_kernel", "paged_gather")


def pow2_buckets(cap: int) -> tuple[int, ...]:
    """The bounded pow2 shape universe ``engine.pow2_chunk`` can emit under
    ``cap`` — (1, 2, 4, ..., <=cap). This is THE bucket enumeration behind
    the compile ledger's shape contract (obs/compile): prefill chunks,
    hybrid budget slices, and the warmup precompile worklist all quantize
    to exactly this set, which is what makes the compiled-shape universe
    declarable (and its violations detectable) in the first place."""
    vals, c = [], 1
    while c <= max(1, int(cap)):
        vals.append(c)
        c *= 2
    return tuple(vals)


@dataclass
class KernelSelection:
    mm: Callable  # matmul for output-dim-sharded / replicated weights
    mm_in: Callable | None  # matmul for input-dim-sharded weights (wo/w2)
    attn_fn: Callable | None  # attention impl; None = jnp gqa_attention
    backend: str  # 'pallas' | 'xla' (what the quantized matmuls run on)
    attn_route: str = "jnp"  # which attention path attn_fn resolves to:
    # 'jnp' | 'flash' | 'sharded_flash' | 'ring' | 'paged_kernel' |
    # 'paged_gather' | 'no_cache_rows' (a model none of whose layers holds
    # cache rows) — the single string obs/README/the benchmark quote for
    # "what actually runs"
    interpret: bool = False  # Pallas interpret mode baked into attn_fn (and
    # what ops.matmul derives for the matmuls): true only off-TPU

    def __post_init__(self):
        from dllama_tpu.ops.matmul import device_platform

        if self.interpret and device_platform() == "tpu":
            raise RuntimeError(
                "kernel selection carries interpret=True on a TPU: the "
                "serving path must run compiled kernels")

    @property
    def route(self) -> str:
        """The mixers' routes: `attn_route` (with '.window' where the model
        has windowed layers and that route clips their walk:
        'paged_kernel.window'; '.latent' where its cache rows are latent,
        '.latent.rope_yarn' where they are rotated ones;
        '.heads48g64w' where the global and the windowed layers have a head
        count each, so the kernel runs at two folds in one program, and
        '.ropes2' where they have a rope table each),
        and behind a '+' each the recurrent state's
        decode step and element type where the model has one
        ('paged_kernel+ssm_step.float32', '...+kda_step.float32',
        '...+retention_step.float32') and the expert layers' route
        where it has experts ('paged_kernel.window+moe_grouped',
        '...+moe_grouped.groups4of8' where the selection is group-limited)."""
        return (self.attn_route
                + (f"+{self.state_route}" if self.state_route else "")
                + (f"+moe_{self.moe_route}" if self.moe_route else ""))

    def bucket_tag(self) -> str:
        """'backend/route' — the variant tag the compile ledger's
        shape-bucket contract stamps on each declared bucket, so a
        coverage dump says WHICH compiled universe (dense vs paged, jnp vs
        flash, which state step) the buckets belong to."""
        return f"{self.backend}/{self.route}"

    state_step: Callable | None = None  # recurrent models: the whole-batch
    # decode step on the layer-stacked state (models/llama.RecurrentState
    # carries it to the mixer); None = the jnp step on a layer's slice
    state_route: str = ""  # '' (no recurrent state) | 'ssm_step.<dtype>' (the
    # in-place Pallas kernel, ops/pallas/ssm_step) | 'ssm_jnp.<dtype>': what
    # the state-space layers' decode step runs on and the state's element
    # type — a fallback or a narrowed state shows in the tag, never silently
    moe_impl: str = "auto"  # the expert layers' scheme as resolved
    # (ops.layers.moe_ffn): 'grouped' = the Q40 kernel over the expert axis
    moe_route: str = ""  # '' (no experts) | 'grouped' | 'jnp': what the
    # route tag says of it — a fallback shows there, never silently
    fused_scatter_max_t: int | None = None  # paged_kernel route only: the
    # widest chunk (query rows per slot) whose new-KV scatter stays fused
    # inside the kernel launch. A speculative verify forward is spec_k+1
    # rows wide, so engines log when their K rides the per-layer
    # pre-scatter path instead (still correct — one XLA scatter per layer
    # per cycle — just not the zero-extra-dispatch fused write)


def resolve_state_step(cfg: LlamaConfig, batch: int, backend: str,
                       state_dtype=None) -> tuple[Callable | None, str]:
    """(step, route) of the recurrent layers' decode step for a model with
    recurrent state (state-space layers: `ssm_step`; delta-rule layers:
    `kda_step`; power-retention layers: `retention_step`), (None, '') for any
    other. The in-place Pallas kernel serves
    where the quantized matmuls run on Pallas and the kernel takes the
    state's shape and element type (32-bit); everything else is the jnp
    step, and the route says so."""
    if not cfg.recurrent:
        return None, ""
    import jax.numpy as jnp

    from dllama_tpu.ops.matmul import device_platform, resolve_backend

    name = cfg.state_kind
    if name == "retention":
        from dllama_tpu.ops.pallas.retention_step import (
            retention_step as step, supported)
        supported = partial(supported, head_size=cfg.head_size)
    elif name == "kda":
        from dllama_tpu.ops.pallas.kda_step import kda_step as step, supported
    else:
        from dllama_tpu.ops.pallas.ssm_step import ssm_step as step, supported
    dtype = jnp.dtype(jnp.float32 if state_dtype is None else state_dtype)
    shape = (cfg.n_state_layers, batch, *cfg.state_shape)
    if resolve_backend(backend) == "pallas" and supported(shape, dtype):
        return (partial(step, interpret=device_platform() != "tpu"),
                f"{name}_step.{dtype.name}")
    return None, f"{name}_jnp.{dtype.name}"


def resolve_moe_impl(moe_impl: str, shardings, cfg: LlamaConfig, params,
                     kernels: str) -> str:
    """MoE compute-scheme resolution shared by both engines. 'auto' resolves
    to 'grouped', the Q40 kernel over the expert axis
    (ops/pallas/q40_matmul.q40_expert_matmul), where the quantized matmuls
    run on Pallas (the engine's `kernels` choice), the engine is unsharded
    and the kernel takes the expert stacks' shapes and the activations' type; an
    explicit 'grouped' that cannot run is refused. On an
    expert-parallel mesh (ep > 1) the 'sort' scheme is OFF the table:
    jax.lax.ragged_dot has no correct GSPMD partitioning over a sharded
    group (expert) axis on this backend — the partitioned lowering drifts
    far beyond accumulation noise (~3e-2 on a 64-dim toy). The ep layout
    was designed for the dense all-experts einsum (parallel/sharding.py:
    "the all-experts einsum psums over ep under GSPMD"), so 'auto'
    resolves to 'dense' there and an explicit 'sort' is rejected loudly
    instead of serving wrong numerics."""
    ep = shardings.mesh.shape.get("ep", 1) if shardings is not None else 1
    if ep > 1:
        if moe_impl == "sort":
            raise ValueError(
                "moe_impl='sort' is unsupported on ep>1 meshes: ragged_dot "
                "partitions incorrectly over a sharded expert axis; use "
                "'dense' (exact) or 'dispatch'")
        if moe_impl == "auto":
            return "dense"
    if cfg.n_experts and moe_impl in ("auto", "grouped"):
        from dllama_tpu.ops.matmul import engine_matmul
        from dllama_tpu.ops.pallas.q40_matmul import expert_supported

        layers = params["layers"]
        backend = engine_matmul(kernels, shardings).keywords["backend"]
        ok = (shardings is None and backend == "pallas" and all(
            expert_supported(layers[w], params["embedding"].dtype)
            for w in ("moe_w1", "moe_w2", "moe_w3")))
        if moe_impl == "grouped" and not ok:
            raise ValueError(
                "moe_impl='grouped' needs unsharded Pallas kernels, Q40 "
                "expert weights of k % 256 == 0 and n % 128 == 0, and "
                "bfloat16 activations")
        return "grouped" if ok else "auto"
    return moe_impl


def resolve_kernels(
    cfg: LlamaConfig,
    seq_len: int,
    batch: int,
    kernels: str = "auto",  # 'auto' | 'pallas' | 'xla'
    attn_impl: str = "auto",  # 'auto' | 'jnp' | 'flash'
    shardings=None,
    paged: bool = False,  # paged KV layout: route the paged attention path
    page_size: int = 0,
    cache_dtype=None,  # KV pool element type (paged capability check);
    # None = bf16, the serving default
    state_dtype=None,  # recurrent state's element type; None = float32
    moe_impl: str = "auto",  # as resolve_moe_impl left it
) -> KernelSelection:
    """Resolution rules:

    * unsharded on TPU (or kernels='pallas' anywhere): fused Pallas kernels,
      flash attention; off-TPU they run in interpret mode.
    * tp/dp mesh, auto-on-TPU or forced pallas: shard_map'd Pallas
      (parallel/sharding.pallas_mms + pallas_attn) — each chip runs the fused
      kernel on its local shard; wo/w2 partials psum over ICI.
    * any other sharded case: XLA path — pallas_call has no GSPMD
      partitioning rule, so outside shard_map it would gather sharded
      operands per call (VERDICT r2 weak #1 / ADVICE r1).
    * sp meshes keep their ring-attention shard_map (shardings.attn_fn).
    """
    from dllama_tpu.ops.matmul import device_platform, engine_matmul

    mm = engine_matmul(kernels, shardings)
    backend = mm.keywords["backend"]
    mm_in = None
    on_tpu = device_platform() == "tpu"

    sharded_pallas = (
        shardings is not None
        and shardings.supports_sharded_pallas()
        and (kernels == "pallas" or (kernels == "auto" and on_tpu))
    )
    if sharded_pallas:
        mm, mm_in = shardings.pallas_mms(batch)
        backend = "pallas"

    state_step, state_route = resolve_state_step(cfg, batch, backend,
                                                 state_dtype)
    moe = dict(moe_impl=moe_impl, moe_route=(
        "" if not cfg.n_experts else
        ("grouped" if moe_impl == "grouped" else "jnp")
        # a group-limited selection: groups kept of groups
        + (f".groups{cfg.expert_groups_kept}of{cfg.n_expert_groups}"
           if cfg.grouped_routing else "")))
    windowed = cfg.n_window_layers > 0

    if not cfg.n_attn_layers:
        # no layer holds cache rows: there is no attention to route, in
        # either layout (the caches have a layer axis of 0)
        return KernelSelection(mm=mm, mm_in=mm_in, attn_fn=None,
                               backend=backend, attn_route="no_cache_rows",
                               interpret=not on_tpu, state_step=state_step,
                               state_route=state_route, **moe)
    if paged and shardings is None:
        # paged KV cache (BatchEngine --kv-layout paged; unsharded only — the
        # page pool has no slot axis for a dp mesh to shard, and BatchEngine
        # rejects paged+mesh at construction; a sharded resolve_kernels call
        # falls through to the dense rules below as defense in depth).
        # attn_fn=None means models.llama.forward defaults to the jnp gather
        # fallback (ops.layers.paged_gqa_attention), valid everywhere but
        # re-materializing the whole paged view through XLA each step; the
        # general flash-decode kernel (scalar-prefetched block tables,
        # pages DMA'd as whole blocks of kv heads through a ring that does
        # not drain between grid steps, the new KV rows blended into the
        # sweep's own copy of their page) routes on an explicit
        # CAPABILITY check — dtype/head-dim/page-geometry, ANY page size —
        # not the old whole-64-row-tile gate.
        from dllama_tpu.ops.pallas.paged_attention import (
            FUSED_SCATTER_MAX_T,
            paged_decode_attention,
            paged_decode_supported,
        )

        import jax.numpy as jnp

        attn_fn = None
        route = "paged_gather"
        fused_cap = None
        if attn_impl != "jnp" and paged_decode_supported(
            (cfg.n_heads, cfg.cache_row), page_size,
            kv_dtype=cache_dtype if cache_dtype is not None else jnp.bfloat16,
        ) and (attn_impl == "flash" or on_tpu):
            def attn_fn(q, k_pool, v_pool, tables, pos, new_k, new_v, active,
                        layer, **kind):
                # the pools are the whole layer-stacked arrays: the kernel
                # indexes `layer` (models/llama.run_layers carries them); a
                # windowed layer brings window=W and the sweep clips its
                # walk, a latent layer latent=rank and its score scale
                return paged_decode_attention(
                    q, k_pool, v_pool, tables, pos, new_k, new_v, active,
                    layer=layer, interpret=not on_tpu, **kind)

            # models/llama._layer hands the new KV rows to the kernel
            # instead of paying a separate scatter dispatch per layer; the
            # fused write serves chunks up to FUSED_SCATTER_MAX_T rows —
            # decode (t=1) and spec verify (t=spec_k+1) both ride it as
            # long as spec_k+1 fits (wider verifies pre-scatter via XLA,
            # identical results)
            attn_fn.fused_kv_scatter = True
            route = "paged_kernel"
            fused_cap = FUSED_SCATTER_MAX_T
        if windowed:
            route += ".window"  # both paged routes take the window
        if cfg.latent:
            route += ".latent"  # and the latent row (one pool, read once)
        route += kinds_tag(cfg)
        return KernelSelection(mm=mm, mm_in=mm_in, attn_fn=attn_fn,
                               backend=backend, attn_route=route,
                               interpret=not on_tpu,
                               state_step=state_step, state_route=state_route,
                               fused_scatter_max_t=fused_cap, **moe)

    if windowed and shardings is not None:
        raise ValueError("a model with windowed attention layers serves on "
                         "one device: no sharded attention route takes a "
                         "window yet")
    attn_fn = shardings.attn_fn(batch) if shardings is not None else None
    route = "ring" if attn_fn is not None else "jnp"
    if windowed:
        # the dense layouts' flash kernel takes no window yet: the jnp
        # attention masks it (the serving path is the paged kernel above)
        route = "jnp.window"
    elif cfg.latent:
        # nor a latent row (models/llama._mla_mixer's jnp attention)
        route = "jnp.latent"
    elif attn_fn is None and attn_impl != "jnp":
        from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention, supported

        if supported((cfg.n_heads, cfg.head_size), seq_len):
            if sharded_pallas:
                attn_fn = shardings.pallas_attn(batch, interpret=not on_tpu)
                route = "sharded_flash"
            elif attn_impl == "flash" or (on_tpu and shardings is None):
                route = "flash"
                attn_fn = partial(
                    flash_gqa_attention, interpret=not on_tpu,
                    # kv grids bucketed by live-context length — decode steps
                    # and early prefill chunks alike. Opt-in: exactness is
                    # tested and the lax.switch compiles for v5e, but the
                    # flip needs a measured shallow-pos win at S=8192 with no
                    # deep-pos regression, and no chip run has timed it yet.
                    s_buckets=os.environ.get("DLLAMA_FLASH_BUCKETS") == "1")

    return KernelSelection(mm=mm, mm_in=mm_in, attn_fn=attn_fn,
                           backend=backend, attn_route=route + kinds_tag(cfg),
                           interpret=not on_tpu, state_step=state_step,
                           state_route=state_route, **moe)


def kinds_tag(cfg: LlamaConfig) -> str:
    """What the route says of attention whose shape goes by the layer's
    kind: the two head counts, and that there are two rope tables; of
    latent attention that rotates, its one table's type ('.rope_llama',
    '.rope_yarn': the rows in the cache are rotated ones)."""
    if cfg.latent:
        if cfg.rope_type == RopeType.NONE:
            return ""
        spec = cfg.rope_spec
        return ".rope_" + (spec.type if spec else cfg.rope_type).name.lower()
    return ((f".heads{cfg.n_heads}g{cfg.window_heads}w" if cfg.window_heads else "")
            + (".ropes2" if cfg.global_rope is not None else ""))

"""Sharding rules: the reference's TP decomposition as PartitionSpecs.

Maps one-to-one onto the reference's slicers (nn-core.cpp:170-238):
  sliceRowMatmul  (q/k/v/w1/w3/wcls, output-dim shard) -> P(..., 'tp') on out
  sliceColMatmul  (wo/w2, input-dim shard + merge-add) -> P(..., 'tp', ...) on in
  sliceKvCache / sliceMultiHeadAtt (head shard)        -> cache P on kv-head axis
  + the axis the reference lacks: cache seq axis on 'sp' (ring/context parallel)

Under pjit, XLA emits the collectives the reference hand-codes: the
col-matmul partial-sum exchange (SYNC_NODE_SLICES + OP_MERGE_ADD,
nn-network.cpp:521-554) becomes a reduce-scatter/all-gather pair on ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.ops.quant import QTensor
from dllama_tpu.models.llama import KVCache


# specs for stacked per-layer weights: leading L axis, then (in, out)
_ROW_SHARD = P(None, None, "tp")  # output-dim sharded (reference "row" slice)
_COL_SHARD = P(None, "tp", None)  # input-dim sharded (reference "col" slice)

LAYER_SPECS = {
    "wq": _ROW_SHARD,
    "wk": _ROW_SHARD,
    "wv": _ROW_SHARD,
    "w1": _ROW_SHARD,
    "w3": _ROW_SHARD,
    "wo": _COL_SHARD,
    "w2": _COL_SHARD,
    "rms_att": P(None, None),
    "rms_ffn": P(None, None),
    # MoE (expert axis on 'ep'; per-expert in/out dims keep the tp pattern):
    # leaves are [L, E, in, out] operands, gate is [L, dim, E] replicated —
    # the all-experts einsum psums over ep under GSPMD.
    "moe_gate": P(None, None, None),
    "moe_w1": P(None, "ep", None, "tp"),
    "moe_w3": P(None, "ep", None, "tp"),
    "moe_w2": P(None, "ep", "tp", None),
}


class LlamaShardings:
    """Placement rules bound to a concrete mesh."""

    def __init__(self, mesh: Mesh, cfg: LlamaConfig):
        self.mesh = mesh
        self.cfg = cfg
        tp = mesh.shape["tp"]
        sp = mesh.shape["sp"]
        pp = mesh.shape["pp"]
        if cfg.n_kv_heads % tp != 0:
            # the reference's hard requirement nNodes <= nKvHeads (app.cpp:201-203);
            # ours is divisibility of the kv-head axis.
            raise ValueError(f"n_kv_heads={cfg.n_kv_heads} not divisible by tp={tp}")
        if cfg.seq_len % max(sp, 1) != 0:
            raise ValueError(f"seq_len={cfg.seq_len} not divisible by sp={sp}")
        if pp > 1:
            if cfg.n_layers % pp != 0:
                raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={pp}")
            if sp > 1:
                raise ValueError("pp x sp composition is not supported; use pp with tp/dp")

    def _named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _sanitize(self, spec: P, *shapes) -> P:
        """Replicate any spec axis that does not evenly divide the leaf's dim
        (for every given shape): device placement requires exact tiling, and
        an oddly-sized tensor (e.g. a non-power-of-two vocab on wcls) should
        load replicated rather than crash — the reference simply refuses such
        configs (nNodes must divide every slice, nn-core.cpp:170-238)."""
        n = max(len(s) for s in shapes)
        axes = list(spec) + [None] * (n - len(spec))
        out = []
        for i, ax in enumerate(axes):
            if ax is not None and any(
                len(s) > i and s[i] % self.mesh.shape[ax] != 0 for s in shapes
            ):
                ax = None
            out.append(ax)
        return P(*out)

    def _expand(self, spec: P, leaf):
        """Spec for one leaf (QTensor packed/scales share one spec — both are
        [in?, out] shaped). Lazy (memmap-backed) Q40 leaves follow the same
        rule."""
        from dllama_tpu.models.formats import LazyQ40, LazyQ40Stack

        if isinstance(leaf, (QTensor, LazyQ40, LazyQ40Stack)):
            tp = self.mesh.shape["tp"]
            axes = tuple(spec)
            if isinstance(leaf, QTensor):
                kdim = leaf.scales.shape[-2]
                shapes = (leaf.packed.shape, leaf.scales.shape)
            else:
                kdim = leaf.scales_shape[-2]
                shapes = (leaf.packed_shape, leaf.scales_shape)
            if len(axes) >= 2 and axes[-2] == "tp" and kdim % tp != 0:
                # 'tp' on the contraction dim splits the 32-elem quant-block
                # axis: it must hold tp whole blocks (col-shard, moe_w2)
                raise ValueError(
                    f"Q40 col-shard needs in_dim % (32*tp) == 0; "
                    f"got {kdim * 32} with tp={tp}"
                )
            spec = self._sanitize(spec, *shapes)
            return QTensor(spec, spec)
        if hasattr(leaf, "shape"):
            spec = self._sanitize(spec, leaf.shape)
        return spec

    def param_spec(self, name: str, leaf):
        """Spec for a named param leaf ('embedding', 'wcls', 'layers.<short>')."""
        if name == "embedding":
            spec = P(None, None)  # replicated; vocab shard lives on wcls
        elif name == "final_norm":
            spec = P(None)
        elif name == "wcls":
            spec = P(None, "tp")
        else:
            spec = LAYER_SPECS[name.split(".")[-1]]
            if self.mesh.shape["pp"] > 1:
                # stage-split: the stacked layer axis shards over 'pp'
                spec = P("pp", *tuple(spec)[1:])
        return self._expand(spec, leaf)

    def param_spec_tree(self, params) -> dict:
        """A pytree of PartitionSpecs congruent with the params pytree."""
        return {
            "embedding": self.param_spec("embedding", params["embedding"]),
            "final_norm": self.param_spec("final_norm", params["final_norm"]),
            "wcls": self.param_spec("wcls", params["wcls"]),
            "layers": {
                name: self.param_spec(f"layers.{name}", leaf)
                for name, leaf in params["layers"].items()
            },
        }

    def param_put(self, name: str, leaf):
        """Shard-direct placement of one host-resident param leaf: each device
        receives only its shard — a model bigger than one chip's HBM never
        materializes on a single device (the reference's slice-then-ship,
        nn-network.cpp:775-869, without the wire). Lazy Q40 leaves go further:
        each shard's bytes are decoded straight off the `.m` memmap on demand,
        so a multi-host load never materializes the full tensor on ANY host."""
        from dllama_tpu.models.formats import LazyQ40, LazyQ40Stack
        from dllama_tpu.parallel.multihost import device_put_sharded

        spec = self.param_spec(name, leaf)
        if isinstance(leaf, (LazyQ40, LazyQ40Stack)):
            sh = self._named(spec.packed)  # QTensor(spec, spec): shared P

            def memo(fn):
                # make_array_from_callback invokes the callback once PER
                # addressable device with no dedup — replicated mesh axes
                # (dp, pp-replicated wcls) would re-decode identical bytes
                cache: dict = {}

                def cb(idx):
                    key = tuple((s.start, s.stop, s.step) for s in idx)
                    if key not in cache:
                        cache[key] = fn(*idx)
                    return cache[key]

                return cb

            packed = jax.make_array_from_callback(
                leaf.packed_shape, sh, memo(leaf.packed_shard)
            )
            scales = jax.make_array_from_callback(
                leaf.scales_shape, sh, memo(leaf.scales_shard)
            )
            return QTensor(packed, scales)
        return jax.tree.map(
            lambda x, s: device_put_sharded(x, self._named(s)),
            leaf,
            spec,
            is_leaf=lambda x: isinstance(x, P),
        )

    def put_params(self, params):
        from dllama_tpu.parallel.multihost import device_put_sharded

        specs = self.param_spec_tree(params)
        return jax.tree.map(
            lambda x, s: device_put_sharded(x, self._named(s)),
            params,
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _batch_axis(self, batch: int) -> str | None:
        # batch shards over dp only when divisible (a single sequence stays
        # replicated over dp)
        return "dp" if batch % self.mesh.shape["dp"] == 0 else None

    def cache_spec(self, batch: int) -> P:
        # [n_layers, batch, n_kv_heads, seq, head_size]
        layer_axis = "pp" if self.mesh.shape["pp"] > 1 else None
        return P(layer_axis, self._batch_axis(batch), "tp", "sp", None)

    def put_cache(self, cache: KVCache) -> KVCache:
        from dllama_tpu.parallel.multihost import device_put_sharded

        s = self._named(self.cache_spec(batch=cache.k.shape[1]))
        return KVCache(device_put_sharded(cache.k, s), device_put_sharded(cache.v, s))

    def put_replicated(self, x):
        from dllama_tpu.parallel.multihost import device_put_sharded

        return device_put_sharded(x, self._named(P()))

    def attn_fn(self, batch: int):
        """shard_map'd sequence-parallel attention when sp > 1, else None
        (plain full-cache GQA; XLA handles tp head sharding by itself)."""
        if self.mesh.shape["sp"] == 1:
            return None
        from dllama_tpu.parallel.ring_attention import make_sp_attention

        return make_sp_attention(self.mesh, self._batch_axis(batch))

    def tokens_spec(self) -> P:
        return P("dp", None)

    # ---------------------------------------------- sharded Pallas kernels
    #
    # pallas_call has no GSPMD partitioning rule, so under a mesh the fused
    # Q40 kernels must run inside shard_map: each chip executes the kernel on
    # its local weight shard and XLA only sees the manual region's collectives.
    # This keeps the reference's TP decomposition (llm.cpp:133-141) fused:
    # out-dim-sharded matmuls (wq/wk/wv/w1/w3/wcls) are embarrassingly
    # parallel, in-dim-sharded ones (wo/w2) psum their partials — the
    # SYNC_NODE_SLICES + OP_MERGE_ADD exchange (nn-network.cpp:521-554) as one
    # ICI psum per call.

    def supports_sharded_pallas(self) -> bool:
        """tp/dp meshes only: sp needs ring attention (its own shard_map) and
        pp replaces the layer scan with the stage schedule."""
        return self.mesh.shape["sp"] == 1 and self.mesh.shape["pp"] == 1

    def pallas_mms(self, batch: int):
        """(mm, mm_in) shard_map-wrapped Pallas matmuls for the model forward.

        mm:    x @ w with w sharded on the OUTPUT dim -> out sharded on 'tp'
        mm_in: x @ w with w sharded on the INPUT dim  -> psum('tp'), replicated
        Both take (x[B,T,K], w: QTensor 2-D or [L,...] stacked, layer) like
        ops.matmul.matmul; untileable shards fall back to the XLA path inside
        the manual region (ops.matmul dispatch runs per-shard).
        """
        from functools import partial

        from dllama_tpu.ops.matmul import matmul

        mesh = self.mesh
        b_ax = self._batch_axis(batch)
        pmm = partial(matmul, backend="pallas")

        def make(shard_dim: int, reduce_over_tp: bool):
            """shard_dim: weight dim carrying 'tp' (-1 out-shard, -2 in-shard)."""

            def call(x, w, layer=None):
                is_q = isinstance(w, QTensor)
                nd = w.packed.ndim if is_q else jnp.ndim(w)
                axes = [None] * nd
                axes[shard_dim] = "tp"
                wspec = P(*axes)
                wspec_t = QTensor(wspec, wspec) if is_q else wspec
                x_spec = P(b_ax, None, "tp" if reduce_over_tp else None)
                out_spec = P(b_ax, None, None if reduce_over_tp else "tp")

                def body(x, w, li=None):
                    out = pmm(x, w, li)
                    return jax.lax.psum(out, "tp") if reduce_over_tp else out

                if nd == 3:  # layer-stacked weight: the layer index rides along
                    fn = jax.shard_map(
                        body, mesh=mesh, in_specs=(x_spec, wspec_t, P()),
                        out_specs=out_spec, check_vma=False,
                    )
                    return fn(x, w, jnp.asarray(layer, jnp.int32))
                fn = jax.shard_map(
                    lambda x, w: body(x, w), mesh=mesh,
                    in_specs=(x_spec, wspec_t), out_specs=out_spec, check_vma=False,
                )
                return fn(x, w)

            return call

        return make(-1, False), make(-2, True)

    def pallas_attn(self, batch: int, interpret: bool = False):
        """Head-sharded flash attention: each chip runs the online-softmax
        kernel on its local kv-head shard (attention is per-head local — the
        reference's sliceMultiHeadAtt, nn-core.cpp:215-238)."""
        from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

        mesh = self.mesh
        b_ax = self._batch_axis(batch)

        def attn(q, k_cache, v_cache, pos_base):
            b = q.shape[0]
            pos_vec = jnp.broadcast_to(
                jnp.atleast_1d(jnp.asarray(pos_base, jnp.int32)), (b,)
            )
            fn = jax.shard_map(
                lambda q, k, v, p: flash_gqa_attention(q, k, v, p, interpret=interpret),
                mesh=mesh,
                in_specs=(
                    P(b_ax, None, "tp", None),   # q [B, T, Hq, hd]
                    P(b_ax, "tp", None, None),   # k cache [B, Hkv, S, hd]
                    P(b_ax, "tp", None, None),
                    P(b_ax),                     # per-row positions
                ),
                out_specs=P(b_ax, None, "tp", None),
                check_vma=False,
            )
            return fn(q, k_cache, v_cache, pos_vec)

        return attn

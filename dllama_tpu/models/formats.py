"""`.m` model-file reader/writer.

File schema (llm.cpp:26-98, converter/writer.py:109-143): i32 magic
0xA00ABCD, i32 headerSize, (key,value) i32 pairs, then raw tensors in fixed
order (llm.cpp:453-468):

  embedding f32 [vocab, dim]
  per layer: q [dim,dim] k [kv_dim,dim] v [kv_dim,dim] wo [dim,dim]
             (q [heads*head_size, dim] and wo [dim, heads*head_size] where
             the header gives a head size of its own)
             w1 [hidden,dim] w2 [dim,hidden] w3 [hidden,dim]   (weight_type)
             rms_norm_0 f32 [dim], rms_norm_1 f32 [dim]
             (a state-space layer of an ArchType.HYBRID_SSM file holds, in
             place of q/k/v/wo: in_proj [2*inner + 2*state + heads, dim],
             conv_w f32 [inner + 2*state, taps], conv_b, dt_bias [heads],
             a_log [heads], d [heads], ssm_norm [inner], out_proj [dim, inner];
             a delta-rule layer (LayerKind.KDA): kda_proj [3*inner + 2*rank +
             heads, dim] (q | k | v | decay's inner | gate's inner | beta),
             kda_conv_w f32 [3*inner, taps], kda_fb / kda_gb [inner, rank],
             kda_dt_bias f32 [inner], kda_a_log f32 [heads], kda_norm f32
             [head], kda_o [dim, inner]; a latent attention layer
             (LayerKind.MLA): mla_q [heads*(nope+pe), dim], mla_kva [rank+pe,
             dim], mla_kv_norm f32 [rank], mla_kvb [heads*(nope+v), rank],
             mla_o [dim, heads*v]; a power-retention layer
             (LayerKind.RETENTION): q/k/v/wo and the head norms as an
             attention layer's, then ret_gate f32 [kv_heads, dim] and
             ret_gate_bias f32 [kv_heads]. An expert layer holds moe_gate f32
             [experts, dim] (every column, whatever the file holds of the
             experts), moe_bias f32 [experts] under a sigmoid router, the
             held experts' stacks at the expert width, and shared_w1/w2/w3
             where the header counts shared experts; a layer the header
             marks dense holds w1/w2/w3 at HIDDEN_DIM)
  final_rms_norm f32 [dim]
  wcls [vocab, dim]                                            (weight_type)

Matmul tensors are stored [out, in] row-major; we load them as transposed
``x @ W`` operands ([in, out]) — Q40 becomes a :class:`QTensor`, f32/f16
become dense arrays. Where the reference root slices each tensor and ships
shards to workers over TCP (nn-network.cpp:775-869), here every tensor is
`jax.device_put` with its mesh sharding — XLA/ICI replaces the wire protocol.
"""

from __future__ import annotations

import struct
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.models.config import MODEL_MAGIC, LayerKind, LlamaConfig
from dllama_tpu.ops.quant import (
    FloatType,
    Q_BLOCK,
    Q8Tensor,
    QTensor,
    dequantize_q40_np,
    dequantize_q80_np,
    quantize_q40_np,
    quantize_q80_np,
)


class ModelFileError(ValueError):
    """A .m file that cannot be what it claims: wrong magic, truncated
    header, or fewer/more tensor bytes than the header's config implies.
    Every message names the file and the expected-vs-actual numbers — the
    raw struct/mmap errors these replace said neither."""


def read_header(path: str, max_seq_len: int | None = None) -> tuple[LlamaConfig, int]:
    """Returns (config, header_size_bytes). Mirrors loadLlmHeader (llm.cpp:26-98)."""
    from dllama_tpu.utils import faults

    faults.fire("loader.read")
    with open(path, "rb") as f:
        head = f.read(8)
        if len(head) < 8:
            raise ModelFileError(
                f"{path}: not a .m model file — {len(head)} bytes on disk, "
                f"need at least the 8-byte magic+size header")
        magic, header_size = struct.unpack("<ii", head)
        if magic in (0xABCD00, 0xABCD01):
            raise ModelFileError(f"{path}: old model format is not supported")
        if magic != MODEL_MAGIC:
            raise ModelFileError(
                f"{path}: unsupported magic number {magic:#x} "
                f"(expected {MODEL_MAGIC:#x}) — not a .m model file, or corrupt")
        if header_size < 8 or (header_size - 8) % 8 != 0:
            raise ModelFileError(
                f"{path}: corrupt header: headerSize={header_size} "
                f"(want 8 + a multiple of 8 key/value bytes)")
        body = f.read(header_size - 8)
        if len(body) < header_size - 8:
            raise ModelFileError(
                f"{path}: truncated header: declares {header_size} bytes but "
                f"only {8 + len(body)} are on disk")
        n_kv = (header_size - 8) // 4 // 2
        kv = []
        for i in range(n_kv):
            key, value = struct.unpack_from("<ii", body, i * 8)
            kv.append((key, value))
    config = LlamaConfig.from_header_kv(kv)
    return config.clamp_seq_len(max_seq_len), header_size


def write_header(f, config: LlamaConfig) -> int:
    kv = config.to_header_kv()
    header = struct.pack("<ii", MODEL_MAGIC, 8 + len(kv) * 8)
    body = b"".join(struct.pack("<ii", k, v) for k, v in kv)
    f.write(header + body)
    return len(header) + len(body)


def tensor_plan(config: LlamaConfig) -> list[tuple[str, tuple[int, int] | tuple[int], FloatType]]:
    """(name, file_shape, float_type) in on-disk order (llm.cpp:453-468)."""
    wt = config.weight_type
    plan: list = [("embedding", (config.vocab_size, config.dim), FloatType.F32)]
    kinds = config.layer_kinds or (LayerKind.ATTENTION,) * config.n_layers
    for layer, kind in enumerate(kinds):
        if kind == LayerKind.SSM:
            # the state-space mixer (ops/ssm.py): in_proj's output rows are
            # z | x | B | C | dt side by side, at the published width
            f32 = FloatType.F32
            plan += [
                (f"layers.{layer}.in_proj", (config.ssm_in_proj, config.dim), wt),
                (f"layers.{layer}.conv_w", (config.ssm_conv_dim, config.ssm_conv), f32),
                (f"layers.{layer}.conv_b", (config.ssm_conv_dim,), f32),
                (f"layers.{layer}.dt_bias", (config.ssm_heads,), f32),
                (f"layers.{layer}.a_log", (config.ssm_heads,), f32),
                (f"layers.{layer}.d", (config.ssm_heads,), f32),
                (f"layers.{layer}.ssm_norm", (config.ssm_inner,), f32),
                (f"layers.{layer}.out_proj", (config.dim, config.ssm_inner), wt),
            ]
        elif kind == LayerKind.KDA:
            f32, p = FloatType.F32, f"layers.{layer}."
            inner, rank = config.kda_inner, config.kda_rank
            plan += [
                (p + "kda_proj", (config.kda_proj, config.dim), wt),
                (p + "kda_conv_w", (3 * inner, config.kda_conv), f32),
                (p + "kda_fb", (inner, rank), wt),
                (p + "kda_gb", (inner, rank), wt),
                (p + "kda_dt_bias", (inner,), f32),
                (p + "kda_a_log", (config.kda_heads,), f32),
                (p + "kda_norm", (config.kda_head_dim,), f32),
                (p + "kda_o", (config.dim, inner), wt),
            ]
        elif kind == LayerKind.MLA:
            p, h, r = f"layers.{layer}.", config.n_heads, config.kv_lora_rank
            qd, qr = h * (config.qk_nope_dim + config.qk_pe_dim), config.q_lora_rank
            # q through its low rank and norm where the header gives one
            plan += ([(p + "mla_qa", (qr, config.dim), wt),
                      (p + "mla_q_norm", (qr,), FloatType.F32),
                      (p + "mla_qb", (qd, qr), wt)] if qr
                     else [(p + "mla_q", (qd, config.dim), wt)])
            plan += [
                (p + "mla_kva", (r + config.qk_pe_dim, config.dim), wt),
                (p + "mla_kv_norm", (r,), FloatType.F32),
                (p + "mla_kvb", (h * (config.qk_nope_dim + config.v_head_dim), r), wt),
                (p + "mla_o", (config.dim, h * config.v_head_dim), wt),
            ]
        else:
            # softmax attention, or power retention over the same tensors. A
            # layer's attention tensors go by its kind, windowed or global,
            # where the header gives the windowed layers heads of their own:
            # `*_win` names, so the loader stacks the two kinds apart
            win = bool(config.layer_window(layer))
            p, sfx = f"layers.{layer}.", config.attn_suffix(win)
            heads, ad = config.heads_of(win), config.attn_dim_of(win)
            plan += [
                (p + "wq" + sfx, (ad, config.dim), wt),
                (p + "wk" + sfx, (config.kv_dim, config.dim), wt),
                (p + "wv" + sfx, (config.kv_dim, config.dim), wt),
                (p + "wo" + sfx, (config.dim, ad), wt),
            ]
            if config.qk_norm:
                plan += [(p + "q_norm" + sfx, (config.head_size,), FloatType.F32),
                         (p + "k_norm" + sfx, (config.head_size,), FloatType.F32)]
            if config.attn_gate:
                plan.append((p + "attn_gate" + sfx, (heads, config.dim),
                             FloatType.F32))
            if kind == LayerKind.RETENTION:
                # the decay's gate, one a kv head: projection and bias
                plan += [(p + "ret_gate", (config.n_kv_heads, config.dim),
                          FloatType.F32),
                         (p + "ret_gate_bias", (config.n_kv_heads,),
                          FloatType.F32)]
        if config.n_experts and not (config.layer_ffn and config.layer_ffn[layer]):
            # MoE extension: the reference header carries N_EXPERTS
            # (llm.hpp:17-18) and its HF converter emits expert tensors
            # (convert-hf.py:66-73), but its runtime never reads them; this
            # is the layout our converter writes — router gate then
            # expert-stacked w1/w2/w3 blobs.
            held, width = config.n_held_experts, config.expert_width
            plan.append((f"layers.{layer}.moe_gate", (config.n_experts, config.dim),
                         FloatType.F32))
            if config.router_sigmoid:
                plan.append((f"layers.{layer}.moe_bias", (config.n_experts,),
                             FloatType.F32))
            plan += [
                (f"layers.{layer}.moe_w1", (held, width, config.dim), wt),
                (f"layers.{layer}.moe_w2", (held, config.dim, width), wt),
                (f"layers.{layer}.moe_w3", (held, width, config.dim), wt),
            ]
            if config.n_shared_experts:
                sw = config.n_shared_experts * width
                plan += [
                    (f"layers.{layer}.shared_w1", (sw, config.dim), wt),
                    (f"layers.{layer}.shared_w2", (config.dim, sw), wt),
                    (f"layers.{layer}.shared_w3", (sw, config.dim), wt),
                ]
        else:
            plan += [
                (f"layers.{layer}.w1", (config.hidden_dim, config.dim), wt),
                (f"layers.{layer}.w2", (config.dim, config.hidden_dim), wt),
                (f"layers.{layer}.w3", (config.hidden_dim, config.dim), wt),
            ]
        plan += [
            (f"layers.{layer}.rms_att", (config.dim,), FloatType.F32),
            (f"layers.{layer}.rms_ffn", (config.dim,), FloatType.F32),
        ]
    plan += [
        ("final_norm", (config.dim,), FloatType.F32),
        ("wcls", (config.vocab_size, config.dim), wt),
    ]
    return plan


def write_tensor(f, x: np.ndarray, float_type: FloatType) -> int:
    """Serialize a tensor in the reference byte format (writer.py:29-107).

    Q40 quantization runs in C++ when the native library is available
    (bit-identical to quantize_q40_np; tests/test_native.py pins it)."""
    flat = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    if float_type == FloatType.F32:
        buf = flat.tobytes()
    elif float_type == FloatType.F16:
        buf = flat.astype(np.float16).tobytes()
    elif float_type == FloatType.Q40:
        from dllama_tpu.utils import native

        if native.available():
            packed, scales = native.quantize_q40(flat)
        else:
            packed, scales = quantize_q40_np(flat)
        rec = np.zeros((packed.shape[0], 2 + Q_BLOCK // 2), dtype=np.uint8)
        rec[:, :2] = scales.reshape(-1, 1).view(np.uint8)
        rec[:, 2:] = packed
        buf = rec.tobytes()
    elif float_type == FloatType.Q80:
        # reference record: f16 delta + 32 int8 codes (writer.py:55-74)
        codes, scales = quantize_q80_np(flat)
        rec = np.zeros((codes.shape[0], 2 + Q_BLOCK), dtype=np.uint8)
        rec[:, :2] = scales.reshape(-1, 1).view(np.uint8)
        rec[:, 2:] = codes.view(np.uint8)
        buf = rec.tobytes()
    else:
        raise ValueError(f"unsupported weight type: {float_type}")
    f.write(buf)
    return len(buf)


def save_model(path: str, config: LlamaConfig, tensors: dict[str, np.ndarray]) -> None:
    """Write a complete `.m` file; `tensors` maps plan names to file-shape arrays."""
    with open(path, "wb") as f:
        write_header(f, config)
        for name, shape, ft in tensor_plan(config):
            x = tensors[name]
            assert tuple(x.shape) == tuple(shape), (name, x.shape, shape)
            write_tensor(f, x, ft)


def iter_tensors(path: str, config: LlamaConfig, header_size: int) -> Iterator[tuple[str, tuple, FloatType, np.ndarray]]:
    """Yield (name, file_shape, float_type, raw_bytes_view) per plan entry.

    Uses a read-only memmap — the analog of the reference's mmap weight load
    (mmap.hpp:35-70); no copy happens until a tensor is decoded.
    """
    data = np.memmap(path, dtype=np.uint8, mode="r")
    plan = tensor_plan(config)
    # validate the WHOLE plan against the on-disk size up front: a truncated
    # download/copy fails here with the offending tensor named, not as an
    # opaque out-of-bounds view (or worse, a SIGBUS on the mmap) deep inside
    # the layer-stacking loop
    total = header_size + sum(ft.nbytes(int(np.prod(shape))) for _, shape, ft in plan)
    if data.shape[0] < total:
        offset = header_size
        for name, shape, ft in plan:
            nbytes = ft.nbytes(int(np.prod(shape)))
            if offset + nbytes > data.shape[0]:
                raise ModelFileError(
                    f"{path}: truncated .m file: {data.shape[0]:,} bytes on "
                    f"disk, {total:,} expected for this header's config; "
                    f"first incomplete tensor is {name!r} "
                    f"(needs bytes [{offset:,}, {offset + nbytes:,}))")
            offset += nbytes
    if data.shape[0] > total:
        raise ModelFileError(
            f"{path}: .m file has {data.shape[0]:,} bytes but this header's "
            f"config accounts for {total:,} — corrupt header or mismatched "
            f"weight type")
    offset = header_size
    for name, shape, ft in plan:
        nbytes = ft.nbytes(int(np.prod(shape)))
        yield name, shape, ft, data[offset : offset + nbytes]
        offset += nbytes


def decode_dense(raw: np.ndarray, shape: tuple, ft: FloatType) -> np.ndarray:
    """Decode raw bytes to an f32 array of `shape`."""
    if ft == FloatType.F32:
        return raw.view(np.float32).reshape(shape)
    if ft == FloatType.F16:
        return raw.view(np.float16).reshape(shape).astype(np.float32)
    if ft == FloatType.Q40:
        n = int(np.prod(shape))
        rec = raw.reshape(n // Q_BLOCK, 2 + Q_BLOCK // 2)
        scales = rec[:, :2].copy().view(np.float16).reshape(-1)
        packed = rec[:, 2:]
        return dequantize_q40_np(packed, scales).reshape(shape)
    if ft == FloatType.Q80:
        n = int(np.prod(shape))
        rec = raw.reshape(n // Q_BLOCK, 2 + Q_BLOCK)
        scales = rec[:, :2].copy().view(np.float16).reshape(-1)
        codes = rec[:, 2:].view(np.int8)  # same-itemsize view: no copy
        return dequantize_q80_np(codes, scales).reshape(shape)
    raise ValueError(f"unsupported weight type: {ft}")


class LazyQ40:
    """A Q40 matmul weight still living as bytes on the `.m` memmap.

    Shards decode ON DEMAND in the device layout (packed u8[k/2, n], scales
    f16[k/32, n]): `jax.make_array_from_callback` asks only for the shards a
    host's devices own, so a model bigger than one host's RAM never fully
    decodes anywhere — the byte-range analog of the reference's
    slice-then-ship (nn-network.cpp:775-869), with the mmap as the wire.
    Both device dims map to contiguous/strided ranges of the file's
    [n_out, k_in/32, 18-byte-block] record array, so a shard read touches
    only its own byte ranges.
    """

    def __init__(self, raw: np.ndarray, n_out: int, k_in: int):
        self.rec = raw.reshape(n_out, k_in // Q_BLOCK, 2 + Q_BLOCK // 2)
        self.n_out = n_out
        self.k_in = k_in

    @property
    def packed_shape(self) -> tuple[int, ...]:
        return (self.k_in // 2, self.n_out)

    @property
    def scales_shape(self) -> tuple[int, ...]:
        return (self.k_in // Q_BLOCK, self.n_out)

    @staticmethod
    def _aligned(sl: slice, total: int, unit: int) -> tuple[int, int]:
        start = sl.start or 0
        stop = total if sl.stop is None else sl.stop
        assert start % unit == 0 and stop % unit == 0, (sl, unit)
        return start // unit, stop // unit

    def packed_shard(self, k2_sl: slice, n_sl: slice) -> np.ndarray:
        """Device-layout packed rows [k2_sl, n_sl] (k2 units of half-blocks)."""
        b0, b1 = self._aligned(k2_sl, self.k_in // 2, Q_BLOCK // 2)
        n0, n1 = self._aligned(n_sl, self.n_out, 1)
        from dllama_tpu.utils import native

        if native.has_q40_shard():
            return native.q40_shard(self.rec, n0, n1, b0, b1, True, False)[0]
        sub = np.ascontiguousarray(self.rec[n0:n1, b0:b1, 2:])  # [n, nb, 16]
        return np.transpose(sub, (1, 2, 0)).reshape(-1, sub.shape[0])

    def scales_shard(self, kb_sl: slice, n_sl: slice) -> np.ndarray:
        b0, b1 = self._aligned(kb_sl, self.k_in // Q_BLOCK, 1)
        n0, n1 = self._aligned(n_sl, self.n_out, 1)
        from dllama_tpu.utils import native

        if native.has_q40_shard():
            # the C++ twin emits f32; narrowing back to f16 is exact
            return native.q40_shard(self.rec, n0, n1, b0, b1, False, True)[1].astype(np.float16)
        sub = np.ascontiguousarray(self.rec[n0:n1, b0:b1, :2])  # [n, nb, 2]
        return np.ascontiguousarray(sub.view(np.float16)[..., 0].T)  # f16 [nb, n]

    def eager(self) -> QTensor:
        full = slice(None)
        return QTensor(self.packed_shard(full, full), self.scales_shard(full, full))


class LazyQ40Stack:
    """Layer-stacked LazyQ40s: one more leading axis on every shard request
    (sharded over 'pp' on pipeline meshes — a host decodes only its stage)."""

    def __init__(self, members: list[LazyQ40]):
        self.members = members

    @property
    def packed_shape(self) -> tuple[int, ...]:
        return (len(self.members), *self.members[0].packed_shape)

    @property
    def scales_shape(self) -> tuple[int, ...]:
        return (len(self.members), *self.members[0].scales_shape)

    def packed_shard(self, l_sl: slice, k2_sl: slice, n_sl: slice) -> np.ndarray:
        return np.stack([m.packed_shard(k2_sl, n_sl) for m in self.members[l_sl]])

    def scales_shard(self, l_sl: slice, kb_sl: slice, n_sl: slice) -> np.ndarray:
        return np.stack([m.scales_shard(kb_sl, n_sl) for m in self.members[l_sl]])

    def eager(self) -> QTensor:
        parts = [m.eager() for m in self.members]
        return QTensor(
            np.stack([p.packed for p in parts]), np.stack([p.scales for p in parts])
        )


def _load_matmul(raw: np.ndarray, shape: tuple[int, int], ft: FloatType, dtype, dequantize: bool,
                 lazy: bool = False, q80_packed: bool = False):
    """File [out, in] -> host-resident x@W operand: QTensor/Q8Tensor or dense [in, out]."""
    n_out, k_in = shape
    if ft == FloatType.Q40 and not dequantize:
        if lazy:
            return LazyQ40(raw, n_out, k_in)
        rec = raw.reshape(n_out * k_in // Q_BLOCK, 2 + Q_BLOCK // 2)
        scales = rec[:, :2].copy().view(np.float16)
        packed = rec[:, 2:]
        return QTensor.from_file_layout(packed, scales, n_out, k_in, device=False)
    if ft == FloatType.Q80 and q80_packed and not dequantize:
        # keep Q80 weights packed on device (int8 + f16 scales, 1.0625
        # bytes/weight vs 2 for the dense fallback); unsharded engines only —
        # the mesh slicers know QTensor/dense layouts, not Q8Tensor
        rec = raw.reshape(n_out * k_in // Q_BLOCK, 2 + Q_BLOCK)
        scales = rec[:, :2].copy().view(np.float16)
        codes = rec[:, 2:].view(np.int8)
        return Q8Tensor.from_file_layout(codes, scales, n_out, k_in, device=False)
    return decode_dense(raw, shape, ft).T.astype(dtype, order="C")


#: a layer's small float32 tensors, loaded as they lie: the norms and the
#: state-space mixer's conv, step and skip parameters
_F32_LEAVES = ("rms_att", "rms_ffn", "conv_w", "conv_b", "dt_bias", "a_log",
               "d", "ssm_norm", "kda_conv_w", "kda_dt_bias", "kda_a_log",
               "kda_norm", "mla_kv_norm", "mla_q_norm", "moe_bias", "q_norm", "k_norm",
               "q_norm_win", "k_norm_win", "ret_gate_bias")
#: matmul weights whose published output width is not whole lane tiles: zero
#: columns are added on the way to the device, the file keeps the width
#: (kda_proj to whole 512s so that the wide tiles divide it)
_PADDED_COLUMNS = {"in_proj": 128, "mla_kva": 128, "kda_proj": 512}


def _pad_columns(w, multiple: int):
    """Zero output columns up to a whole `multiple` (host-side leaf)."""
    n = w.shape[-1]
    pad = (-n) % multiple
    if not pad:
        return w
    if isinstance(w, QTensor):
        # nibble 8 is weight 0; the scale is 0 as well
        return QTensor(np.pad(w.packed, ((0, 0), (0, pad)), constant_values=0x88),
                       np.pad(w.scales, ((0, 0), (0, pad))))
    if isinstance(w, Q8Tensor):
        return Q8Tensor(np.pad(w.codes, ((0, 0), (0, pad))),
                        np.pad(w.scales, ((0, 0), (0, pad))))
    return np.pad(w, ((0, 0), (0, pad)))


def _load_expert_matmul(raw: np.ndarray, shape: tuple[int, int, int], ft: FloatType, dtype, dequantize: bool):
    """File [E, out, in] blob -> expert-stacked host x@W operand [E, in, out]."""
    e, n_out, k_in = shape
    per = ft.nbytes(n_out * k_in)
    leaves = [
        _load_matmul(raw[i * per : (i + 1) * per], (n_out, k_in), ft, dtype, dequantize)
        for i in range(e)
    ]
    return jax.tree.map(lambda *xs: np.stack(xs, axis=0), *leaves)


def load_params(
    path: str,
    config: LlamaConfig,
    header_size: int,
    dtype=jnp.bfloat16,
    dequantize: bool = False,
    put: Callable[[str, object], object] | None = None,
    q80_packed: bool = False,
):
    """Load the full parameter pytree.

    Per-layer tensors are stacked on a leading layer axis so the model can
    `lax.scan` over layers (one XLA while-loop instead of n_layers copies of
    the graph — the TPU analog of the reference's per-layer segment list).

    `put(name, leaf)` receives each finished leaf as a *host* (numpy-backed or
    :class:`LazyQ40`/:class:`LazyQ40Stack`) pytree and decides device
    placement — the shard-direct path passes LlamaShardings.param_put so every
    tensor goes straight from the memmap to its device shards, and Q40 matmul
    weights stay LAZY: only the byte ranges of a host's own shards are ever
    decoded (no whole-model staging on any host or device; the reference's
    analog is slice-then-ship, nn-network.cpp:775-869). Default: eager
    host->default-device.
    """
    def default_put(name, x):
        if isinstance(x, (LazyQ40, LazyQ40Stack)):
            x = x.eager()
        return jax.tree.map(jnp.asarray, x)

    put = put or default_put
    layer_acc: dict[str, list] = {}
    params: dict = {}
    for name, shape, ft, raw in iter_tensors(path, config, header_size):
        if name in ("embedding",):
            params["embedding"] = put(name, decode_dense(raw, shape, ft).astype(dtype))
        elif name in ("final_norm",):
            params["final_norm"] = put(name, decode_dense(raw, shape, ft))
        elif name == "wcls":
            params["wcls"] = put(name, _load_matmul(raw, shape, ft, dtype, dequantize,
                                                    lazy=True, q80_packed=q80_packed))
        else:
            _, _, short = name.split(".")
            if short in _F32_LEAVES:
                leaf = decode_dense(raw, shape, ft)
            elif short in _PADDED_COLUMNS:
                # 2*inner + 2*state + heads columns are not whole 128-lane
                # tiles (8,512 at the published widths): zero columns are
                # added after the last block HERE, on the way to the device;
                # the file keeps the published width
                leaf = _pad_columns(_load_matmul(raw, shape, ft, dtype, dequantize,
                                                 q80_packed=q80_packed),
                                    _PADDED_COLUMNS[short])
            elif short == "mla_kvb":
                # [heads * (nope + v), rank] -> f32 [heads, nope + v, rank]:
                # the absorbed form multiplies q by the key half's TRANSPOSE
                # (a contraction over the file's output rows, which the Q40
                # block layout does not serve), so the 8 M weights a layer
                # are held as the float32 values the Q40 blocks decode to
                leaf = decode_dense(raw, shape, ft).reshape(
                    config.n_heads, -1, shape[1]).astype(np.float32, order="C")
            elif short in ("moe_gate", "attn_gate", "attn_gate_win", "ret_gate"):
                # router stays f32; file [E, dim] -> h@gate operand [dim, E]
                # (the attention output's gate and the retention decay's
                # likewise: [heads, dim] -> [dim, heads])
                leaf = decode_dense(raw, shape, ft).T.astype(np.float32, order="C")
            elif short.startswith("moe_"):
                leaf = _load_expert_matmul(raw, shape, ft, dtype, dequantize)
            else:
                leaf = _load_matmul(raw, shape, ft, dtype, dequantize, lazy=True,
                                    q80_packed=q80_packed)
            layer_acc.setdefault(short, []).append(leaf)

    layers = {}
    for short, leaves in layer_acc.items():
        if isinstance(leaves[0], LazyQ40):
            stacked = LazyQ40Stack(leaves)
        else:
            stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *leaves)
        layers[short] = put(f"layers.{short}", stacked)
    params["layers"] = layers
    return params

"""The benchmark's own writer (and reader) of the files the program serves.

A `.m` model file of random Q40 weights and a byte-level `.t` tokenizer, both
made from the seed with numpy alone. Nothing here imports the program: the
file formats are the interface (magic, key/value header, tensors in the order
below), so the plain reference (`reference/`) reads exactly the bytes this
module wrote and a refactor of the program's loader cannot change what the
benchmark compares against. Copied from `chip_smoke.write_model` /
`write_tokenizer` (PR 21) and made faster: random bytes are drawn as 64-bit
words, not one call per byte.

`.m` layout: i32 magic 0x0A00ABCD, i32 header bytes, (key, value) i32 pairs,
then tensors: embedding f32 [vocab, dim]; per layer wq [dim, dim], wk
[kv_dim, dim], wv [kv_dim, dim], wo [dim, dim], w1 [hidden, dim], w2
[dim, hidden], w3 [hidden, dim] (Q40, stored [out, in] row-major), rms_att
f32 [dim], rms_ffn f32 [dim]; final_norm f32 [dim]; wcls Q40 [vocab, dim].
A Q40 block is 32 weights along `in`: an f16 scale, then 16 bytes whose low
nibbles are weights 0..15 and high nibbles weights 16..31; weight =
scale * (nibble - 8).
"""

from __future__ import annotations

import os
import struct

import numpy as np

MODEL_MAGIC = 0x0A00ABCD
TOKENIZER_MAGIC = 0x567124
Q_BLOCK = 32
Q40_BLOCK_BYTES = 2 + Q_BLOCK // 2
#: variance of (nibble - 8) once 0 is folded onto 8: (2 * 140 + 0) / 16
NIBBLE_VARIANCE = 17.5
#: what a configuration's `weights` block may set, and the default:
#: `attention_sharpness`, the standard deviation of the attention scores (wq
#: is that much larger than unit gain)
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "norm_epsilon_x1e12": 100}
ARCH_LLAMA, ACT_SILU, FT_Q40 = 0xABCD00, 1, 2


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (HF key names in, the
    `.m` header's names out)."""
    dim = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    return {"dim": dim, "hidden_dim": int(config["intermediate_size"]),
            "n_layers": int(config["num_hidden_layers"]), "n_heads": heads,
            "n_kv_heads": kv_heads, "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "rope_theta": float(config["rope_theta"]),
            "norm_epsilon": float(config["rms_norm_eps"]),
            "head_size": dim // heads, "kv_dim": dim * kv_heads // heads}


def tensor_plan(s: dict) -> list[tuple[str, tuple, str]]:
    """(name, file shape, 'f32' | 'q40') in on-disk order."""
    plan = [("embedding", (s["vocab_size"], s["dim"]), "f32")]
    for li in range(s["n_layers"]):
        plan += [(f"layers.{li}.wq", (s["dim"], s["dim"]), "q40"),
                 (f"layers.{li}.wk", (s["kv_dim"], s["dim"]), "q40"),
                 (f"layers.{li}.wv", (s["kv_dim"], s["dim"]), "q40"),
                 (f"layers.{li}.wo", (s["dim"], s["dim"]), "q40"),
                 (f"layers.{li}.w1", (s["hidden_dim"], s["dim"]), "q40"),
                 (f"layers.{li}.w2", (s["dim"], s["hidden_dim"]), "q40"),
                 (f"layers.{li}.w3", (s["hidden_dim"], s["dim"]), "q40"),
                 (f"layers.{li}.rms_att", (s["dim"],), "f32"),
                 (f"layers.{li}.rms_ffn", (s["dim"],), "f32")]
    plan += [("final_norm", (s["dim"],), "f32"),
             ("wcls", (s["vocab_size"], s["dim"]), "q40")]
    return plan


def tensor_nbytes(shape: tuple, kind: str) -> int:
    n = int(np.prod(shape))
    return 4 * n if kind == "f32" else n // Q_BLOCK * Q40_BLOCK_BYTES


def _header(s: dict) -> bytes:
    kv = [(_K["version"], 0), (_K["arch"], ARCH_LLAMA), (_K["dim"], s["dim"]),
          (_K["hidden_dim"], s["hidden_dim"]), (_K["n_layers"], s["n_layers"]),
          (_K["n_heads"], s["n_heads"]), (_K["n_kv_heads"], s["n_kv_heads"]),
          (_K["n_experts"], 0), (_K["n_active_experts"], 0),
          (_K["vocab_size"], s["vocab_size"]), (_K["seq_len"], s["seq_len"]),
          (_K["hidden_act"], ACT_SILU),
          (_K["rope_theta"], int(s["rope_theta"])),
          (_K["weight_type"], FT_Q40)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"],
                   int(round(s["norm_epsilon"] * 1e12))))
    body = b"".join(struct.pack("<ii", k, v) for k, v in kv)
    return struct.pack("<ii", MODEL_MAGIC, 8 + len(body)) + body


def _tensor_bytes(seq: np.random.SeedSequence, name: str, shape: tuple,
                  kind: str, weights: dict) -> np.ndarray:
    """One tensor's bytes as they lie on disk, from its own seed sequence."""
    rng = np.random.Generator(np.random.PCG64(seq))
    n = int(np.prod(shape))
    if kind == "f32":
        if name == "embedding":
            x = (rng.random(n, np.float32) - 0.5) * np.float32(0.04)
        else:  # rms norm gains
            x = np.ones(n, np.float32)
        return x.view(np.uint8)
    blocks = n // Q_BLOCK
    # random bytes for the whole record, then the two scale bytes of each
    # block written over them: one pass, no 16-of-18 strided copy
    words = rng.bit_generator.random_raw((blocks * Q40_BLOCK_BYTES + 7) // 8)
    # a nibble of 0 (weight -8) becomes 8 (weight 0): the weights are then
    # symmetric about zero. Uniform nibbles have mean -0.5, which gives every
    # matrix a rank-one part that amplifies the activations' mean about
    # sevenfold a matmul at these widths: the residual stream becomes one
    # constant direction and neither context nor precision shows in the
    # logits any more (PERF.md, PR 25's first drill).
    low = np.uint64(0x1111111111111111)
    nonzero = words | (words >> np.uint64(1))
    nonzero |= nonzero >> np.uint64(2)
    words |= (~nonzero & low) << np.uint64(3)
    rec = words.view(np.uint16)[:blocks * (Q40_BLOCK_BYTES // 2)].reshape(
        blocks, Q40_BLOCK_BYTES // 2)
    # scales sized so a matmul keeps its input's magnitude (the weights'
    # variance is 17.5 scale^2); wq times the configuration's sharpness
    scale = np.float32(1.0 / np.sqrt(NIBBLE_VARIANCE * shape[-1]))  # [out, in]
    if name.endswith(".wq"):
        scale *= np.float32(weights["attention_sharpness"])
    scales = scale * (np.float32(0.5) + rng.random(blocks, np.float32))
    rec[:, 0] = scales.astype(np.float16).view(np.uint16)
    return rec.reshape(-1).view(np.uint8)


def write_model(path: str, config: dict, seed: int, workers: int = 4) -> int:
    """A whole `.m` of random weights, the same bytes for the same seed.
    Q40 tensors are written as they lie on disk (f16 scale + 16 packed
    bytes a block), so no float copy of the model is made. Weights are
    symmetric about zero, scales sized so that every matmul keeps its
    input's magnitude (see _tensor_bytes); norm gains are 1; the embedding
    is uniform in +-0.02. Each tensor has its own stream spawned from the seed, so a
    few threads make them side by side and the file does not depend on how
    many. Returns the bytes written."""
    from concurrent.futures import ThreadPoolExecutor

    s = shapes_of(config)
    plan = tensor_plan(s)
    weights = {**WEIGHT_DEFAULTS, **config.get("weights", {})}
    seqs = np.random.SeedSequence(int(seed)).spawn(len(plan))
    tmp = path + ".tmp"
    with open(tmp, "wb") as f, ThreadPoolExecutor(workers) as pool:
        f.write(_header(s))
        pending = []
        for i, (seq, (name, shape, kind)) in enumerate(zip(seqs, plan)):
            pending.append(pool.submit(_tensor_bytes, seq, name, shape, kind, weights))
            if len(pending) > 2 * workers:  # bounded: a layer or so in flight
                f.write(pending.pop(0).result().data)
        for fut in pending:
            f.write(fut.result().data)
        size = f.tell()
    os.replace(tmp, path)
    return size


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    with open(path, "rb") as f:
        magic, size = struct.unpack("<ii", f.read(8))
        if magic != MODEL_MAGIC:
            raise ValueError(f"{path}: not a .m file (magic {magic:#x})")
        body = f.read(size - 8)
    names = {v: k for k, v in _K.items()}
    kv = {names[k]: v for k, v in
          (struct.unpack_from("<ii", body, i) for i in range(0, len(body), 8))}
    if kv["arch"] != ARCH_LLAMA or kv["weight_type"] != FT_Q40:
        raise ValueError(f"{path}: the reference reads Q40 LLAMA files only")
    s = {k: kv[k] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                            "n_kv_heads", "vocab_size", "seq_len")}
    s["rope_theta"] = float(kv["rope_theta"])
    s["norm_epsilon"] = kv.get("norm_epsilon_x1e12", 10_000_000) / 1e12
    s["head_size"] = s["dim"] // s["n_heads"]
    s["kv_dim"] = s["dim"] * s["n_kv_heads"] // s["n_heads"]
    return s, size


def tensor_views(path: str) -> tuple[dict, dict]:
    """(sizes, {name: (uint8 memmap view, file shape, kind)}) of a `.m`."""
    s, offset = read_header(path)
    data = np.memmap(path, dtype=np.uint8, mode="r")
    views = {}
    for name, shape, kind in tensor_plan(s):
        n = tensor_nbytes(shape, kind)
        views[name] = (data[offset:offset + n], shape, kind)
        offset += n
    if offset != data.shape[0]:
        raise ValueError(f"{path}: {data.shape[0]} bytes on disk, the header "
                         f"accounts for {offset}")
    return s, views


def write_tokenizer(path: str, vocab_size: int) -> None:
    """A byte-level `.t` (v1 format) that covers the whole vocabulary, so
    the server decodes whatever id random weights emit: ids 0-255 are the
    bytes, then filler pieces that no merge can reach, then 256 specials
    from <|begin_of_text|> (vocab_size - 256) on. Text of plain ASCII
    letters therefore encodes to one token a byte. The file names NO
    end-of-sequence id: a random model emits any id about once in a
    vocabulary's worth of tokens, so with two EOS ids every second Mistral
    window had a request stop early and re-admit, which moved `out_tok_s`
    by 3.5% from seed to seed (PERF.md, PR 25). A request's `max_tokens` is
    what stands for the model being done."""
    bos = vocab_size - 256
    special = {0: b"<|begin_of_text|>", 1: b"<|end_of_text|>",
               6: b"<|start_header_id|>", 7: b"<|end_header_id|>",
               9: b"<|eot_id|>"}
    vocab = [bytes([i]) for i in range(256)]
    vocab += [b" t%d" % i for i in range(256, bos)]
    vocab += [special.get(i, b"<|reserved_special_token_%d|>" % i)
              for i in range(256)]
    scores = [0.0] * 256 + [-1e6] * (bos - 256) + [0.0] * 256
    template = b"{{ '<|start_header_id|>' }}"
    kv = [(0, 1), (1, len(vocab)), (2, max(len(p) for p in vocab)), (3, bos),
          (7, len(template))]
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(struct.pack("<ii", TOKENIZER_MAGIC, 8 + len(kv) * 8))
        for k, v in kv:
            f.write(struct.pack("<ii", k, v))
        f.write(template)
        f.write(b"".join(struct.pack("<fi", sc, len(p)) + p
                         for sc, p in zip(scores, vocab)))
    os.replace(tmp, path)


def write_files(config: dict, seed: int, out_dir: str) -> tuple[str, str, int]:
    """The `.m` and the `.t` of one (configuration, seed) under `out_dir`
    -> (model path, tokenizer path, model bytes)."""
    os.makedirs(out_dir, exist_ok=True)
    model = os.path.join(out_dir, f"{config['name']}-seed{seed}.m")
    tok = os.path.join(out_dir, f"{config['name']}.t")
    size = write_model(model, config, seed)
    write_tokenizer(tok, int(config["vocab_size"]))
    return model, tok, size

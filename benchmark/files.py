"""The benchmark's own writer (and reader) of the files the program serves:
the formats and the discipline, and no model.

A `.m` model file of random weights and a byte-level `.t` tokenizer, both
made from the seed with numpy alone. Nothing here imports the program: the
file formats are the interface, so the plain reference (`reference/`) reads
exactly the bytes this module wrote and a refactor of the program's loader
cannot change what the benchmark compares against.

WHICH tensors a model file holds, in what order, under which header keys
and with which gains is a LAYOUT: a module the configuration file names
under `layout` (`benchmark.layouts.llama`), found by that name like the
reference. A layout gives

    shapes_of(config) -> dict        the file-level sizes of a configuration
    header(shapes) -> [(key, value)] the header's i32 pairs, its arch id
                                     among them
    tensor_plan(shapes, weights={}) -> [Entry]   in on-disk order; `weights`
                                     is the configuration's block of gains
    read_header(path) -> (shapes, header bytes)
    tensor_views(path) -> (shapes, {name: (uint8 view, file shape, kind)})

and this module writes whatever plan it is handed: it names no tensor, no
header key and no architecture.

`.m` format: i32 magic 0x0A00ABCD, i32 header bytes, (key, value) i32
pairs, then the plan's tensors back to back. An `f32` tensor is its values;
a `q40` tensor is stored [out, in] row-major in blocks of 32 weights along
`in`: an f16 scale, then 16 bytes whose low nibbles are weights 0..15 and
high nibbles weights 16..31; weight = scale * (nibble - 8).
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import struct
from typing import Callable

import numpy as np

MODEL_MAGIC = 0x0A00ABCD
TOKENIZER_MAGIC = 0x567124
Q_BLOCK = 32
Q40_BLOCK_BYTES = 2 + Q_BLOCK // 2
#: variance of (nibble - 8) once 0 is folded onto 8: (2 * 140 + 0) / 16
NIBBLE_VARIANCE = 17.5


@dataclasses.dataclass(frozen=True)
class Entry:
    """One tensor of a plan: all the writer needs to know of it.

    `kind` "q40": random blocks whose scales are sized `1 / sqrt(17.5 * in)`
    (a matmul keeps its input's magnitude) times `gain`: one number, or
    ((rows, gain), ...) by block of output rows, top to bottom, for a
    matrix whose outputs are several projections side by side. With
    `derived_from` it is instead the Q40 quantisation of that f32 entry (a
    tied head) and draws nothing.
    `kind` "f32": `init(rng, n) -> float32[n]`, given the entry's own
    generator (`ones` and `uniform` below; a layout may bring its own)."""

    name: str
    shape: tuple
    kind: str
    gain: float | tuple = 1.0
    init: Callable | None = None
    derived_from: str | None = None

    @property
    def nbytes(self) -> int:
        n = int(np.prod(self.shape))
        return 4 * n if self.kind == "f32" else n // Q_BLOCK * Q40_BLOCK_BYTES


def ones(rng: np.random.Generator, n: int) -> np.ndarray:
    return np.ones(n, np.float32)


def uniform(half_width: float) -> Callable:
    """An initialiser: uniform in +-half_width."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        return (rng.random(n, np.float32) - 0.5) * np.float32(2 * half_width)
    return init


def layout_of(config: dict):
    """The layout module a configuration names. No default architecture: a
    configuration without the key fails by name, as a missing file does."""
    if "layout" not in config:
        raise SystemExit(f"benchmark: the configuration {config.get('name')!r} "
                         "names no `layout` (a module such as "
                         "benchmark.layouts.llama)")
    try:
        return importlib.import_module(config["layout"])
    except ModuleNotFoundError as e:
        raise SystemExit(f"benchmark: the configuration {config.get('name')!r} "
                         f"names the layout {config['layout']!r}: {e}") from e


# ------------------------------------------------------------ the header


def pack_header(pairs: list) -> bytes:
    body = b"".join(struct.pack("<ii", k, v) for k, v in pairs)
    return struct.pack("<ii", MODEL_MAGIC, 8 + len(body)) + body


def parse_header(path: str) -> tuple[dict, int]:
    """({key: value} as the file has them, header bytes) of a `.m` file."""
    with open(path, "rb") as f:
        magic, size = struct.unpack("<ii", f.read(8))
        if magic != MODEL_MAGIC:
            raise ValueError(f"{path}: not a .m file (magic {magic:#x})")
        body = f.read(size - 8)
    return dict(struct.unpack_from("<ii", body, i)
                for i in range(0, len(body), 8)), size


def views(path: str, offset: int, plan: list) -> dict:
    """{name: (uint8 memmap view, file shape, kind)} of the plan's tensors
    from `offset` on; the file must end where the plan does."""
    data = np.memmap(path, dtype=np.uint8, mode="r")
    out = {}
    for e in plan:
        out[e.name] = (data[offset:offset + e.nbytes], e.shape, e.kind)
        offset += e.nbytes
    if offset != data.shape[0]:
        raise ValueError(f"{path}: {data.shape[0]} bytes on disk, the header "
                         f"accounts for {offset}")
    return out


# ----------------------------------------------------------- the tensors


def _block_gains(entry: Entry) -> np.ndarray | np.float32:
    """The entry's gain per Q40 block: a scalar, or one value a block where
    the gain goes by block of output rows."""
    if not isinstance(entry.gain, tuple):
        return np.float32(entry.gain)
    rows = [r for r, _ in entry.gain]
    if sum(rows) != entry.shape[0]:
        raise ValueError(f"{entry.name}: gains cover {sum(rows)} rows of "
                         f"{entry.shape[0]}")
    per_row = entry.shape[-1] // Q_BLOCK
    return np.repeat(np.asarray([g for _, g in entry.gain], np.float32),
                     [r * per_row for r in rows])


def _random_q40(rng: np.random.Generator, entry: Entry) -> np.ndarray:
    blocks = int(np.prod(entry.shape)) // Q_BLOCK
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
    # variance is 17.5 scale^2), times the entry's gain
    scale = np.float32(1.0 / np.sqrt(NIBBLE_VARIANCE * entry.shape[-1]))  # [out, in]
    scale = scale * _block_gains(entry)
    scales = scale * (np.float32(0.5) + rng.random(blocks, np.float32))
    rec[:, 0] = scales.astype(np.float16).view(np.uint16)
    return rec.reshape(-1).view(np.uint8)


def quantise_q40(x: np.ndarray) -> np.ndarray:
    """float32 values (a multiple of 32 of them) -> their Q40 record bytes:
    per block the scale is the entry of largest magnitude over -8, so that
    entry lands on nibble 0 exactly, and every weight rounds to the nearest
    nibble (the reference converter's rule)."""
    blocks = np.asarray(x, np.float32).reshape(-1, Q_BLOCK)
    peak = blocks[np.arange(len(blocks)), np.abs(blocks).argmax(axis=1)]
    scale = (peak / np.float32(-8.0)).astype(np.float16)
    d = scale.astype(np.float32)
    inv = np.divide(np.float32(1.0), d, out=np.zeros_like(d), where=d != 0)
    q = np.clip(blocks * inv[:, None] + np.float32(8.5), 0, 15).astype(np.uint8)
    rec = np.empty((len(blocks), Q40_BLOCK_BYTES), np.uint8)
    rec[:, :2] = scale.view(np.uint8).reshape(-1, 2)
    rec[:, 2:] = q[:, :Q_BLOCK // 2] | (q[:, Q_BLOCK // 2:] << 4)
    return rec.reshape(-1)


def _tensor_bytes(i: int, plan: list, seqs: list) -> np.ndarray:
    """The i-th tensor's bytes as they lie on disk, from its own seed
    sequence (a derived tensor: from its source's)."""
    entry = plan[i]
    if entry.derived_from is not None:
        src = next(j for j, e in enumerate(plan) if e.name == entry.derived_from)
        if plan[src].kind != "f32" or plan[src].shape != entry.shape:
            raise ValueError(f"{entry.name}: derived from {entry.derived_from}, "
                             "which is not an f32 tensor of its shape")
        return quantise_q40(_tensor_bytes(src, plan, seqs).view(np.float32))
    rng = np.random.Generator(np.random.PCG64(seqs[i]))
    if entry.kind == "f32":
        return entry.init(rng, int(np.prod(entry.shape))).view(np.uint8)
    return _random_q40(rng, entry)


def write_model(path: str, config: dict, seed: int, workers: int = 4) -> int:
    """A whole `.m` of random weights, the same bytes for the same seed.
    Q40 tensors are written as they lie on disk (f16 scale + 16 packed
    bytes a block), so no float copy of the model is made. Each plan entry
    has its own stream spawned from the seed, in plan order, so a few
    threads make them side by side and the file does not depend on how
    many. Returns the bytes written."""
    from concurrent.futures import ThreadPoolExecutor

    layout = layout_of(config)
    s = layout.shapes_of(config)
    plan = layout.tensor_plan(s, config.get("weights", {}))
    seqs = np.random.SeedSequence(int(seed)).spawn(len(plan))
    tmp = path + ".tmp"
    with open(tmp, "wb") as f, ThreadPoolExecutor(workers) as pool:
        f.write(pack_header(layout.header(s)))
        pending = []
        for i in range(len(plan)):
            pending.append(pool.submit(_tensor_bytes, i, plan, seqs))
            if len(pending) > 2 * workers:  # bounded: a layer or so in flight
                f.write(pending.pop(0).result().data)
        for fut in pending:
            f.write(fut.result().data)
        size = f.tell()
    os.replace(tmp, path)
    return size


# --------------------------------------------------------- the tokenizer


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

"""Convert a HuggingFace safetensors checkpoint to the `.m` format.

Analog of the reference converter (converter/convert-hf.py): reads
``config.json`` + ``*.safetensors`` shards lazily (one tensor materialized at
a time), applies the Q/K rope permutation, and streams tensors to disk in the
fixed `.m` plan order (llm.cpp:453-468).

Checkpoints: `LlamaForCausalLM` / Mistral / Mixtral (the `.m` LLAMA arch)
and `GraniteMoeHybridForCausalLM` (Mamba-2 mixers `mamba.in_proj` / `conv1d`
/ `dt_bias` / `A_log` / `D` / `norm` / `out_proj` beside attention layers,
`shared_mlp.input_linear` / `output_linear`, the four scalars and
`layer_types` into the header: `converter_core.HYBRID_NAME_MAP`), and the
DeepSeek-V3 family's config keys (latent attention with `q_lora_rank`, a
`yarn` block with its mscales, `n_group` / `topk_group`,
`first_k_dense_replace`; `converter_core.LATENT_NAME_MAP`; the header mapping
is unit-tested on a hand-written config, no checkpoint of the family has
been converted).

Usage:
    python -m dllama_tpu.tools.convert_hf <model_dir> <weight_type> [--output out.m] [--max-seq-len N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from dllama_tpu.ops.quant import parse_float_type
from dllama_tpu.tools.converter_core import (
    default_output_name,
    hf_config_to_llama,
    hf_tensor_for,
    write_model,
)


class SafetensorsDir:
    """Lazy tensor accessor over a sharded safetensors checkpoint dir."""

    def __init__(self, model_dir: str):
        from safetensors import safe_open

        self._safe_open = safe_open
        self.model_dir = model_dir
        index_path = os.path.join(model_dir, "model.safetensors.index.json")
        if os.path.exists(index_path):
            with open(index_path) as f:
                self.weight_map = json.load(f)["weight_map"]
        else:
            single = [fn for fn in sorted(os.listdir(model_dir)) if fn.endswith(".safetensors")]
            if not single:
                raise FileNotFoundError(f"no .safetensors files in {model_dir}")
            self.weight_map = {}
            for fn in single:
                with safe_open(os.path.join(model_dir, fn), framework="np") as f:
                    for key in f.keys():
                        self.weight_map[key] = fn
        self._open_file = None
        self._open_name = None

    def __contains__(self, name: str) -> bool:
        return name in self.weight_map

    def get(self, name: str):
        """Returns the tensor as float32 numpy. KeyError if absent."""
        import numpy as np

        fn = self.weight_map[name]  # KeyError propagates (tied-embedding probe)
        if self._open_name != fn:
            if self._open_file is not None:
                self._open_file.__exit__(None, None, None)
            self._open_file = self._safe_open(
                os.path.join(self.model_dir, fn), framework="np"
            ).__enter__()
            self._open_name = fn
        x = self._open_file.get_tensor(name)
        if x.dtype == np.uint16:  # bfloat16 stored raw; upcast via int shift
            x = (x.astype(np.uint32) << 16).view(np.float32)
        return x.astype(np.float32)

    def close(self) -> None:
        if self._open_file is not None:
            self._open_file.__exit__(None, None, None)
            self._open_file = None


def convert_hf(model_dir: str, weight_type_name: str, output: str | None = None,
               max_seq_len: int | None = None) -> str:
    weight_type = parse_float_type(weight_type_name)
    with open(os.path.join(model_dir, "config.json")) as f:
        hf_config = json.load(f)
    cfg = hf_config_to_llama(hf_config, weight_type)
    if max_seq_len:
        cfg = cfg.clamp_seq_len(max_seq_len)
    if output is None:
        output = default_output_name(model_dir, weight_type_name)

    src = SafetensorsDir(model_dir)
    write_model(cfg, output, lambda name: hf_tensor_for(name, cfg, src.get))
    src.close()
    return output


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("model_dir", help="HF checkpoint dir (config.json + *.safetensors)")
    p.add_argument("weight_type", choices=["q40", "q80", "f16", "f32"], help="on-disk matmul weight type")
    p.add_argument("--output", default=None, help="output .m path")
    p.add_argument("--max-seq-len", type=int, default=None, help="clamp seq_len in the header")
    args = p.parse_args(argv)
    convert_hf(args.model_dir, args.weight_type, args.output, args.max_seq_len)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Are the accepted configurations' step programs the parent's, to the
instruction? StableHLO of the decode, hybrid and prefill programs of each
tiny configuration under benchmark/tests (and tests/test_state_space.TINY),
on the jnp route and on the kernels in interpret mode: sha256 of each text.

    python experiments/hlo_identity.py <tree root> <out.json> [names]

Run it on a `git archive` of the parent and on the working tree and compare
the two files (CHANGES.md, PR 38 and PR 42: 18 of 18 and 24 of 24 equal).
A header key that is absent keeps its meaning exactly when these agree.
"""

import hashlib
import importlib
import json
import os
import sys


def main(root: str, out_path: str, only: str = "") -> int:
    root = os.path.abspath(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.chdir(root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax.numpy as jnp

    from benchmark import files
    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models import formats

    here = os.path.join(root, "benchmark", "tests")
    configs = {f[5:-5]: json.load(open(os.path.join(here, f)))
               for f in sorted(os.listdir(here))
               if f.startswith("tiny-") and f.endswith(".json")}
    configs["granite"] = importlib.import_module("test_state_space").TINY
    if only:
        configs = {k: v for k, v in configs.items() if k in only.split(",")}
    out = {}
    for name, conf in configs.items():
        path = os.path.join(root, f"hlo-{name}.m")
        files.write_model(path, conf, 5)
        cfg, header = formats.read_header(path, 256)
        params = formats.load_params(path, cfg, header, dtype=jnp.bfloat16)
        os.remove(path)
        for kernels, attn in (("xla", "jnp"), ("pallas", "flash")):
            be = BatchEngine(cfg, params, n_slots=4, max_seq_len=256,
                             kv_layout="paged", page_size=8, kv_pages=64,
                             kernels=kernels, attn_impl=attn, max_prefill_chunk=16)
            i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
            vecs = (i32(4), jnp.zeros((4,), bool), jnp.zeros((4, 2), jnp.uint32),
                    jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.float32))
            rope = be.rope_cache
            texts = {
                "decode": be._decode.lower(params, be.cache, i32(4, 1), *vecs, 4,
                                           rope, i32(4)),
                "hybrid": be._hybrid.lower(params, be.cache, i32(1, 16), i32(),
                                           i32(), i32(4, 1), *vecs, 4, rope, i32(4)),
                "prefill": be._prefill_slot.lower(params, be.cache, i32(1, 16),
                                                  i32(), i32(), rope)}
            for prog, lowered in texts.items():
                text = lowered.as_text()
                out[f"{name}/{be.kernel_route}/{prog}"] = [
                    hashlib.sha256(text.encode()).hexdigest()[:16], len(text)]
            del be
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))

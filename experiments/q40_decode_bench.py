"""Decode-shaped Q40 matmul on the chip, by kernel style and batch: the
block-dot kernel against the dequantise-then-dot tier at the shapes of a
serving decode step (x[m, 1, k] against layer-stacked weights).

    chiprun -- python experiments/q40_decode_bench.py [--m 16,48,64]

Prints one JSON line a (shape, m, style): microseconds a call (a 36-call
loop cycling the layer, as the layer scan does), GB/s of packed weights,
and the largest relative difference between the two styles' results.
PERF.md section 6 (PR 29) has the readings: at m = 48 the dequantise-then-dot
tier had split the batch into 3 m tiles of 16 and streamed the weights three
times (`_deq_call` now takes a batch up to 512 rows as one tile).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.ops.pallas import q40_matmul as q
from dllama_tpu.ops.quant import QTensor

SHAPES = {"in_proj": (2048, 8576), "out_proj": (4096, 2048),
          "w1": (2048, 8192), "w2": (8192, 2048)}
LAYERS, CALLS = 12, 36


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", default="16,48,64")
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    for name, (k, n) in SHAPES.items():
        w = QTensor(
            jnp.asarray(rng.integers(0, 256, (LAYERS, k // 2, n), dtype=np.uint8)),
            jnp.asarray((rng.random((LAYERS, k // 32, n), np.float32) * 0.02
                         + 1e-3).astype(np.float16)))
        for m in (int(v) for v in args.m.split(",")):
            x = jnp.asarray(rng.standard_normal((m, 1, k)), jnp.bfloat16)
            outs = {}
            for style in ("blockdot", "deq"):
                q.STYLE = style

                @jax.jit
                def loop(x, w):
                    def body(i, acc):
                        return acc + q.q40_matmul(x, w, i % LAYERS).astype(jnp.float32)
                    return jax.lax.fori_loop(0, CALLS, body,
                                             jnp.zeros((m, 1, n), jnp.float32))

                once = jax.jit(lambda x, w: q.q40_matmul(x, w, 3))
                outs[style] = np.asarray(once(x, w), np.float32)
                loop(x, w).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(5):
                    loop(x, w).block_until_ready()
                us = (time.perf_counter() - t0) / 5 / CALLS * 1e6
                print(json.dumps({"shape": name, "k": k, "n": n, "m": m,
                                  "style": style, "us_per_call": round(us, 1),
                                  "weights_gb_s": round(k * n * 0.5625 / us / 1e3, 1)}),
                      flush=True)
            q.STYLE = "auto"
            d = np.abs(outs["blockdot"] - outs["deq"]).max() / np.abs(outs["deq"]).max()
            print(json.dumps({"shape": name, "m": m, "styles_rel_diff": float(d)}),
                  flush=True)


if __name__ == "__main__":
    main()

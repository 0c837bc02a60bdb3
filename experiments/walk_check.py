"""Does a layout's greedy stream walk the vocabulary at the PUBLISHED widths?
No chip: the benchmark's float32 reference on the CPU, one forward pass.

`benchmark/layouts/axk1.py` lays out which token a greedy stream emits next
(`successor`: the token's sign vector is the signs of the head's row of its
successor, and `weights.head_token_gain` makes that logit stand out). How far
it stands out depends on the residual stream's size at the real widths, which
a tiny configuration does not show (the written dims of `a.x-k1` have ten
times the RMS of its token dims: at gain 1 the successor's logit is 2
standard deviations out, at 6 it is 10; PERF.md section 6, PR 44). This
writes the configuration's model file from a seed (6.4 GB for `a.x-k1`, 30-60
s), runs the reference over one sequence of a prompt's letters followed by a
walk, and prints how often the successor is the argmax and how many standard
deviations of its row its logit stands out, by position. About 5 minutes and
5 GB of memory at 4,000 tokens; run it after any change to the layout's
gains, before a cell run.

    python experiments/walk_check.py [CONFIG.json] [--seed N] [--tokens N]
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="?",
                    default=os.path.join(ROOT, "benchmark/configs/a.x-k1.json"))
    ap.add_argument("--seed", type=int, default=4480000019)
    ap.add_argument("--tokens", type=int, default=4000)
    args = ap.parse_args()
    from benchmark import files

    with open(args.config) as f:
        config = json.load(f)
    layout = files.layout_of(config)
    ref = importlib.import_module(config["reference"])
    vocab = int(config["vocab_size"])
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "walk.m")
        t = time.time()
        size = files.write_model(path, config, args.seed, workers=2)
        print(f"wrote {size / 1e9:.2f} GB in {time.time() - t:.0f} s", flush=True)
        letters = np.random.default_rng(1).integers(97, 123, args.tokens // 2)
        walk = [int(layout.successor(letters[-1:], vocab)[0])]
        while len(walk) < args.tokens // 2:
            walk.append(int(layout.successor(np.array(walk[-1:]), vocab)[0]))
        seq = np.concatenate([[vocab - 256], letters, walk]).astype(np.int32)
        t = time.time()
        rows = ref.logits_at(path, [seq], [list(range(len(seq)))])[0]
        print(f"reference over {len(seq)} tokens in {time.time() - t:.0f} s")
    nxt = layout.successor(seq, vocab)
    mean, std = rows.mean(1), rows.std(1)
    z = (rows[np.arange(len(seq)), nxt] - mean) / std
    runner_up = (np.sort(rows, axis=1)[:, -2] - mean) / std
    print(f"successor is the argmax at {100 * (rows.argmax(1) == nxt).mean():.2f}% "
          f"of positions; its logit stands {z.mean():.2f} sigma out on average "
          f"(least {z.min():.2f}); the runner-up {runner_up.mean():.2f} "
          f"(most {runner_up.max():.2f})")
    print("by position:", [round(float(v), 1) for v in z[::max(1, len(seq) // 24)]])
    return 0


if __name__ == "__main__":
    sys.exit(main())

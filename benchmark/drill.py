"""The seed drill: the check alone (no server, no window) over many seeds.

    python benchmark/drill.py --config deepseek-llm-7b --seeds 101,202,... \
        [--control --cache-dtype --control f8] [--out chiprun_out/drill.jsonl]

For each seed: write the `.m`/`.t` from the seed, run `serve_child.py
--check-only` (its own process, the only holder of the chip), print the
check's numbers, delete the files. The next seed's file is written while the
current check runs. `--control WORD` (repeatable) appends words to the
program's command line: `--cache-dtype f8` is the lower-precision control
whose numbers the tolerances must refuse. The limits in each configuration's
file were set from this tool's output on the chip (PERF.md has the table).
This process never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import files  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True,
                    help="a name under benchmark/configs, or a path")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    path = (args.config if os.path.exists(args.config)
            else os.path.join(HERE, "configs", args.config + ".json"))
    with open(path) as f:
        config = json.load(f)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out_dir = os.path.join(HERE, "out")
    rows, made = [], {}

    def prepare(seed):
        t0 = time.monotonic()
        model, tok, _ = files.write_files(config, seed, out_dir)
        made[seed] = (model, tok, time.monotonic() - t0)

    th = threading.Thread(target=prepare, args=(seeds[0],))
    th.start()
    for i, seed in enumerate(seeds):
        th.join()
        model, tok, write_s = made.pop(seed)
        if i + 1 < len(seeds):
            th = threading.Thread(target=prepare, args=(seeds[i + 1],))
            th.start()
        cmd = [sys.executable, os.path.join(HERE, "serve_child.py"),
               "--config", path, "--model", model, "--tokenizer", tok,
               "--seed", str(seed), "--check-only"]
        for word in args.control:
            cmd.append(f"--flag={word}")
        t0 = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
        row = {"seed": seed, "control": args.control, "rc": proc.returncode,
               "write_s": round(write_s, 1),
               "child_s": round(time.monotonic() - t0, 1)}
        for line in proc.stdout.splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("phase") == "check":
                row.update(rec)
            elif rec.get("phase") == "load":
                row["load_s"] = rec["seconds"]
        os.remove(model)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if "rel_l2_max" not in row:
            sys.stderr.write("---- the child's stderr ----\n"
                             + proc.stderr[-6000:] + "\n")
            if i == 0:  # nothing ran: do not spend the other seeds on it
                th.join()
                break
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    good = [r for r in rows if "rel_l2_max" in r]
    if good:
        print(json.dumps({
            "config": config["name"], "control": args.control,
            "seeds": len(good),
            "rel_l2_mean": [min(r["rel_l2_mean"] for r in good),
                            max(r["rel_l2_mean"] for r in good)],
            "deficit_sigma_mean": [min(r["deficit_sigma_mean"] for r in good),
                                   max(r["deficit_sigma_mean"] for r in good)]}),
            flush=True)
    return 0 if len(good) == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())

"""The one process that holds the chip: load once, decide `correct`, serve.

    python benchmark/serve_child.py --config F --model M --tokenizer T \
        --seed N --port P [--check-only] [--flag=--cache-dtype --flag=f8]

It calls the program's own command line, `dllama_tpu.cli.main.main(["serve",
...])`, with the flags of the configuration's `serve` block, so argument
parsing, loading, engine construction, warm-up and the HTTP server are the
program's. The only seam is `engine.loader.load_model`: wrapped so that, once
the CLI has loaded the weights and before it builds the server, the check of
`benchmark/check.py` runs on the loaded model. The weights are therefore
loaded once per run, and the check sees the cache type and context the CLI's
flags produced (`--cache-dtype f8` here is the lower-precision control).

Every stdout line is one JSON object with a `phase`. `--check-only` exits
after the check (the seed drill). The parent (`run.py`) never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402


class CheckDone(Exception):
    """Raised through the CLI after the check in --check-only mode."""


def serve_argv(config: dict, model: str, tokenizer: str, port: int,
               extra: list) -> list:
    s = config["serve"]
    return ["serve", "--model", model, "--tokenizer", tokenizer,
            "--port", str(port), "--slots", str(s["slots"]),
            "--max-seq-len", str(config["max_position_embeddings"]),
            "--page-size", str(s["page_size"]),
            "--kv-pages", str(s["kv_pages"]), *s["flags"], *extra]


def engine_kwargs(config: dict) -> dict:
    """The BatchEngine the CLI's flags build (serve/api.make_server), as
    the check builds it: same slots, pool and page size, so the check runs
    the serving programs themselves and adds none to the compile cache."""
    s = config["serve"]
    return {"n_slots": int(s["slots"]), "kv_layout": "paged",
            "page_size": int(s["page_size"]), "kv_pages": int(s["kv_pages"]),
            "radix_cache": "auto"}


def require_device(expect: dict) -> dict:
    """Exit non-zero unless JAX runs on the platform and chips the cell
    asks for. No fallback: a CPU number is never a device number."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if (device["platform"] != expect["platform"]
            or device["count"] < int(expect["chips"])):
        check.say({"phase": "device", "error": "wrong device", **device,
                   "expected": expect})
        raise SystemExit(3)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--tokenizer", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--check-only", action="store_true")
    ap.add_argument("--flag", action="append", default=[],
                    help="one more word for the program's command line")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    t0 = time.monotonic()
    device = require_device({"platform": config["expect"]["platform"],
                             "chips": args.chips})
    check.say({"phase": "device", **device})

    from dllama_tpu.engine import loader

    real_load = loader.load_model

    def load_then_check(*a, **kw):
        t1 = time.monotonic()
        loaded = real_load(*a, **kw)
        import jax

        jax.block_until_ready(loaded.engine.params)
        check.say({"phase": "load", "seconds": round(time.monotonic() - t1, 2)})
        cfg = dict(config, engine=engine_kwargs(config))
        result = check.run(loaded, cfg, args.model, args.seed)
        result["phase"] = "check"
        result["expected_route"] = config["expect"]["route"]
        if not args.flag and result["route"] != config["expect"]["route"]:
            result["correct"] = False  # the cell's route did not run
        check.say(result)
        if args.check_only:
            raise CheckDone()
        return loaded

    loader.load_model = load_then_check
    from dllama_tpu.cli.main import main as cli_main

    try:
        rc = cli_main(serve_argv(config, args.model, args.tokenizer,
                                 args.port, args.flag))
    except CheckDone:
        rc = 0
    check.say({"phase": "child_exit", "rc": rc,
               "seconds": round(time.monotonic() - t0, 2)})
    return rc


if __name__ == "__main__":
    sys.exit(main())

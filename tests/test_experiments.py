"""CPU smoke of the experiment scripts (experiments/*.py).

Each test executes a script's ACTUAL main path end-to-end on CPU — tiny
shapes, interpret-mode Pallas — so an import error, bad flag, or shape typo
is caught here and never spends chip time. The numbers produced are
meaningless; only completion + parity markers are asserted. (What the v5e
compiler itself accepts, no chip attached, is tests/test_chip_compile.py.)
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, extra_env=None, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # scripts run single-device, like on the chip
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable] + argv,
        cwd=REPO, capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_kbench_suite_smoke():
    p = _run(["experiments/kbench.py", "suite", "--smoke"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout
    assert "FAILED" not in p.stdout, p.stdout
    # the production dispatch and both forced tiers were timed at m = 8
    for row in ("A auto=", "BD blockdot=", "DQ deq="):
        assert row in p.stdout, p.stdout


def test_kbench_sampler_smoke():
    """The sampler alone (`kbench.py sampler`, what priced ISSUE 52's bodies
    on the chip) at a tiny size: PR 51's one straight-line body and today's
    three, each on the batch that asks for it."""
    p = _run(["experiments/kbench.py", "sampler", "--smoke"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout and "FAILED" not in p.stdout, p.stdout
    for row in ("the argmax alone", "PR 51, a greedy batch", "today, a greedy batch",
                "today, a temperature batch", "PR 51, a nucleus batch",
                "today, a nucleus batch", "greedy with ONE nucleus row"):
        assert p.stdout.count(row) == 1, (row, p.stdout)


def test_kbench_paged_smoke():
    """The paged-decode loop of the benchmark's cells (the pricing of every
    paged-kernel PR) at a tiny size: fused and read-only, pools threaded."""
    p = _run(["experiments/kbench.py", "paged", "--smoke", "--sub"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout and "FAILED" not in p.stdout, p.stdout
    # two global calls and a windowed one (PR 53), each fused and read-only
    # beside the bytes needed, touched and MOVED, then the fused call again
    # at each size of an end page's copies (`--sub`: 16 rows, the page)
    assert p.stdout.count("fused scatter:") == p.stdout.count("read-only:") == 3
    assert p.stdout.count("; moved ") == 12 and p.stdout.count(", window 80;") == 4
    assert p.stdout.count("fused scatter, end copies of") == 6
    # the latent cells' sweep (PR 48): decode and slice, each as it is, as
    # the parent's page-a-pass body and over the pages-a-pass sweep
    assert p.stdout.count("the parent's body (a page a pass") == 2
    assert p.stdout.count("pass fill") == 3 and "against float64" in p.stdout


def test_kbench_q40_smoke():
    """The block-dot kernel's own bench (the pricing of every change to it)
    at a tiny size: parity, the kernel with each part taken out, the tile
    sweep and the inner-loop sweep, stacked and unstacked."""
    p = _run(["experiments/kbench.py", "q40", "--smoke"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout and "FAILED" not in p.stdout, p.stdout
    for row in ("as it is", "no dot", "no unpack", "no scale", "DMA only",
                "sweep tk=", "sweep lanes="):
        assert p.stdout.count(row) >= 2, (row, p.stdout)  # both weights
    assert p.stdout.count("parity") == 2


def test_kbench_deq_smoke():
    """The dequantising tier's own bench (the pricing of every change to it)
    at a tiny size: parity, the kernel with each part taken out, the
    block-dot kernel at the same rows, the (tk, tn, rows) sweep, and PR 36's
    byte-wise body whole, in parts and over its tiles."""
    p = _run(["experiments/kbench.py", "deq", "--smoke", "--parent"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout and "FAILED" not in p.stdout, p.stdout
    for row in ("as it is", "no scale rows", "no scale multiply", "no dequantise",
                "DMA only", "sweep tk=", "PR 36's body, as it was",
                "PR 36's body, dot + DMA"):
        assert p.stdout.count(row) >= 2, (row, p.stdout)  # both weights
    assert p.stdout.count("the block-dot kernel") == 1  # 48 rows; 24 are no whole tiles
    assert p.stdout.count("parity") == 2


def test_kbench_expert_smoke():
    """The grouped expert kernel's own bench (the pricing of every change to
    its tile walk) at a tiny size: parity against each tile's expert, the
    kernel with each part taken out, every tile live against the fill's dead
    ones, the (tn, lanes) sweep, a slice at two tile heights, one chip's
    share."""
    p = _run(["experiments/kbench.py", "expert", "--smoke"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout and "FAILED" not in p.stdout, p.stdout
    for row in ("as it is", "no dot", "no unpack", "DMA only", "layout matrices not computed",
                "rows laid out at the call's first tile only", "every tile live",
                "one tile live", "sweep tn="):
        assert p.stdout.count(row) >= 3, (row, p.stdout)  # every shape
    assert p.stdout.count("parity") == 3 + 2 * 2  # decode fills, a slice at two heights
    assert "slice tm=32 as it is" in p.stdout and "tiny share" in p.stdout


def test_kbench_moe_layer_smoke():
    """One whole grouped expert layer-step (`moe_ffn`, router logits in, [N,
    D] out) at a tiny size: today's route against PR 42's (kept in the
    experiment as the yardstick) to the bit with equal counters, and the
    three kernel calls alone; a decode step, a slice and one chip's share."""
    p = _run(["experiments/kbench.py", "moe_layer", "--smoke"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "KBENCH DONE" in p.stdout and "FAILED" not in p.stdout, p.stdout
    assert p.stdout.count("largest difference 0.00e+00 of tanh(out), counters equal") == 3
    for row in ("the three kernel calls alone", "PR 42's route:", "as it is:"):
        assert p.stdout.count(row) >= 3, (row, p.stdout)
    assert "tiny slice" in p.stdout and "tiny share" in p.stdout


def test_warm_compile_smoke():
    """A cell's warm worklist compiled one program after another over zero
    weights (the on-chip check for what the described chip's compiler lets
    through), at a tiny size: every program is named and accepted."""
    p = _run(["experiments/warm_compile.py", "--smoke", "prefill_chunk.m4.,decode.n4.,hybrid.p4."])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "WARM DONE 3/3" in p.stdout and "REJECT" not in p.stdout, p.stdout
    for name in ("prefill_chunk m4 ACCEPT", "decode n4 ACCEPT", "hybrid p4.n4 ACCEPT"):
        assert name in p.stdout, (name, p.stdout)
    assert "decode n1" not in p.stdout and "hybrid_pen" not in p.stdout


def test_walk_check_smoke():
    """The reference's look at a layout's greedy stream (which token stands
    out after each), at the tiny size: the file is written, the reference
    runs and the successor's logit is reported by position."""
    p = _run(["experiments/walk_check.py", "benchmark/tests/tiny-axk1.json",
              "--tokens", "40"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "successor is the argmax at" in p.stdout and "by position:" in p.stdout


def test_collectives_table_smoke():
    p = _run(["experiments/collectives_table.py", "--smoke"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "COLLECTIVES DONE" in p.stdout, p.stdout
    assert "FAILED" not in p.stdout, p.stdout


def test_kbench_no_flash():
    """--no-flash skips the flash section but still delivers the q40 rows."""
    p = _run(["experiments/kbench.py", "suite", "--smoke", "--no-flash"])
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-2000:]}"
    assert "flash bench SKIPPED" in p.stdout
    assert "flash decode" not in p.stdout
    assert "A auto=" in p.stdout and "KBENCH DONE" in p.stdout

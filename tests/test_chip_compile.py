"""The serving path's programs, compiled for TPU v5e with no chip attached.

libtpu is installed here, and a described topology lets XLA:TPU + Mosaic
compile for a chip that is not there. Interpret mode — what every other
kernel test in this suite runs — cannot show a slice that is not aligned to
the tiling, a VMEM overflow or a program that does not fit HBM; this can
(it is how PR 21 found that Mosaic refuses the paged kernel at
Llama-3.2-1B's head size 64 until the pool rows are whole 128-lane vectors).

This is the one offline compile check: every case of
`experiments/aot_check.py`'s table (Llama-3.2-1B width — what
`chip_smoke.py` serves on the chip — and the benchmark cells' own kernel
shapes) here, the step programs of the five served architectures at their
published widths in `tests/test_chip_compile_arch.py` (a file of its own: a
file is one worker's unit of work). `pytest tests/test_chip_compile.py -k
'<case>'` compiles one. Both take the described chip from conftest's `chip`
fixture. Nothing runs: a pass says the chip's compiler accepts the program,
nothing about results or speed; the chip's own compiler can still abort
what this accepts (`experiments/warm_compile.py`, on the chip).
"""

import re

import pytest

from experiments import aot_check


@pytest.fixture(scope="module")
def thunks(chip):
    """name -> compile thunk of every case in the table, built once."""
    return dict(aot_check.all_cases(chip))


def test_the_table_names_every_case_it_builds(thunks):
    assert tuple(thunks) == aot_check.CASES


@pytest.mark.parametrize("name", aot_check.CASES)
def test_compiles_for_v5e(thunks, name):
    compiled = thunks[name]()
    # the chip's compiler saw a Pallas kernel, not an interpret-mode trace
    # (the expert schemes hold none: they lean on ragged_dot and scatters)
    assert "tpu_custom_call" in compiled.as_text() or name.startswith("moe ")


@pytest.mark.parametrize("name,group,m,k,n", [
    ("q40 decode m=16 deepseek w2(11008x4096)", "_blockdot_call", 16, 11008, 4096),
    # 8 rows ride as 16
    ("q40 decode m=8 granite head(2048x100352)", "_blockdot_call", 16, 2048, 100352),
    # `q40_deq_roofline`: the claimed cell's 48 slots, and a prefill slice
    ("q40 m=48 granite in_proj(2048x8576)", "_deq_call", 48, 2048, 8576),
    ("q40 m=48 granite w2(8192x2048)", "_deq_call", 48, 8192, 2048),
    ("q40 m=512 smallthinker wq(2560x3584)", "_deq_call", 512, 2560, 3584),
])
def test_q40_call_is_named_and_shaped_as_the_benchmark_reads_it(thunks, name, group, m, k, n):
    """`q40_matmul_roofline` and `q40_deq_roofline` find their kernel by the
    device op's group, `_blockdot_call` / `_deq_call` (the compiled
    instruction's name without its number), and price it from that
    instruction's text: the real m, k, n, whatever the call lays out inside."""
    from benchmark.costs import q40_matmul as cost

    calls = [line for line in thunks[name]().as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.search(rf"%({group})(\.\d+)? = ", calls[0]), calls[0][:200]
    assert cost.calls({}, {"hlo": calls[0]}) == cost.cost(m, k, n)


@pytest.mark.parametrize("name", [
    "serve 1b paged decode chunk n=4",
    "serve 1b hybrid step p=64 n=4",
    "serve 1b paged prefill chunk m=256",
])
def test_step_program_moves_no_layer_of_the_pool(thunks, name):
    """The KV page pool rides the layer scan and the step scan as a carry
    and the paged kernel indexes the layer (PR 27): in the compiled program
    no loop body cuts a layer's slice out of the stacked pool, puts one
    back or copies the pool, and the program's temp is not a second pool.
    (At 7B this was 45% of a decode step's device time, PERF.md section 6;
    `experiments/pool_copies.py` is the same reading at that width.) And the
    decode and hybrid programs hold no `slice-start` of a u16 array: XLA's
    memory-space assignment copies no Q40 call's stacked scales into VMEM
    ahead of it, which the calls' VMEM claim keeps out (PERF.md section 6,
    PR 32 and PR 37)."""
    from experiments import pool_copies

    compiled = thunks[name]()
    if "prefill" not in name:
        assert not [line for line in compiled.as_text().splitlines()
                    if re.search(r"= .*slice-start\(", line) and "u16[" in line]
    # serving_cases' 1b pool: [16 layers, 8 slots x 16 blocks + 1, 8, 128, 128] bf16
    layer_bytes = (aot_check.SLOTS * (aot_check.SEQ // 128) + 1) * aot_check.HKV * 128 * 128 * 2
    moved = [m for m in pool_copies.big_movers(compiled.as_text(), layer_bytes)
             if m[1]]
    assert not moved, moved
    pool_bytes = aot_check.N_LAYERS * layer_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2

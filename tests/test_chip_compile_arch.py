"""The step programs of five of the six served architectures (the sixth:
`tests/test_chip_compile_retention.py`), compiled for TPU v5e
with no chip attached: the decode chunk and the hybrid step of each, at the
published widths and the cell's slice, cut in slots, pages and depth to what
the host builds in seconds (`experiments/aot_check.family_cases`). Beside
"the chip's compiler accepts it", each is held to what the benchmark reads
off the compiled program: the kernels' names as the device plane groups
them, each custom call's line as its cost file parses it, and no instruction
that moves a layer of the state or of a pool. `tests/test_chip_compile.py`
is the same check over the kernels and the Llama-3.2-1B programs.
"""

import re

import pytest

from experiments import aot_check

#: family -> what cuts aot_check.family_cases() to an engine the host builds
#: in seconds and a program that compiles in tens of them; the widths, the
#: layer bodies and the slice are the cell's
CUTS = {
    # 40 layers; 8 slots hold 0.6 GB of state on the host and not 3.7
    "hybrid-ssm": dict(slots=8, pages=80),
    # one period of four layers, 4 slots over 320 + the window pool's pages
    "window-moe": dict(slots=4, pages=320, n_layers=4),
    # full depth, 0.5 GB of state and not 2.1 (12 slots, not 8: at 8 a layer's
    # state over the slots is to the byte a latent layer's float32 W_kvb,
    # whose slice out of its stack IS copied, 16.8 MB a latent layer and step)
    "delta-latent": dict(slots=12, pages=120),
    # 12 of 40 layers: the same prefix and the same two period bodies, two
    # periods instead of nine; a 0.2 GB window pool
    "attn-kinds": dict(slots=8, pages=280, n_layers=12),
    # 3 of 9 layers: the dense layer's body and the expert layers' body, two
    # periods instead of eight; the cell's 32 slots
    "rot-latent": dict(pages=160, n_layers=3),
}


@pytest.fixture(scope="module")
def step_program(chip):
    """(family, program name) -> that step program compiled for v5e, the
    family's engine built once."""
    built = {}

    def compiled(family, name):
        if family not in built:
            built[family] = {n.split("-slot ")[1]: thunk for n, thunk in
                             aot_check.family_cases(chip, family, **CUTS[family])}
        return built[family][name]()
    return compiled


def _custom_calls(text):
    """(a compiled program's custom-call lines, the kernels' names as the
    device plane groups them: the instruction's name without its number)"""
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    return calls, {m.group(1) for line in calls
                   for m in [re.search(r"%(_[a-z_]+?)(?:\.\d+)? = ", line)] if m}


@pytest.mark.parametrize("name", ["paged decode chunk n=4",
                                  "hybrid step p=64 n=4"])
def test_hybrid_ssm_step_program_moves_no_layer_of_the_state(step_program, name):
    """The recurrent state [36 layers, slots, 64, 64, 128] f32 rides the
    period scan and the step scan as a carry and `_ssm_step` indexes the
    layer in the stack (input/output aliased): the compiled decode and
    hybrid programs hold no instruction that writes a buffer the size of
    one layer's state over the slots (16.8 MB at 8 slots) or more, in a
    loop body or out of one, other than the kernel's in-place update; a
    prefill slice cuts its ONE slot's 2 MB a layer. The temp is not a
    second state."""
    from experiments import pool_copies

    compiled = step_program("hybrid-ssm", name)
    layer_state = 8 * 64 * 64 * 128 * 4
    moved = pool_copies.big_movers(compiled.as_text(), layer_state)
    assert not moved, moved
    assert "_ssm_step" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 36 * layer_state // 2


@pytest.mark.parametrize("name", ["paged decode chunk n=4",
                                  "hybrid step p=512 n=4"])
def test_window_moe_step_program_compiles_with_its_kernels_named(step_program, name):
    """The decode and hybrid programs compile for v5e; the device plane will
    read the grouped expert kernel (`_expert_call`, beside the attention
    matmuls' `_blockdot_call`) and the paged kernel's two names
    (`_paged_folded` for the global layers, `_paged_window` for the windowed
    ones), each custom call's line parses as its cost file reads it, and no
    instruction writes a layer's expert stack (dequantised or not) or a
    pool's layer."""
    from benchmark.costs import moe_experts, paged_attention
    from experiments import pool_copies

    compiled = step_program("window-moe", name)
    text = compiled.as_text()
    calls, groups = _custom_calls(text)
    assert {"_expert_call", "_blockdot_call", "_paged_folded",
            "_paged_window"} <= groups, groups
    for line in calls:
        if "%_expert_call" in line:
            assert moe_experts.shape({"hlo": line}) in ((64, 2560, 768),
                                                        (64, 768, 2560))
        if "%_paged_" in line:
            assert paged_attention.shape({"hlo": line})[1:] == (4, "bf16")
    one_expert_layer = 64 * 2560 * 768 // 2  # a projection's packed stack
    assert not pool_copies.big_movers(text, one_expert_layer)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("name", ["paged decode chunk n=4",
                                  "hybrid step p=64 n=4"])
def test_delta_latent_step_program_compiles_with_its_kernels_named(step_program, name):
    """The decode and hybrid programs compile for v5e with ONE body a kind
    of layer (a leading dense-FFN KDA layer, then KDA runs of 2, 3, ..., 2
    layers as a loop of a length that is data, and a latent layer): the
    device plane will read `_kda_step`, `_paged_latent` and `_expert_call`
    beside `_deq_call` / `_blockdot_call`, each custom call's line parses as
    its cost file reads it, and no instruction writes a layer's state over
    the slots (`_kda_step` updates the stack in place)."""
    from benchmark.costs import kda_step, moe_experts, paged_attention_latent
    from experiments import pool_copies

    compiled = step_program("delta-latent", name)
    text = compiled.as_text()
    calls, groups = _custom_calls(text)
    assert {"_kda_step", "_paged_latent", "_expert_call", "_deq_call"} <= groups, groups
    count = lambda g: sum(f"%{g}" in l for l in calls)
    # three bodies a step: the prefix layer's and the period's KDA body, one
    # latent layer (a hybrid launch's prefill slice holds the latent sweep
    # once more; its KDA layers scan the jnp step)
    assert count("_kda_step") == 2, count("_kda_step")
    assert count("_paged_latent") == (1 if "decode" in name else 2)
    for line in calls:
        if "%_kda_step" in line:
            assert kda_step.shape({"hlo": line}) == (12, 32, 128, 128, "f32")
        if "%_expert_call" in line:
            assert moe_experts.shape({"hlo": line}) in ((64, 2304, 1024),
                                                        (64, 1024, 2304))
        if "%_paged_latent" in line:
            batch, rows, dtype = paged_attention_latent.shape({"hlo": line})
            assert (batch, dtype) in ((12, "bf16"), (1, "bf16")) and rows >= 32
    layer_state = 12 * 32 * 128 * 128 * 4
    assert not pool_copies.big_movers(text, layer_state)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_attn_kinds_decode_program_compiles_at_two_folds(step_program):
    """The decode program compiles for v5e with the paged sweep at BOTH
    folds in one program (`_paged_folded` at 48 / 8 = 6 query rows a kv
    head, padded to 8; `_paged_window` at 64 / 8 = 8, a window of four
    pages) over a pool a kind, the grouped expert kernel at width 512, and
    each custom call's line parses as its cost file reads it; no instruction
    moves a pool's layer."""
    from benchmark.costs import moe_experts, paged_attention
    from experiments import pool_copies

    compiled = step_program("attn-kinds", "paged decode chunk n=4")
    text = compiled.as_text()
    calls, groups = _custom_calls(text)
    # (at 8 slots the projections are the block-dot tier's)
    assert {"_paged_folded", "_paged_window", "_expert_call", "_blockdot_call"} <= groups, groups
    count = lambda g: sum(f"%{g}" in l for l in calls)
    # a global layer in the prefix and one in the period; the windowed
    # layers' run in the prefix and in the period
    assert (count("_paged_folded"), count("_paged_window")) == (2, 2)
    for line in calls:
        if "%_paged_" in line:
            assert paged_attention.shape({"hlo": line}) == (8, 8, "bf16")
            assert re.search(r"= \(f32\[8,8,8,128\]", line)  # folds 6 (padded) and 8
        if "%_expert_call" in line:
            assert moe_experts.shape({"hlo": line}) in ((64, 2048, 512),
                                                        (64, 512, 2048))
    window_layer = 8 * 7 * 8 * 128 * 128 * 2  # a layer's slice of the window pool
    assert not pool_copies.big_movers(text, window_layer)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_rot_latent_decode_program_compiles_with_its_kernels_named(step_program):
    """The decode program compiles for v5e: the latent sweep at 64 query
    heads (`_paged_latent`, one a layer body), the grouped expert kernel at
    7,168 x 2,048 and back over the held group of 24, the projections through
    the q-side rank (`_deq_call` at k = 1,536 and n = 1,536), and each custom
    call's line parses as its cost file reads it; no instruction moves a
    layer of the latent pool (W_kvb's float32 layer slice is moved, and is
    the only thing of that size that is)."""
    from benchmark.costs import moe_experts, paged_attention_latent
    from experiments import pool_copies

    compiled = step_program("rot-latent", "paged decode chunk n=4")
    text = compiled.as_text()
    calls, groups = _custom_calls(text)
    assert {"_paged_latent", "_expert_call", "_deq_call"} <= groups, groups
    count = lambda g: sum(f"%{g}" in l for l in calls)
    assert count("_paged_latent") == 2  # the dense layer's body, the expert layers'
    assert any(re.search(r"%_deq_call(\.\d+)? = f32\[32,1536\]", l) for l in calls)
    assert any(re.search(r"%_deq_call(\.\d+)? = f32\[32,12288\]", l) for l in calls)
    for line in calls:
        if "%_expert_call" in line:
            assert moe_experts.shape({"hlo": line}) in ((24, 7168, 2048),
                                                        (24, 2048, 7168))
        if "%_paged_latent" in line:
            assert paged_attention_latent.shape({"hlo": line}) == (32, 64, "bf16")
    pool_layer = 161 * 128 * 640 * 2  # a layer's slice of the latent pool
    # the one thing of that size a layer moves is W_kvb's float32 slice, cut
    # out of its stack for the absorb and expand products (33.6 MB a layer and
    # step, what `mla_proj_small_ops_busy_share` reads; ROADMAP Reach 2)
    w_kvb = 64 * (128 + 128) * 512 * 4
    assert {m[-1] for m in pool_copies.big_movers(text, pool_layer)} <= {w_kvb}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("family,name,kernels", [
    ("attn-kinds", "hybrid step p=256 n=4",
     {"_paged_folded", "_paged_window", "_expert_call", "_deq_call"}),
    ("rot-latent", "hybrid step p=512 n=4", {"_paged_latent", "_expert_call", "_deq_call"}),
])
def test_hybrid_step_compiles_at_the_cells_slice(step_program, family, name, kernels):
    """The hybrid step of the two cells that launch nothing else (a slice of
    the cell's own `--max-prefill-chunk` beside the decode batch) compiles
    for v5e with its kernels named, the slice's rows on the dequantising
    tier."""
    compiled = step_program(family, name)
    _, groups = _custom_calls(compiled.as_text())
    assert kernels <= groups, groups
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


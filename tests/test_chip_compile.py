"""The serving path's programs, compiled for TPU v5e with no chip attached.

libtpu is installed here, and a described topology lets XLA:TPU + Mosaic
compile for a chip that is not there. Interpret mode — what every other
kernel test in this suite runs — cannot show a slice that is not aligned to
the tiling, a VMEM overflow or a program that does not fit HBM; this can
(it is how PR 21 found that Mosaic refuses the paged kernel at
Llama-3.2-1B's head size 64 until the pool rows are whole 128-lane vectors).

The cases are `experiments/aot_check.py`'s own, at Llama-3.2-1B width — the
width `chip_smoke.py` serves on the chip — so the script, MOSAIC_AOT.md and
these tests cannot drift apart. Nothing runs: a pass says the chip's
compiler accepts the program, nothing about results or speed.
"""

import jax
import pytest

from dllama_tpu.ops import matmul as mmod
from experiments import aot_check

#: a subset of aot_check.all_cases() by name: the kernels and whole programs
#: of `serve --slots 8 --max-seq-len 2048 --spec-k 4` on a 1b model
CASES = (
    "q40 decode m=8 w1(2048x8192)",
    "q40 decode m=8 w2(8192x2048)",
    "q40 decode m=8 wcls(2048x128256)",
    # the block-dot kernel at the benchmark cells' decode shapes
    "q40 decode m=16 deepseek wq(4096x4096)",
    "q40 decode m=16 deepseek w1(4096x11008)",
    "q40 decode m=16 deepseek w2(11008x4096)",
    "q40 decode m=16 deepseek head(4096x102400)",
    "q40 decode m=8 granite head(2048x100352)",
    "q40 decode m=8 granite in_proj(2048x8576)",
    "q40 prefill m=256 w1(2048x8192)",
    "q40 prefill m=256 w2(8192x2048)",
    "q40 prefill m=256 wcls(2048x128256)",
    # the dequantising tier (m > 16) at the cells' shapes: another tile each
    "q40 m=48 granite in_proj(2048x8576)",
    "q40 m=48 granite out_proj(4096x2048)",
    "q40 m=48 granite w1(2048x8192)",
    "q40 m=48 granite w2(8192x2048)",
    "q40 m=48 granite head(2048x100352)",
    "q40 m=512 smallthinker wq(2560x3584)",
    "q40 m=128 deepseek w2(11008x4096)",
    "flash decode t=1 S=2048 hd=64",
    "flash prefill t=256 S=2048 hd=64",
    "paged decode t=1 p=128 hd=64 fused scatter",
    "paged spec verify t=5 p=128 hd=64 fused scatter",
    "paged decode t=1 p=16 hd=64 fused scatter",
    "paged decode t=1 p=128 hd=128 fused scatter",
    "paged decode t=1 p=128 hd=64 layer-indexed stack",
    "paged prefill t=256 p=128 hd=64 layer-indexed stack (XLA pre-scatter)",
    # the benchmark's two cells at their own shapes: head blocks of 32 and 8
    "paged decode t=1 p=128 b=12 Hkv=32 hd=128 layer-indexed stack "
    "(deepseek7b.decode_closed)",
    "paged decode t=1 p=128 b=48 Hkv=8 hd=64 layer-indexed stack "
    "(granite4h.reason_closed)",
    # the latent sweep's several pages a pass at the two latent cells' shapes
    "paged latent decode t=1 p=128 b=32 64 heads x 576 layer-indexed stack "
    "(axk1.long_reason_closed)",
    "paged latent slice t=512 p=128 64 heads x 576 layer-indexed stack "
    "(axk1.long_reason_closed, XLA pre-scatter)",
    "paged latent decode t=1 p=128 b=48 32 heads x 576 layer-indexed stack "
    "(kimilinear.reason_closed)",
    "tp=4 shard_map mm in-shard+psum (w2)",
    "serve 1b paged decode chunk n=4",
    "serve 1b hybrid step p=64 n=4",
)


@pytest.fixture(scope="module")
def thunks():
    """name -> compile thunk, built once: the described topology, the
    platform steer, and the persistent compile cache off (an entry written
    for a described chip cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(aot_check.TARGET, platform="tpu")
    except Exception as e:  # no libtpu / no topology support in this install
        pytest.skip(f"cannot describe {aot_check.TARGET}: {e!r}"[:200])
    mp = pytest.MonkeyPatch()
    # kernels=auto / interpret= derive from the platform; the chip is only
    # described, so steer the one place the package asks (in the test, not
    # through an option of the program)
    mp.setattr(mmod, "device_platform", lambda: "tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest forces true-f32 dots for the numerics tests; the program
    # the chip runs traces at the default precision (and Mosaic refuses a
    # bf16 matmul asked for at fp32 contract precision)
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        yield {name: thunk for name, thunk, _ in aot_check.all_cases(topo)}
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.mark.parametrize("name", CASES)
def test_compiles_for_v5e(thunks, name):
    compiled = thunks[name]()
    # the chip's compiler saw a Pallas kernel, not an interpret-mode trace
    assert "tpu_custom_call" in compiled.as_text()


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
    import re

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
    `experiments/pool_copies.py` is the same reading at that width.)"""
    from experiments import pool_copies

    compiled = thunks[name]()
    # serving_cases' 1b pool: [16 layers, 8 slots x 16 blocks + 1, 8, 128, 128] bf16
    layer_bytes = (aot_check.SLOTS * (aot_check.SEQ // 128) + 1) * aot_check.HKV * 128 * 128 * 2
    moved = [m for m in pool_copies.big_movers(compiled.as_text(), layer_bytes)
             if m[1]]
    assert not moved, moved
    pool_bytes = aot_check.N_LAYERS * layer_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2


# ------------------------------------------- the hybrid state-space model


@pytest.fixture(scope="module")
def hybrid_thunks(thunks):
    """The hybrid state-space / attention model's step programs at its
    published widths, 40 layers (aot_check.hybrid_cases), at 8 slots so the
    engine built on the host holds 0.6 GB of state and not 3.7. Depends on
    `thunks` for the platform steer and the cache settings."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(aot_check.TARGET, platform="tpu")
    return {name.split("-slot ")[1]: thunk
            for name, thunk, _ in aot_check.hybrid_cases(topo, slots=8, pages=80)}


@pytest.mark.parametrize("name", ["paged decode chunk n=4",
                                  "hybrid step p=64 n=4"])
def test_hybrid_ssm_step_program_moves_no_layer_of_the_state(hybrid_thunks, name):
    """The recurrent state [36 layers, slots, 64, 64, 128] f32 rides the
    period scan and the step scan as a carry and `_ssm_step` indexes the
    layer in the stack (input/output aliased): the compiled decode and
    hybrid programs hold no instruction that writes a buffer the size of
    one layer's state over the slots (16.8 MB at 8 slots) or more, in a
    loop body or out of one, other than the kernel's in-place update; a
    prefill slice cuts its ONE slot's 2 MB a layer. The temp is not a
    second state."""
    from experiments import pool_copies

    compiled = hybrid_thunks[name]()
    layer_state = 8 * 64 * 64 * 128 * 4
    moved = pool_copies.big_movers(compiled.as_text(), layer_state)
    assert not moved, moved
    assert "_ssm_step" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 36 * layer_state // 2


# --------------------- windowed attention layers over routed experts


@pytest.fixture(scope="module")
def window_moe_thunks(thunks):
    """The window-and-global, routed-expert model's step programs at its
    published widths (aot_check.window_moe_cases: 2,560 stream, 28/4 heads
    of 128, 64 experts of 768 with 6 active, a 151,936-row head), one
    period of four layers and 4 slots over 320 + the window pool's pages so
    the engine built on the host stays small. Depends on `thunks` for the
    platform steer and the cache settings."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(aot_check.TARGET, platform="tpu")
    return {name.split("-slot ")[1]: thunk for name, thunk, _ in
            aot_check.window_moe_cases(topo, slots=4, pages=320, n_layers=4)}


@pytest.mark.parametrize("name", ["paged decode chunk n=4",
                                  "hybrid step p=512 n=4"])
def test_window_moe_step_program_compiles_with_its_kernels_named(window_moe_thunks, name):
    """The decode and hybrid programs compile for v5e; the device plane will
    read the grouped expert kernel (`_expert_call`, beside the attention
    matmuls' `_blockdot_call`) and the paged kernel's two names
    (`_paged_folded` for the global layers, `_paged_window` for the windowed
    ones), each custom call's line parses as its cost file reads it, and no
    instruction writes a layer's expert stack (dequantised or not) or a
    pool's layer."""
    import re

    from benchmark.costs import moe_experts, paged_attention
    from experiments import pool_copies

    compiled = window_moe_thunks[name]()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    groups = {m.group(1) for l in calls
              for m in [re.search(r"%(_[a-z_]+?)(?:\.\d+)? = ", l)] if m}
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


# ------- delta-rule / latent-attention layers over one chip's share of experts


@pytest.fixture(scope="module")
def delta_latent_thunks(thunks):
    """The delta-rule / latent-attention model's step programs at its
    published widths and full depth (aot_check.delta_latent_cases: 2,304
    stream, 20 KDA layers of 32 x 128 x 128 state, 7 latent layers of 512 +
    64, 64 held of 256 experts), at 12 slots over 120 pages so the engine
    built on the host holds 0.5 GB of state and not 2.1 (12, not 8: at 8 a
    layer's state over the slots is to the byte a latent layer's float32
    W_kvb, whose slice out of its stack IS copied, 16.8 MB a latent layer
    and step). Depends on `thunks` for the platform steer and the cache
    settings."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(aot_check.TARGET, platform="tpu")
    return {name.split("-slot ")[1]: thunk for name, thunk, _ in
            aot_check.delta_latent_cases(topo, slots=12, pages=120)}


@pytest.mark.parametrize("name", ["paged decode chunk n=4",
                                  "hybrid step p=64 n=4"])
def test_delta_latent_step_program_compiles_with_its_kernels_named(
        delta_latent_thunks, name):
    """The decode and hybrid programs compile for v5e with ONE body a kind
    of layer (a leading dense-FFN KDA layer, then KDA runs of 2, 3, ..., 2
    layers as a loop of a length that is data, and a latent layer): the
    device plane will read `_kda_step`, `_paged_latent` and `_expert_call`
    beside `_deq_call` / `_blockdot_call`, each custom call's line parses as
    its cost file reads it, and no instruction writes a layer's state over
    the slots (`_kda_step` updates the stack in place)."""
    import re

    from benchmark.costs import kda_step, moe_experts, paged_attention_latent
    from experiments import pool_copies

    compiled = delta_latent_thunks[name]()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    groups = {m.group(1) for l in calls
              for m in [re.search(r"%(_[a-z_]+?)(?:\.\d+)? = ", l)] if m}
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


# ---------------- attention by layer kind over one chip's share of experts


@pytest.fixture(scope="module")
def attn_kinds_thunks(thunks):
    """The step programs of the model whose attention goes by layer kind at
    its published widths (aot_check.attn_kinds_cases: 2,048 stream, 48 global
    / 64 windowed query heads over 8 kv heads of 128, a 512-row window, 64
    held of 256 experts of width 512), at 12 of its 40 layers (the same
    prefix and the same two period bodies, two periods instead of nine) and
    8 slots over 280 pages so the engine built on the host holds a 0.2 GB
    window pool. Depends on `thunks` for the platform steer and the cache
    settings."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(aot_check.TARGET, platform="tpu")
    return {name.split("-slot ")[1]: thunk for name, thunk, _ in
            aot_check.attn_kinds_cases(topo, slots=8, pages=280, n_layers=12)}


def test_attn_kinds_decode_program_compiles_at_two_folds(attn_kinds_thunks):
    """The decode program compiles for v5e with the paged sweep at BOTH
    folds in one program (`_paged_folded` at 48 / 8 = 6 query rows a kv
    head, padded to 8; `_paged_window` at 64 / 8 = 8, a window of four
    pages) over a pool a kind, the grouped expert kernel at width 512, and
    each custom call's line parses as its cost file reads it; no instruction
    moves a pool's layer."""
    import re

    from benchmark.costs import moe_experts, paged_attention
    from experiments import pool_copies

    compiled = attn_kinds_thunks["paged decode chunk n=4"]()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    groups = {m.group(1) for l in calls
              for m in [re.search(r"%(_[a-z_]+?)(?:\.\d+)? = ", l)] if m}
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


# ------- rotated latent attention over one routing group of wide experts


@pytest.fixture(scope="module")
def rot_latent_thunks(thunks):
    """The step programs of the rotated-latent model at its published widths
    (aot_check.rot_latent_cases: 7,168 stream, 64 heads over a 512 + 64
    latent row, q through 1,536, 24 held of 192 experts of width 2,048 in 8
    groups), at 3 of its 9 layers (the dense layer's body and the expert
    layers' body, two periods instead of eight) and the cell's 32 slots over
    160 pages. Depends on `thunks` for the platform steer and the cache
    settings."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(aot_check.TARGET, platform="tpu")
    return {name.split("-slot ")[1]: thunk for name, thunk, _ in
            aot_check.rot_latent_cases(topo, pages=160, n_layers=3)}


def test_rot_latent_decode_program_compiles_with_its_kernels_named(rot_latent_thunks):
    """The decode program compiles for v5e: the latent sweep at 64 query
    heads (`_paged_latent`, one a layer body), the grouped expert kernel at
    7,168 x 2,048 and back over the held group of 24, the projections through
    the q-side rank (`_deq_call` at k = 1,536 and n = 1,536), and each custom
    call's line parses as its cost file reads it; no instruction moves a
    layer of the latent pool (W_kvb's float32 layer slice is moved, and is
    the only thing of that size that is)."""
    import re

    from benchmark.costs import moe_experts, paged_attention_latent
    from experiments import pool_copies

    compiled = rot_latent_thunks["paged decode chunk n=4"]()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    groups = {m.group(1) for l in calls
              for m in [re.search(r"%(_[a-z_]+?)(?:\.\d+)? = ", l)] if m}
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

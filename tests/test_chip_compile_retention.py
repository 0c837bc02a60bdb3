"""The step programs of the architecture with NO cache rows (power retention
in every layer, `experiments/aot_check.FAMILIES["retention"]`), compiled for
TPU v5e with no chip attached at the published widths, cut in slots and depth
to what the host builds in seconds. A file of its own so that
`tests/test_chip_compile_arch.py`, the same check over the other five, stays
under the five minutes a file may take (tests/conftest.py)."""

import pytest

from experiments import aot_check
from tests.test_chip_compile_arch import _custom_calls

#: 2 of 10 layers (one body either way), 4 slots: 0.29 GB of state on the
#: host and not 8.7
CUT = dict(slots=4, n_layers=2)


@pytest.fixture(scope="module")
def step_program(chip):
    """program name -> that step program compiled for v5e, the engine built
    once."""
    built = {}

    def compiled(family, name):
        if not built:
            built.update({n.split("-slot ")[1]: thunk for n, thunk in
                          aot_check.family_cases(chip, family, **CUT)})
        return built[name]()
    return compiled


@pytest.mark.parametrize("name", ["paged decode chunk n=4", "hybrid step p=64 n=4",
                                  "hybrid step p=16 n=4",
                                  "paged prefill chunk m=256"])
def test_retention_step_program_moves_no_layer_of_the_state(step_program, name):
    """A model with NO cache rows compiles for v5e: the state [layers, slots,
    8, 136, 8320] f32 rides the scans as a carry and `_retention_step`
    indexes the layer in the stack (input/output aliased), so no instruction
    writes a layer's state over the slots (145 MB at 4 slots) or more; what a
    B = 1 slice moves is ONE slot's layer (36.2 MB cut out and put back) and
    phi of its rows. Held in whole tiles: at 129 x 8,256 the device keeps the
    array's dims in another order and every launch copied the WHOLE state in
    and out (PERF.md section 6, PR 51). The custom call's line parses as its
    cost file reads it; the page pool has no layer."""
    from benchmark.costs import retention_step
    from experiments import pool_copies

    compiled = step_program("retention", name)
    text = compiled.as_text()
    calls, groups = _custom_calls(text)
    decodes = "prefill" not in name
    assert ("_retention_step" in groups) == decodes, groups
    for line in calls:
        if "%_retention_step" in line:
            assert retention_step.shape({"hlo": line}) == (4, 8, 136, 8320, "f32")
    layer_state = 4 * 8 * 136 * 8320 * 4
    # (phi of a 256-row chunk's 40 query heads is larger than 4 slots' layer)
    phi_q = 256 * 40 * 8320 * 4
    assert {m[-1] for m in pool_copies.big_movers(text, layer_state)} <= (
        set() if decodes else {phi_q})
    assert "bf16[0," in text  # the pool: a layer axis of 0
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30

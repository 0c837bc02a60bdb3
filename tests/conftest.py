"""Test harness: simulate an 8-device TPU-like mesh on CPU.

The reference tests multi-node behavior by spawning localhost worker processes
(examples/n-workers.sh); we do strictly better — every distributed test runs in
CI on a virtual 8-device mesh via XLA's host-platform device splitting
(SURVEY.md §4). Env vars must be set before jax initializes.
"""

import os

# Force CPU: the tests never touch an accelerator, whatever the caller's
# environment says. XLA_FLAGS is read lazily at first backend init, so
# setting it here works.
os.environ["JAX_PLATFORMS"] = "cpu"
# Paged-KV allocator auditing after EVERY release (engine/batch.PagePool):
# any refcount/free-list corruption fails at the release that caused it,
# suite-wide, instead of surfacing as a mystery page leak later.
os.environ.setdefault("DLLAMA_POOL_AUDIT", "1")
# Runtime lock-order sanitizer (utils/locks, ISSUE 14): every named lock
# the stack creates audits its acquisition rank suite-wide — an
# out-of-rank nesting (the shape that deadlocks once two threads
# interleave) raises LockOrderError naming both hold sites, at the test
# that introduced it. Must be set before dllama_tpu.obs imports.
os.environ.setdefault("DLLAMA_LOCK_AUDIT", "1")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import logging

import jax
import numpy as np
import pytest

# Daemon threads (HTTP server handlers, scheduler workers) can emit a log
# record after pytest has closed the capture stream their handler is bound
# to; logging then prints a multi-line "--- Logging error ---" dump to
# stderr, which interleaves with the -q progress dots and corrupts the
# tier-1 DOTS_PASSED accounting. The records themselves are harmless
# teardown noise — drop the dump, keep the records.
logging.raiseExceptions = False

jax.config.update("jax_platforms", "cpu")

# This process compiles cold unless the caller's environment placed a cache:
# tests that call the CLI's main() in-process run place_compile_cache(),
# which would otherwise switch the persistent cache on for every later test
# (the compile-ledger tests count REAL compiles; a cache hit fires none).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_enable_compilation_cache", False)

# This JAX build's default matmul precision is bf16-like even for f32 inputs
# (on every backend). Tests compare f32 numerics against torch/numpy, so force
# true-f32 dots; production uses bf16 activations where the default is exact.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="module")
def chip():
    """The described (not attached) TPU v5e the compile checks compile for
    (tests/test_chip_compile*.py), with the platform steer, and the
    persistent compile cache off (an entry written for a described chip
    cannot be read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    from dllama_tpu.ops import matmul as mmod
    from experiments import aot_check

    try:
        topo = aot_check.topology()
    except SystemExit as e:  # no libtpu / no topology support in this install
        pytest.skip(str(e)[:200])
    mp = pytest.MonkeyPatch()
    # kernels=auto / interpret= derive from the platform; the chip is only
    # described, so steer the one place the package asks (in the test, not
    # through an option of the program)
    mp.setattr(mmod, "device_platform", lambda: "tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # this file forces true-f32 dots for the numerics tests; the program
    # the chip runs traces at the default precision (and Mosaic refuses a
    # bf16 matmul asked for at fp32 contract precision)
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        yield topo
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        mp.undo()


#: The files that cost over ~150 s of the driver's run (junit seconds, the
#: table in CHANGES.md PR 49), heaviest first; everything else after them as
#: collected. The tier-1 command runs `-n 6 --dist loadfile`: a file is ONE
#: unit of work, handed to the next free worker in this order, so the longest
#: must not start last (five workers idled behind test_experiments.py for a
#: quarter of the run). A file that grows past ~150 s goes in here; past 300 s
#: it is split along a seam it has.
_HEAVIEST_FIRST = (
    "test_paged_kernel.py",
    "test_chip_compile_arch.py",
    "test_delta_latent.py",
    "test_pallas_kernels.py",
    "test_latent_rope_groups.py",
    "test_chip_compile.py",
    "test_state_space.py",
    "test_laguna.py",
    "test_experiments.py",
    "test_window_moe.py",
    "test_batch_engine.py",
    "test_paged_kv.py",
    "test_engine.py",
    "test_continuous_serve.py",
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow') — long drills "
        "whose coverage an unfiltered `pytest tests/` keeps")
    # xdist (3.8) by default re-sorts loadfile's units by their NUMBER of
    # tests, whatever order they were collected in: keep the order below
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(_HEAVIEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.fspath.basename, len(rank)))

"""CPU rehearsal of `sampler_greedy_launch_share` (PR 52): the accepted
`ratio_of_deltas` reducer over two scrapes of a tiny engine's launches, as
`run.py` takes them (the program's own render through the harness's own
parser), with the None path of a program that has no such counter."""

import json
import os

import jax.numpy as jnp
import pytest

from benchmark import loadlib
from benchmark.reducers import ratio_of_deltas

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "metrics", "sampler_greedy_launch_share.json")) as f:
    SPEC = json.load(f)


def scrape():
    from dllama_tpu.obs import metrics

    return {"metrics": loadlib.prometheus(metrics.render())}


@pytest.fixture(scope="module")
def engine():
    from dllama_tpu.engine.batch import BatchEngine
    from dllama_tpu.models.config import LlamaConfig
    from dllama_tpu.models.llama import random_params

    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=96, seq_len=64)
    return BatchEngine(cfg, random_params(cfg, seed=9, dtype=jnp.float32,
                                          quantize=False),
                       n_slots=3, cache_dtype=jnp.float32)


def share(before, after):
    return ratio_of_deltas.reduce(SPEC["params"], {
        "before": before, "after": after, "config": {}})


def test_greedy_run_reads_100_and_a_sampled_batch_mate_moves_it(engine):
    """Every benchmark stream is greedy (`loadlib`: temperature 0.0): the
    window's launches all count under `greedy` and the share is 100. One
    sampled stream in the batch takes every launch it decodes in off it."""
    engine.add(0, [1, 2, 3], temperature=0.0)
    engine.add(1, [4, 5], temperature=0.0)
    before = scrape()
    for _ in range(3):
        engine.decode(2)
    greedy = scrape()
    assert share(before, greedy) == pytest.approx(100.0)
    engine.add(2, [6, 7], temperature=0.8, topp=0.9)
    engine.decode(2)
    assert share(before, scrape()) == pytest.approx(75.0)
    engine.release(2)  # its stale temperature counts for nothing
    engine.decode(2)
    assert share(before, scrape()) == pytest.approx(80.0)


def test_a_program_without_the_counter_leaves_the_metric_out():
    """The parent commit exports no such family: the denominator does not
    move, the reducer answers None and the line leaves the metric out."""
    parent = {"metrics": loadlib.prometheus(
        'dllama_launches_total{kind="decode"} 41\n')}
    later = {"metrics": loadlib.prometheus(
        'dllama_launches_total{kind="decode"} 97\n')}
    assert share(parent, later) is None
    assert share({"metrics": {}}, {"metrics": {}}) is None

"""The benchmark's model files, held in tier-1 (numpy only): adding a layout
or editing the writer must not move the bytes an accepted cell is measured
on, and the program's reader must read what the benchmark's writer wrote.

Copies of `benchmark/tests/test_second_architecture.py::
test_llama_files_are_the_parents_bytes` and `benchmark/tests/
test_benchmark.py::test_model_file_is_deterministic_and_read_by_the_program`
(benchmark/tests is the builder's rehearsal and not part of tier-1; PERF.md
section 7, "Left out of PR 28"). The hybrid layout's own sha256 guard is in
`tests/test_state_space.py`.
"""

import hashlib
import json
import os

import pytest

from benchmark import files

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny-llama.json")

# sha256 of tiny-llama's `.m` by seed and of its `.t`, recorded from PR 27's
# benchmark/files.py (b13e018, before the layout moved out of it)
PARENT_M = {3: "18a7b7953e59982d039caedb21ab2ae117869629911dcd9f48c975c27a623ac1",
            2147483659: "dafd6b0ae173c3a22a2bb798c8619ba0d965b40801aa0942066499a404bce4a7"}
PARENT_T = "1fdc226b8039eccf7e5c672d9b8c2fbdc49666f478e20ee948ba3682f56852be"


def sha256(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def tiny_config():
    with open(TINY) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", sorted(PARENT_M))
def test_llama_files_are_the_parents_bytes(tmp_path, seed):
    model, tok, size = files.write_files(tiny_config(), seed, str(tmp_path))
    assert sha256(model) == PARENT_M[seed]
    assert sha256(tok) == PARENT_T
    assert size == os.path.getsize(model)


def test_model_file_is_deterministic_and_read_by_the_program(tmp_path):
    from benchmark.layouts import llama as layout
    from dllama_tpu.models.formats import read_header
    from dllama_tpu.tokenizer.tokenizer import Tokenizer

    cfg = tiny_config()
    first, again, other = (str(tmp_path / n) for n in ("a.m", "b.m", "c.m"))
    files.write_model(first, cfg, 3)
    files.write_model(again, cfg, 3, workers=1)
    files.write_model(other, cfg, 2**31 + 7)  # more than 32 signed bits hold
    assert sha256(first) == sha256(again) != sha256(other)
    prog, header = read_header(first)
    mine, header2 = layout.read_header(first)
    assert header == header2
    assert (prog.dim, prog.hidden_dim, prog.n_layers, prog.n_kv_heads) == (
        mine["dim"], mine["hidden_dim"], mine["n_layers"], mine["n_kv_heads"])
    assert not prog.recurrent and prog.layer_kinds == ()
    tok = str(tmp_path / "t.t")
    files.write_tokenizer(tok, cfg["vocab_size"])
    ids = Tokenizer.load(tok).encode("helloworld")
    assert len(ids) == 1 + len("helloworld")  # BOS + one token a byte

"""ISSUE 14: the repo-native invariant analyzer + lock-order sanitizer.

Three layers, mirroring the acceptance criteria:

* **red fixtures** — every rule family has a minimal snippet that trips
  exactly its rule (and a suppression/compliant variant that goes green):
  an analyzer rule without a committed red test is a rule nobody knows
  still fires;
* **clean pass** — the LIVE repo analyzes to zero findings (and stays
  jax-free and fast): the gate merges at zero, so any regression is the
  offender's diff, not pre-existing noise;
* **lock sanitizer units** — utils/locks: an out-of-rank acquisition
  raises with BOTH hold sites named, reentrant RLock re-entry is legal,
  and with the audit off the factories return plain threading locks
  (zero overhead).

Pure host — no jax import, no model, sub-second per test (the CLI
round-trip test spawns one interpreter).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import dllama_tpu
from dllama_tpu.analysis.core import RULE_CATALOG, Diagnostic, Project, run
from dllama_tpu.utils import locks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(dllama_tpu.__file__)))

#: context-file rules that fire on minimal in-memory projects simply
#: because README/aot_check.py aren't part of the fixture
_CONTEXT_RULES = {"gate-routes", "gate-aot", "gate-scripts", "doc-rules",
                  "doc-ranks", "lock-unranked"}


def findings(files: dict, keep_context: bool = False) -> list[Diagnostic]:
    diags = run(Project(files))
    if not keep_context:
        diags = [d for d in diags if d.rule not in _CONTEXT_RULES]
    return diags


def rules_of(diags) -> list[str]:
    return [d.rule for d in diags]


# ------------------------------------------------------------- jit rules


JIT_BAD = '''
import jax
from dllama_tpu.obs import compile as compile_obs


class E:
    def __init__(self):
        self._decode = jax.jit(self._decode_impl)

    @staticmethod
    def _decode_impl(x):
        return x

    def decode(self, x):
        return self._decode(x)
'''


def test_jit_scope_red():
    diags = findings({"dllama_tpu/engine/fake.py": JIT_BAD})
    assert rules_of(diags) == ["jit-scope"]
    assert diags[0].line == 15  # the `return self._decode(x)` line
    assert "self._decode" in diags[0].message


def test_jit_scope_green_under_scope():
    ok = JIT_BAD.replace(
        "        return self._decode(x)",
        "        with compile_obs.LEDGER.scope(\"decode\", \"n1\"):\n"
        "            return self._decode(x)")
    assert findings({"dllama_tpu/engine/fake.py": ok}) == []


def test_jit_scope_suppression_green_with_reason():
    ok = JIT_BAD.replace(
        "    def decode(self, x):",
        "    def decode(self, x):  # dllama: allow[jit-scope] warm thunk")
    assert findings({"dllama_tpu/engine/fake.py": ok}) == []


def test_jit_scope_bare_suppression_is_a_finding():
    bare = JIT_BAD.replace(
        "    def decode(self, x):",
        "    def decode(self, x):  # dllama: allow[jit-scope]")
    assert rules_of(findings({"dllama_tpu/engine/fake.py": bare})) \
        == ["suppress-reason"]


def test_jit_scope_docstring_mention_is_not_a_suppression():
    doc = JIT_BAD.replace(
        "    def decode(self, x):",
        '    def decode(self, x):\n        "# dllama: allow[jit-scope] prose"')
    assert "jit-scope" in rules_of(findings(
        {"dllama_tpu/engine/fake.py": doc}))


def test_jit_scope_impl_functions_are_not_dispatch_sites():
    impl = '''
import jax

def _body(x):
    return helper(x)

helper = jax.jit(lambda x: x)
_fused = jax.jit(_body)
'''
    # helper() inside _body (an impl handed to jax.jit) is traced code
    assert findings({"dllama_tpu/engine/fake.py": impl}) == []


def test_jit_scope_factory_table_dispatch():
    fac = '''
import jax

def make_decoder():
    return jax.jit(lambda x: x)


class E:
    def __init__(self):
        self._decoders = {}
        self._decoders[1] = make_decoder()

    def go(self, x):
        return self._decoders[1](x)
'''
    diags = findings({"dllama_tpu/engine/fake.py": fac})
    assert rules_of(diags) == ["jit-scope"]
    assert "self._decoders[...]" in diags[0].message


def test_jit_label_red():
    bad = '''
from dllama_tpu.obs import compile as compile_obs

def go():
    with compile_obs.LEDGER.scope("not_a_label", "k"):
        pass
'''
    diags = findings({"dllama_tpu/engine/fake.py": bad})
    assert rules_of(diags) == ["jit-label"]
    assert "not_a_label" in diags[0].message


# ------------------------------------------------------- dev-state rule


DEV_TMPL = '''
import jax.numpy as jnp


class E:
    def {name}(self, slot):
        {body}
'''


def _dev(name, body):
    return {"dllama_tpu/engine/fake.py":
            DEV_TMPL.format(name=name, body=body)}


def test_dev_state_red_bulk_upload():
    diags = findings(_dev("oops", "self._pos_dev = jnp.asarray(self.pos)"))
    assert rules_of(diags) == ["dev-state"]
    assert "_pos_dev" in diags[0].message


@pytest.mark.parametrize("body", [
    "self._pos_dev = self._pos_dev.at[slot].set(0)",  # surgical row write
    "(x, self._keys_dev, self._pos_dev) = self._decode(slot)",  # jit carry
    "self._last_dev = nxt" .replace("nxt", "slot"),  # local carry name
])
def test_dev_state_green_sanctioned_shapes(body):
    assert findings(_dev("step", body)) == []


def test_dev_state_green_in_sanctioned_fns():
    for fn in ("__init__", "warm_restart", "_sync_vectors"):
        assert findings(_dev(fn, "self._pos_dev = jnp.zeros(4)")) == []


def test_dev_state_red_outside_engine_ignored():
    # the rule is scoped to engine/ modules
    files = {"dllama_tpu/serve/fake.py":
             DEV_TMPL.format(name="oops",
                             body="self._pos_dev = jnp.asarray(self.pos)")}
    assert findings(files) == []


# -------------------------------------------------------- catalog rules


def test_catalog_metric_red_and_sited_green():
    bad = 'from dllama_tpu.obs import metrics\nX = metrics.counter("x", "h")\n'
    diags = findings({"dllama_tpu/serve/fake.py": bad})
    assert rules_of(diags) == ["catalog-metric"]
    # the same text IS the single registration site in instruments.py
    assert findings({"dllama_tpu/obs/instruments.py": bad}) == []


def test_catalog_span_event_red():
    bad = '''
from dllama_tpu.obs import trace

def go():
    trace.TRACER.event("bogus.event")
    tr = trace.TRACER
    tr.span_at("bogus.span", 0.0, 1.0)
    trace.TRACER.event("drain.begin")   # cataloged: green
'''
    diags = findings({"dllama_tpu/serve/fake.py": bad})
    assert sorted(rules_of(diags)) == ["catalog-event", "catalog-span"]


SEAM_CALLS = {
    # the profiler clock's one writer: the literal prefix is the name
    "profiler_annotation": ('trace.profiler_annotation("{head}", kind)',
                            "dllama.launch.", "mystery.launch."),
    # the phase seam, as the scheduler and the engine reach it
    "self.phases": ('self.phases("{head}", 3)', "emit.scan", "emit.mystery"),
    "ph": ('ph("{head}", 3)', "consume.wait", "consume.mystery"),
}


@pytest.mark.parametrize("how", sorted(SEAM_CALLS))
def test_catalog_span_covers_the_seam(how):
    """A call of obs/trace.profiler_annotation or of the phase seam is a
    span emission: its literal name (the prefix) is a SPAN_CATALOG name."""
    call, good, bad = SEAM_CALLS[how]
    tmpl = ("from dllama_tpu.obs import trace\n\n"
            "def go(self, ph, kind):\n    return {call}\n")
    diags = findings({"dllama_tpu/serve/fake.py":
                      tmpl.format(call=call.format(head=bad))})
    assert rules_of(diags) == ["catalog-span"]
    assert findings({"dllama_tpu/serve/fake.py":
                     tmpl.format(call=call.format(head=good))}) == []


def test_profiler_hook_is_read_through_the_one_helper():
    """Outside obs/trace.py a READ of PROFILER_HOOK is a finding (the
    install in utils/profiling stores it, and stays green)."""
    read = ("from dllama_tpu.obs import trace\n\n"
            "def go():\n    return trace.PROFILER_HOOK is not None\n")
    diags = findings({"dllama_tpu/serve/fake.py": read})
    assert rules_of(diags) == ["catalog-span"] and diags[0].line == 4
    store = ("from dllama_tpu.obs import trace\n\n"
             "def go(h):\n    trace.PROFILER_HOOK = h\n")
    assert findings({"dllama_tpu/utils/fake.py": store}) == []


def test_catalog_fault_red():
    bad = ('from dllama_tpu.utils import faults\n'
           'faults.fire("definitely.not.a.point")\n')
    diags = findings({"dllama_tpu/serve/fake.py": bad})
    assert rules_of(diags) == ["catalog-fault"]
    assert diags[0].line == 2


# ------------------------------------------------------- transfer rule


def test_transfer_note_red_and_green():
    tmpl = '''
import numpy as np
from dllama_tpu.obs import compile as compile_obs


class E:
    def decode_consume(self, chunk):
        toks = np.asarray(chunk.toks)
        {note}
        return toks
'''
    red = {"dllama_tpu/engine/batch.py": tmpl.format(note="pass")}
    diags = findings(red)
    assert rules_of(diags) == ["transfer-note"]
    assert "decode_consume" in diags[0].message
    green = {"dllama_tpu/engine/batch.py": tmpl.format(
        note='compile_obs.note_transfer("d2h", "decode_tokens", 4)')}
    assert findings(green) == []


def test_transfer_note_is_site_level_not_function_level():
    """A note_transfer elsewhere in the function must NOT bless a distant
    unannotated transfer (the annotation windows to its site)."""
    far = '''
import numpy as np
from dllama_tpu.obs import compile as compile_obs


class E:
    def decode_consume(self, chunk):
        toks = np.asarray(chunk.toks)
        compile_obs.note_transfer("d2h", "decode_tokens", 4)
        a = 1
        b = 2
        c = 3
        d = 4
        e = 5
        stray = np.asarray(chunk.other)  # 6 statements from the note
        return toks, stray
'''
    diags = findings({"dllama_tpu/engine/batch.py": far})
    assert rules_of(diags) == ["transfer-note"]
    assert diags[0].line == 15  # the stray np.asarray line


def test_transfer_note_compound_stmt_does_not_self_annotate():
    """An `if` holding both a transfer and a note deep inside must not
    annotate its own out-of-window transfers from the outer level."""
    nested = '''
import numpy as np
from dllama_tpu.obs import compile as compile_obs


class E:
    def decode_consume(self, chunk):
        if chunk.spec:
            stray = np.asarray(chunk.other)
            a = 1
            b = 2
            c = 3
            d = 4
            e = 5
            compile_obs.note_transfer("d2h", "spec_counts", 4)
'''
    diags = findings({"dllama_tpu/engine/batch.py": nested})
    assert rules_of(diags) == ["transfer-note"]


def test_broken_source_does_not_crash_the_analyzer():
    # an unterminated string fails tokenize (comment scan skips) and
    # ast.parse; the analyzer must degrade to ONE parse-error diagnostic
    # per broken file — other files keep being analyzed
    from dllama_tpu.analysis.core import Source

    src = Source("dllama_tpu/engine/broken.py", "x = '''unterminated\n")
    assert src.suppressions == {}
    diags = run(Project({
        "dllama_tpu/engine/broken.py": "def broken(:\n",
        "dllama_tpu/serve/fake.py":
            'from dllama_tpu.utils import faults\nfaults.fire("nope")\n',
    }))
    by_rule = {d.rule: d for d in diags}
    assert by_rule["parse-error"].path == "dllama_tpu/engine/broken.py"
    assert by_rule["parse-error"].line == 1
    assert "catalog-fault" in by_rule  # the healthy file was still checked


def test_gate_routes_required_routes_are_pinned():
    """Deleting a shipped route from BOTH the tuple and the README must
    still fail (the old checks.sh pin, kept)."""
    ksel = 'PAGED_ROUTES = ("paged_kernel",)\n'  # paged_gather gone
    readme = ("## Paged KV cache\n\n| Route | When |\n|---|---|\n"
              "| `paged_kernel` | x |\n")
    diags = [d for d in run(Project({
        "dllama_tpu/engine/kernel_select.py": ksel, "README.md": readme}))
        if d.rule == "gate-routes"]
    assert any("paged_gather" in d.message for d in diags)


def test_transfer_note_only_guards_steady_fns():
    other = '''
import numpy as np


class E:
    def release(self, chunk):
        return np.asarray(chunk.toks)
'''
    assert findings({"dllama_tpu/engine/batch.py": other}) == []


# ----------------------------------------------------------- lock rules


LOCKS_TMPL = '''
from dllama_tpu.utils import locks


class A:
    def __init__(self):
        self._metrics = locks.make_lock("obs.metrics")
        self._pool = locks.make_rlock("engine.pool")
        self._sched = locks.make_lock("scheduler.metrics")

    def f(self):
        {body}
'''


def test_lock_order_red_inversion():
    body = ("with self._pool:\n"
            "            with self._sched:\n"
            "                pass")
    diags = findings({"dllama_tpu/serve/fake.py":
                      LOCKS_TMPL.format(body=body)})
    assert rules_of(diags) == ["lock-order"]
    assert "scheduler.metrics" in diags[0].message
    assert "engine.pool" in diags[0].message


def test_lock_leaf_red():
    body = ("with self._metrics:\n"
            "            with self._pool:\n"
            "                pass")
    diags = findings({"dllama_tpu/serve/fake.py":
                      LOCKS_TMPL.format(body=body)})
    assert rules_of(diags) == ["lock-leaf"]


def test_lock_order_green_ascending_and_reentrant():
    body = ("with self._sched:\n"
            "            with self._pool:\n"
            "                with self._pool:\n"
            "                    with self._metrics:\n"
            "                        pass")
    assert findings({"dllama_tpu/serve/fake.py":
                     LOCKS_TMPL.format(body=body)}) == []


def test_lock_order_crosses_function_calls():
    # f holds the metrics leaf and calls g, which takes the pool lock —
    # the edge is interprocedural, not lexical
    body = ("with self._metrics:\n"
            "            self.g()\n\n"
            "    def g(self):\n"
            "        with self._pool:\n"
            "            pass")
    diags = findings({"dllama_tpu/serve/fake.py":
                      LOCKS_TMPL.format(body=body)})
    assert rules_of(diags) == ["lock-leaf"]


def test_lock_unranked_red():
    bad = ('from dllama_tpu.utils import locks\n'
           '_X = locks.make_lock("not.ranked")\n')
    diags = run(Project({"dllama_tpu/serve/fake.py": bad}))
    assert "lock-unranked" in rules_of(diags)


# ------------------------------------------------------------ gate rules


def test_gate_routes_drift_red():
    ksel = 'PAGED_ROUTES = ("paged_kernel", "paged_gather")\n'
    readme = ("# x\n\n## Paged KV cache\n\n"
              "| Route | When |\n|---|---|\n| `paged_kernel` | x |\n"
              "| `paged_stale` | x |\n")
    diags = [d for d in run(Project({
        "dllama_tpu/engine/kernel_select.py": ksel,
        "README.md": readme,
    })) if d.rule == "gate-routes"]
    msgs = " | ".join(d.message for d in diags)
    assert "paged_gather" in msgs     # catalog-only: README lost it
    assert "paged_stale" in msgs      # readme-only: no such route


def test_gate_aot_red():
    diags = [d for d in run(Project({"experiments/aot_check.py":
                                     "OPS = ['paged_decode_attention']\n"}))
             if d.rule == "gate-aot"]
    msgs = " ".join(d.message for d in diags)
    assert "fused scatter" in msgs and "paged_decode_attention" not in msgs


def test_doc_rules_drift_red():
    readme = ("| Rule | Checks |\n|---|---|\n| `jit-scope` | x |\n"
              "| `no-such-rule` | x |\n")
    diags = [d for d in run(Project({"README.md": readme}))
             if d.rule == "doc-rules"]
    msgs = " ".join(d.message for d in diags)
    assert "no-such-rule" in msgs            # row naming no rule
    assert "`dev-state`" in msgs             # rule missing a row


# -------------------------------------------------- live repo: clean pass


def test_live_repo_zero_findings_fast_and_jaxfree():
    t0 = time.monotonic()
    project = Project.from_disk(REPO)
    diags = run(project)
    dt = time.monotonic() - t0
    assert diags == [], "\n".join(str(d) for d in diags)
    # the acceptance bound is <5s; leave slack for loaded CI boxes
    assert dt < 10.0, f"analyzer took {dt:.1f}s"
    # the analyzer itself never imports jax (conftest pre-imports it in
    # this process, so prove it on the module graph instead: nothing in
    # dllama_tpu.analysis imports jax)
    import dllama_tpu.analysis.rules_jit as rj

    for mod in list(sys.modules):
        if mod.startswith("dllama_tpu.analysis"):
            assert "jax" not in getattr(sys.modules[mod], "__dict__", {}), mod
    assert rj is not None


def test_cli_json_roundtrip():
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-m", "dllama_tpu.analysis", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["count"] == 0 and doc["findings"] == []
    assert doc["rules"] == len(RULE_CATALOG)
    assert doc["seconds"] < 5.0, doc  # the acceptance bound, end to end


def test_lock_graph_cli_is_acyclic_and_ascending():
    from dllama_tpu.analysis.rules_locks import build_graph
    from dllama_tpu.utils.locks import LOCK_RANKS

    edges, reentrant, _ca, _mg = build_graph(Project.from_disk(REPO))
    assert edges, "the live lock graph cannot be empty"
    for holder, acquired, rel, line in edges:
        if holder == acquired:
            assert holder in reentrant, (holder, rel, line)
            continue
        assert LOCK_RANKS[holder] < LOCK_RANKS[acquired], \
            f"descending edge {holder}->{acquired} at {rel}:{line}"


# ------------------------------------------------- runtime lock sanitizer


@pytest.fixture
def armed_locks():
    was = locks.armed()
    locks.configure(True)
    yield
    locks.configure(was)


def test_lock_audit_inversion_raises_with_both_sites(armed_locks):
    hi = locks.make_lock("obs.metrics")
    lo = locks.make_lock("scheduler.metrics")
    with hi:
        with pytest.raises(locks.LockOrderError) as ei:
            lo.acquire()
    msg = str(ei.value)
    assert "scheduler.metrics" in msg and "obs.metrics" in msg
    # BOTH hold sites named: the held lock's acquisition point (this
    # file) and the violating acquisition's
    assert msg.count("test_analysis.py") == 2
    assert "LEAF" in msg  # obs.metrics is a leaf lock; the message says so
    assert locks.held_names() == []  # nothing leaked


def test_lock_audit_equal_rank_distinct_objects_raise(armed_locks):
    a = locks.make_lock("obs.metrics")
    b = locks.make_lock("obs.metrics")
    with a:
        with pytest.raises(locks.LockOrderError):
            b.acquire()


def test_lock_audit_reentrant_rlock_ok(armed_locks):
    pool = locks.make_rlock("engine.pool")
    with pool:
        with pool:  # the radix tree / audit hook shape
            assert locks.held_names() == ["engine.pool", "engine.pool"]
    assert locks.held_names() == []


def test_lock_audit_ascending_ok_and_timeout_surface(armed_locks):
    lo = locks.make_lock("scheduler.metrics")
    hi = locks.make_lock("obs.metrics")
    with lo, hi:
        assert locks.held_names() == ["scheduler.metrics", "obs.metrics"]
    assert lo.acquire(timeout=0.5) is True  # Lock.acquire surface intact
    assert lo.locked()
    lo.release()
    # a second thread blocks on the held lock without tripping the audit
    # (per-thread stacks)
    lo.acquire()
    got = []
    t = threading.Thread(
        target=lambda: got.append(lo.acquire(blocking=False)))
    t.start()
    t.join()
    assert got == [False]
    lo.release()


def test_lock_audit_off_is_plain_threading_lock():
    was = locks.armed()
    locks.configure(False)
    try:
        lk = locks.make_lock("obs.metrics")
        rl = locks.make_rlock("engine.pool")
        assert type(lk) is type(threading.Lock())
        assert type(rl) is type(threading.RLock())
    finally:
        locks.configure(was)


def test_lock_audit_unknown_name_raises():
    with pytest.raises(ValueError):
        locks.make_lock("nope.nope")
    with pytest.raises(ValueError):
        locks.make_rlock("nope.nope")


def test_suite_runs_with_audit_armed():
    # tests/conftest.py arms DLLAMA_LOCK_AUDIT=1 before any dllama import;
    # every lock the stack created in this process is therefore audited
    assert os.environ.get("DLLAMA_LOCK_AUDIT") == "1"
    assert locks.armed()

"""How `correct` is decided: the serving BatchEngine against the plain
reference, on logits, outside any timed window.

For a seeded sample of prompts (lengths from the configuration's `check`
block, spanning one and several power-of-two prefill chunks):

1. prefill through the paged cache (`add_begin` / `add_step` to the end) and
   read `Admission.logits`: compared with the reference's row at that
   position by relative L2 error, |got - want|_2 / |want|_2; the limit is
   on the MEAN of these over the sample (steady from seed to seed; the
   largest of them is printed beside it);
2. commit every slot greedy and decode `decode_steps` tokens with all of
   them active (the batched decode program, the paged kernel over slots of
   different lengths). Tokens are never compared for equality: the reference
   is teacher-forced on the engine's own tokens and each emitted token has
   a deficit, (max(ref_row) - ref_row[token]) / std(ref_row): 0 where the
   engine chose the reference's argmax. A sound engine misses the argmax
   only on a near-tie; a wrong row, slot or position picks a token several
   sigma down. The limit is on the MEAN deficit over all judged tokens (one
   token 3 sigma down in 132 reads 0.023); the largest single deficit is an
   extreme value, swings from seed to seed, and is printed, not judged;
3. release each slot keeping its rows and prefill `tail_tokens` more on top
   of them (`start_pos` = rows kept): the logits of that chunk read the rows
   the decode program wrote, and are compared by relative L2 like (1).

What is imported from the program, and nothing else: `BatchEngine(...)`,
`add_begin`, `add_step`, `add_commit`, `decode`, `release`, `Admission.logits`
(and `attn_route` to name the route that ran). A refactor that changes these
brings a `benchmark` issue with it.
"""

from __future__ import annotations

import importlib
import json
import time

import numpy as np


def sample_prompts(seed: int, vocab_size: int, lengths: list, tail: int):
    """Seeded token ids: the prompts and the tail chunks fed after decode.
    Ids stay below the byte-level tokenizer's 256 specials."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    hi = vocab_size - 256
    prompts = [rng.integers(0, hi, int(n)).astype(np.int32) for n in lengths]
    tails = [rng.integers(0, hi, int(tail)).astype(np.int32) for _ in lengths]
    return prompts, tails


def engine_side(loaded, engine_kwargs: dict, prompts, tails, decode_steps: int,
                chunk: int = 4) -> dict:
    """Drive the program's BatchEngine; returns its logits rows and tokens."""
    import gc

    from dllama_tpu.engine.batch import BatchEngine

    be = BatchEngine(loaded.config, loaded.engine.params,
                     cache_dtype=loaded.engine.cache.k.dtype,
                     max_seq_len=loaded.engine.seq_len, **engine_kwargs)
    n = len(prompts)
    prefill_rows, firsts = [], []
    for slot, prompt in enumerate(prompts):
        adm = be.add_begin(slot, prompt.tolist())
        while not be.add_step(adm):
            pass
        prefill_rows.append(np.asarray(adm.logits, np.float32)[0])
        firsts.append(be.add_commit(adm, temperature=0.0))
    if decode_steps % chunk:
        raise ValueError(f"decode_steps must be a multiple of {chunk}")
    steps = [np.asarray(be.decode(chunk))[:, :n]
             for _ in range(decode_steps // chunk)]
    decoded = np.concatenate(steps, axis=0)  # [decode_steps, n]
    if decoded.shape[0] != decode_steps:
        raise RuntimeError("decode returned short chunks: a slot has no room")
    tail_rows, sequences = [], []
    for slot, (prompt, tail) in enumerate(zip(prompts, tails)):
        # rows written so far: the prompt, `first`, decoded[:-1]; the last
        # decoded token is still unfed and opens the tail chunk
        rows = len(prompt) + decode_steps
        be.release(slot, keep_rows=rows)
        fed = np.concatenate([decoded[-1:, slot], tail])
        adm = be.add_begin(slot, fed.tolist(), start_pos=rows)
        while not be.add_step(adm):
            pass
        tail_rows.append(np.asarray(adm.logits, np.float32)[0])
        sequences.append(np.concatenate(
            [prompt, [firsts[slot]], decoded[:, slot], tail]))
    for slot in range(n):
        be.release(slot)
    route = f"{be.backend}/{be.attn_route}"
    del be, adm
    gc.collect()
    return {"prefill_rows": prefill_rows, "tail_rows": tail_rows,
            "firsts": firsts, "decoded": decoded, "sequences": sequences,
            "route": route}


def rel_l2(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def deficit_sigma(ref_row: np.ndarray, token: int) -> float:
    row = np.asarray(ref_row, np.float64)
    return float((row.max() - row[int(token)]) / row.std())


def compare(eng: dict, prompts, model_path: str, reference: str,
            decode_steps: int) -> dict:
    """Teacher-force the reference on the engine's own tokens and reduce to
    the numbers the limits are set on."""
    ref = importlib.import_module(reference)
    positions = []
    for prompt, seq in zip(prompts, eng["sequences"]):
        L = len(prompt)
        # L-1 predicts `first`; L+j predicts decoded[j]; the last row of the
        # sequence is the tail chunk's logits
        positions.append(list(range(L - 1, L + decode_steps)) + [len(seq) - 1])
    rows = ref.logits_at(model_path, eng["sequences"], positions)
    per_prompt = []
    for i, prompt in enumerate(prompts):
        r = rows[i]
        emitted = [eng["firsts"][i]] + eng["decoded"][:, i].tolist()
        deficits = [deficit_sigma(r[k], t) for k, t in enumerate(emitted)]
        misses = [{"step": k, "token": int(t), "reference_argmax": int(r[k].argmax()),
                   "deficit_sigma": d,
                   "row_max_sigma": float((r[k].max() - r[k].mean()) / r[k].std())}
                  for k, (t, d) in enumerate(zip(emitted, deficits)) if d > 0]
        finite = bool(np.isfinite(eng["prefill_rows"][i]).all()
                      and np.isfinite(eng["tail_rows"][i]).all())
        per_prompt.append({
            "prompt_tokens": len(prompt),
            "prefill_rel_l2": rel_l2(eng["prefill_rows"][i], r[0]),
            "tail_rel_l2": rel_l2(eng["tail_rows"][i], r[-1]),
            "deficit_sigma_max": max(deficits),
            "deficit_sigma_mean": float(np.mean(deficits)),
            "argmax_misses": len(misses), "misses": misses[:8],
            "tokens_judged": len(deficits), "finite": finite})
    errs = [p[k] for p in per_prompt for k in ("prefill_rel_l2", "tail_rel_l2")]
    return {"per_prompt": per_prompt,
            # the number the limit is set on: the mean over the sample's rows
            # is steady from seed to seed where the largest of them is not
            "rel_l2_mean": float(np.mean(errs)), "rel_l2_max": max(errs),
            "deficit_sigma_mean": float(
                sum(p["deficit_sigma_mean"] * p["tokens_judged"] for p in per_prompt)
                / sum(p["tokens_judged"] for p in per_prompt)),
            "deficit_sigma_max": max(p["deficit_sigma_max"] for p in per_prompt),
            "finite": all(p["finite"] for p in per_prompt)}


def run(loaded, config: dict, model_path: str, seed: int) -> dict:
    """The whole check for one loaded model -> numbers, limits, verdict."""
    chk, tol = config["check"], config["tolerances"]
    t0 = time.monotonic()
    prompts, tails = sample_prompts(seed, int(config["vocab_size"]),
                                    chk["prompt_lengths"], chk["tail_tokens"])
    eng = engine_side(loaded, config["engine"], prompts, tails,
                      int(chk["decode_steps"]))
    t1 = time.monotonic()
    out = compare(eng, prompts, model_path, config["reference"],
                  int(chk["decode_steps"]))
    out.update(route=eng["route"], limits=tol,
               engine_seconds=round(t1 - t0, 2),
               reference_seconds=round(time.monotonic() - t1, 2))
    out["correct"] = bool(out["finite"]
                          and out["rel_l2_mean"] <= tol["rel_l2_mean"]
                          and out["deficit_sigma_mean"] <= tol["deficit_sigma_mean"])
    return out


def say(record: dict) -> None:
    print(json.dumps(record), flush=True)

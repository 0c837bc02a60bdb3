"""On-device token sampling: greedy / temperature / top-p nucleus.

Semantics follow the reference Sampler (tokenizer.cpp:332-453): temp==0 is
argmax; otherwise softmax(logits/temp) then plain multinomial, or top-p
truncation when 0 < topp < 1. RNG is jax.random (threefry) seeded from the
user seed rather than the reference's xorshift — sequences are seedable and
reproducible, but not bit-identical to the C++ RNG.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dllama_tpu.obs import compile as compile_obs


# top-p candidate-set width: nucleus sampling restricts to the approx-top-K
# logits instead of full-vocab sort (see sample_logits). At real-vocab sizes
# and topp <= 0.99 the nucleus essentially never exceeds a few dozen tokens.
# None = exact mode (ADVICE r3): full-vocab sort like the reference's nucleus
# (tokenizer.cpp:389-395) — no approx recall loss, no wide-nucleus fallback,
# at the cost of a 128k-row sort per decode step. CLI: --exact-topp.
NUCLEUS_K: int | None = 256


def apply_penalties(logits: jax.Array, counts, presence, frequency) -> jax.Array:
    """OpenAI-style repetition penalties on raw logits:
    ``mu[j] = logit[j] - presence * 1[counts[j] > 0] - frequency * counts[j]``.

    counts: [B, V] occurrence counts of each token SAMPLED in this
    completion so far (OpenAI's published formula: the prompt — and any
    KV-cached earlier turns — carries no penalty, so output never depends
    on prefix-cache state). presence/frequency: scalars or [B] vectors —
    traced like temperature/topp so per-request values never recompile.
    The reference has no analog (its sampler is temp/top-p only,
    tokenizer.cpp:352-416); OpenAI clients send these fields routinely."""
    presence = jnp.asarray(presence, jnp.float32)
    frequency = jnp.asarray(frequency, jnp.float32)
    if presence.ndim == 1:
        presence = presence[:, None]
    if frequency.ndim == 1:
        frequency = frequency[:, None]
    c = counts.astype(jnp.float32)
    return logits - presence * (c > 0) - frequency * c


def _draw(key: jax.Array, logits: jax.Array) -> jax.Array:
    """One categorical draw a row of `logits` [B, N]: a single key [2] draws
    the whole batch at once; per-row keys [B, 2] draw each row from its own
    key over its own [1, N] row (what a per-row map of the one-key call
    drew), so a row's token never depends on its batch-mates."""
    if key.ndim == 1:
        return jax.random.categorical(key, logits, axis=-1)
    return jax.vmap(
        lambda k, row: jax.random.categorical(k, row[None], axis=-1)[0]
    )(key, logits)


def sample_logits(logits: jax.Array, key: jax.Array, temperature, topp,
                  active=None) -> jax.Array:
    """logits f32 [B, V] -> tokens i32 [B]. `key` is one key [2] for the
    batch or per-row keys [B, 2] (the continuous-batching engine's per-slot
    streams); temperature / topp are scalars or [B] vectors.

    Both stay *traced* values, so the fused decode loop and the API server
    never recompile when a request changes sampling params: ONE program
    holds all three bodies below, and one `lax.switch` on the device runs,
    once a call, the shortest one the batch's own vectors ask for. Its
    index is a scalar of the whole batch and stands outside any per-row
    map (a conditional under `vmap` with a batched predicate lowers to a
    select that runs every branch); only the categorical draws map over
    rows, and only when the keys are per-row:

    0. no row samples (every temperature 0): the argmax alone;
    1. some row samples, none with 0 < topp < 1: also the full-vocabulary
       temperature draw (categorical = gumbel-argmax, no sort);
    2. some row wants a nucleus: also the candidates' top-k, the
       full-vocabulary logsumexp and the candidates' draw.

    The bodies are prefixes of each other and every row's token is the same
    whichever runs: a row that needs less than its batch-mates takes its
    answer from the longer body exactly as the one straight-line body did
    before (which ran all of 2 every call and threw it away with a `where`).
    `active` ([B] bool, optional) keeps rows out of the choice whose token
    the caller discards (a released slot keeps its stale temperature); what
    such a row returns is unspecified.

    Top-p is computed over the ``approx_max_k`` top-NUCLEUS_K candidates (the
    TPU-native top-k; exact on CPU) with probabilities normalized against the
    FULL vocab, instead of the reference's full-vocab sort
    (tokenizer.cpp:389-395): an XLA sort of a 128k-vocab row per decode step
    costs more than a whole transformer layer, and a nucleus wider than 256
    tokens requires a distribution so flat that truncating it is noise. The
    kept-set rule within the candidates is the reference's break-after-include.
    If the candidates cover less than topp of the full-vocab mass (a nucleus
    wider than K — very high temperature on a large vocab), the row falls back
    to full-vocab temperature sampling rather than silently behaving as
    top-k=K. Callers that need the reference's exact semantics (no recall
    loss, no fallback) set ``NUCLEUS_K = None`` for a true full-vocab sort.
    Pure temperature sampling (topp <= 0 or >= 1) stays full-vocab."""
    logits = logits.astype(jnp.float32)
    b = logits.shape[0]
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    topp = jnp.broadcast_to(jnp.asarray(topp, jnp.float32), (b,))
    t_is_zero = temperature == 0.0
    wants_topp = (topp > 0.0) & (topp < 1.0)
    # the rows whose sampled token the caller keeps
    samples = ~t_is_zero if active is None else ~t_is_zero & active
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draws():
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        keys = (jax.random.split(key) if key.ndim == 1
                else jnp.swapaxes(jax.vmap(jax.random.split)(key), 0, 1))
        # pure temperature sampling: full vocab, no truncation
        tok_temp = _draw(keys[1], scaled).astype(jnp.int32)
        return scaled, keys[0], tok_temp

    def temperature_body():
        _, _, tok_temp = draws()
        return jnp.where(t_is_zero, greedy, tok_temp)

    def nucleus_body():
        scaled, key_p, tok_temp = draws()
        # top-p among the top-K candidates, full-vocab-normalized
        if NUCLEUS_K is None:  # exact escape hatch: full-vocab descending sort
            vals, idx = jax.lax.top_k(scaled, scaled.shape[-1])
        else:
            k = min(NUCLEUS_K, logits.shape[-1])
            vals, idx = jax.lax.approx_max_k(scaled, k, recall_target=0.99,
                                             aggregate_to_topk=True)  # sorted desc
        lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
        pk = jnp.exp(vals - lse)  # true softmax probs of the candidates
        cum = jnp.cumsum(pk, axis=-1)
        # keep while cumulative mass *before* the token is < topp (include the
        # token that crosses topp — the reference's break-after-include)
        keep = (cum - pk) < topp[:, None]
        masked = jnp.where(keep, vals, -jnp.inf)
        choice = _draw(key_p, masked)
        tok_topp = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
        # nucleus wider than K: candidates don't reach topp mass — fall back to
        # untruncated temperature sampling for that row (see docstring)
        covered = cum[:, -1] >= topp
        sampled = jnp.where(wants_topp & covered, tok_topp, tok_temp)
        return jnp.where(t_is_zero, greedy, sampled)

    # 0 / 1 / 2: a nucleus row is a sampled row, so the sum names the body.
    # ONE conditional of three branches, not one inside another: the nested
    # form lowered 0.4 s slower a program on the chip machine (PERF.md, PR 52)
    body = samples.any().astype(jnp.int32) + (samples & wants_topp).any()
    return jax.lax.switch(body, (lambda: greedy, temperature_body, nucleus_body))


@jax.jit
def sample(logits: jax.Array, key: jax.Array, temperature=0.8, topp=0.9) -> jax.Array:
    return sample_logits(logits, key, temperature, topp)


class Sampler:
    """Stateful host-side wrapper (the analog of the reference Sampler object,
    plus the OpenAI repetition-penalty fields it lacks)."""

    def __init__(self, temperature: float = 0.8, topp: float = 0.9, seed: int = 0,
                 presence: float = 0.0, frequency: float = 0.0):
        self.temperature = float(temperature)
        self.topp = float(topp)
        self.presence = float(presence)
        self.frequency = float(frequency)
        self.key = jax.random.PRNGKey(seed)

    @property
    def has_penalties(self) -> bool:
        return self.presence != 0.0 or self.frequency != 0.0

    def set_seed(self, seed: int) -> None:
        self.key = jax.random.PRNGKey(seed)

    def set_temp(self, temperature: float) -> None:
        self.temperature = float(temperature)

    def __call__(self, logits: jax.Array) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        # ledger-scoped like every jit dispatch (analysis rule jit-scope):
        # the first-token sample's compile is attributed, not "untracked"
        with compile_obs.LEDGER.scope(
                "single_sample", f"b{logits.shape[0]}",
                sig=lambda: compile_obs.sig_of(logits)):
            return sample(logits, sub, self.temperature, self.topp)

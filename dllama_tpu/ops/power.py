"""Power retention of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239: power attention with a gate): linear
attention whose kernel is (q . k)^2, served in its recurrent form — the
step, its slice form, and the expansion phi both stand on.

Per kv head, with D the head size and phi(u) the symmetric square of u (the
D (D + 1) / 2 products u_i u_j, i <= j, the off-diagonal ones times sqrt 2,
so that phi(q) . phi(k) = (q . k)^2), all float32:

    S_t = exp(gamma_t) S_{t-1} + [v_t; 1] phi(k_t)^T     gamma_t <= 0, one a
                                                         kv head and row
    [y~_t; n_t] = S_t phi(q_t)                           a query head of the
                                                         kv head's group
    y_t = y~_t / (n_t + D eps)

S is [D + 1, R]: a row a value dim and ONE MORE for the normaliser (the
running sum of phi(k), which is what a value that is always 1 accumulates),
over the R = D (D + 1) / 2 expanded key dims. It is HELD as [rows, lanes]
with D + 1 rounded up to whole sublane tiles of 8 and R to whole lane tiles
of 128 (`state_dims`; 136 x 8,320 at D = 128): the padding stays zero (its
value is 0, its phi is 0), costs the device nothing it does not pad anyway,
and keeps the array in the row-major layout the kernel's blocks walk (with
129 rows the device holds the array's dims in another order, and every
launch copies the whole state in and out). The attention form this equals
weighs row s by exp(sum of gamma over (s, t]) (q_t . k_s / sqrt D)^2 and
divides by the weights' sum + eps; the 1 / D of the squared scale is on both
sums and cancels, which leaves it on eps alone. q and k come normed over the
head and rotated; nothing here knows of either.

The expanded dims are ordered BY DIAGONAL: entry g D + i is the pair
(i, (i + g) mod D), for g = 0 .. D/2 - 1 every i and for g = D/2 the first
D/2 (each unordered pair once). A diagonal of phi is then u times u rotated
by g lanes, which is what the kernel builds in VMEM
(ops/pallas/retention_step.py) and why S holds the expanded dims on lanes.

A slice of T rows of one sequence is matrix products, not a scan of the
step: inside the slice the masked (Q K^T)^2 with the decay between its rows,
across slices the incoming state (`retention_slice`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6  # on the normaliser, in the attention form's units
_HI = jax.lax.Precision.HIGHEST  # the state's products: float32 operands
# must not round to bfloat16 inside the MXU


def state_dims(d: int) -> tuple[int, int]:
    """(rows, lanes) a kv head's state is held as at head size `d`."""
    return -(-(d + 1) // 8) * 8, -(-(d * (d + 1) // 2) // 128) * 128


def phi(u, lanes: int):
    """The symmetric square over the last axis, f32 [..., D] -> [..., lanes]:
    the R products in the diagonal order of the module docstring (lane
    rotations and products, no gather), then zeros."""
    d = u.shape[-1]
    if d % 2:
        raise ValueError(f"the diagonal order needs an even head size, not {d}")
    uf = u.astype(jnp.float32) * 2.0 ** 0.25  # sqrt 2 on every product ...
    twice = jnp.concatenate([uf, uf], axis=-1)
    diagonals = [uf * uf * 0.5 ** 0.5]  # ... and off the squares again
    diagonals += [uf * twice[..., g:g + d] for g in range(1, d // 2)]
    diagonals.append((uf * twice[..., d // 2:d // 2 + d])[..., :d // 2])
    r = d * (d + 1) // 2
    diagonals.append(jnp.zeros((*uf.shape[:-1], lanes - r), jnp.float32))
    return jnp.concatenate(diagonals, axis=-1)


def with_one(v, rows: int):
    """[v; 1; 0 ...] over the last axis in float32, `rows` long: the value
    the state sums, beside it the 1 that sums the normaliser."""
    vf = v.astype(jnp.float32)
    one = jnp.zeros((*vf.shape[:-1], rows - vf.shape[-1]), jnp.float32)
    return jnp.concatenate([vf, one.at[..., 0].set(1.0)], axis=-1)


def normalise(y, d: int):
    """[y~; n; ...] -> y~ / (n + D eps) over the last axis (module docstring)."""
    return y[..., :d] / (y[..., d:d + 1] + d * EPS)


def retention_step_ref(s, q, k, v, gamma):
    """One step, plain jnp. s f32 [B, G, rows, lanes]; q [B, H, D] (query
    heads g J .. g J + J - 1 read kv head g); k, v [B, G, D]; gamma f32
    [B, G] -> ([y~; n; ...] f32 [B, H, rows], S_new)."""
    b, h, _ = q.shape
    g, (rows, lanes) = k.shape[1], s.shape[-2:]
    s = (jnp.exp(gamma)[..., None, None] * s
         + with_one(v, rows)[..., :, None] * phi(k, lanes)[..., None, :])
    y = jnp.einsum("bgvr,bgjr->bgjv", s, phi(q, lanes).reshape(b, g, h // g, -1),
                   precision=_HI)
    return y.reshape(b, h, rows), s


def retention_slice(s, q, k, v, gamma):
    """T rows of one sequence from the incoming state, as matrix products.
    s f32 [B, G, rows, lanes]; q [B, T, H, D]; k, v [B, T, G, D]; gamma f32
    [B, T, G] -> ([y~; n; ...] f32 [B, T, H, rows], S_out)."""
    b, t, h, d = q.shape
    g, (rows, lanes) = k.shape[2], s.shape[-2:]
    qf = q.astype(jnp.float32).reshape(b, t, g, h // g, d)
    kf, va = k.astype(jnp.float32), with_one(v, rows)
    run = jnp.cumsum(gamma, axis=1)  # [B, T, G]: log decay since the slice began
    # inside the slice: row t weighs row s <= t by its decay since s
    since = run[:, :, None] - run[:, None]  # [B, T, S, G], <= 0 where s <= t
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    w = jnp.where(causal, jnp.exp(jnp.where(causal, since, 0.0)), 0.0)
    a = jnp.einsum("btgjd,bsgd->btsgj", qf, kf, precision=_HI) ** 2
    y = jnp.einsum("btsgj,bsgv->btgjv", a * w[..., None], va, precision=_HI)
    # across slices: what the incoming state returns, decayed to each row
    y_in = jnp.einsum("btgjr,bgvr->btgjv", phi(qf, lanes), s, precision=_HI)
    y = y + jnp.exp(run)[..., None, None] * y_in
    # the state at the slice's end: each row's write decayed from its row on
    left = jnp.exp(run[:, -1:] - run)  # [B, T, G]
    s = (jnp.exp(run[:, -1])[..., None, None] * s
         + jnp.einsum("btgv,btgr->bgvr", va * left[..., None], phi(kf, lanes),
                      precision=_HI))
    return y.reshape(b, t, h, rows), s

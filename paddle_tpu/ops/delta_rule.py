"""The gated delta rule with per-channel decay (Kimi Delta Attention,
arXiv:2510.26692) in plain ``jax.numpy`` / ``lax``: the recurrent state of a
linear-attention layer, as a one-token step for decode and in chunkwise form
for prefill.

Per head the state is a matrix ``S [dk, dv]`` (float32), ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``alpha_t`` in (0, 1)^dk given as its logarithm (``log_alpha <= 0``) and
``beta_t`` a scalar (up to 2 where negative eigenvalues are allowed). A decay
that is the same in every channel (the gated delta net, arXiv:2412.06464: one
scalar a head) is given with a last axis of 1 and broadcast; value heads that
share a key head (``q`` and ``k`` with fewer heads than ``v``: head ``h`` of
``v`` takes head ``h // group`` of ``q`` and ``k``) are served by repeating
the key heads, not by a second recurrence. Written
with ``u_t = beta_t (v_t - S_{t-1}^T (alpha_t * k_t))`` the update is
``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``: a decay of the rows and a rank-one
write.

A position with ``log_alpha = 0`` and ``beta = 0`` leaves the state bitwise as
it was (``S * 1 + k * 0``): that is how a caller masks the padding of a final
chunk and the slots of a decode batch that are not active.

The chunkwise form (inner chunk ``C``, 64 by default) never divides by a
cumulative decay: the products of ``k_t``, ``q_t`` with ``k_s`` are taken with
``exp(g_t - g_s)`` (``g`` the running sum of ``log_alpha`` inside the chunk),
which is at most 1 for ``s <= t``, so a strong decay underflows to 0 instead
of overflowing. The ``u`` of a chunk solve a unit lower-triangular system
``(I + m) u = ...``; it is solved once for all chunks together (the WY form:
``u = U - W S_0`` with ``W``, ``U`` free of the state) by its inverse in
float32, and only the three small products with the carried state run chunk
after chunk. The inverse is built by halving (``_unit_lower_inverse``): the
two diagonal blocks' inverses and one product pair for the block below them,
all blocks of a size in one batch, so ``log2(C)`` dependent steps where row
substitution takes ``C``. Not by the doubling product
``(I - m)(I + m^2)(I + m^4)...``, exact on paper because ``m`` is nilpotent:
with keys that share a direction the powers of ``m`` grow like binomial
coefficients before they cancel, and in float32 the product is off by
factors of 1e9 and more at ``C`` = 64 where halving and row substitution both
agree with a float64 solve to 1e-6 (``tests/test_solar_open2.py``).
Everything here is float32, on the VPU or at ``"highest"`` matmul precision:
on the TPU a float32 matmul otherwise runs in one bfloat16 pass, and the state
is what carries a sequence's history.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["delta_rule_step", "delta_rule_chunked"]

_HI = jax.lax.Precision.HIGHEST
_MXU_FROM = 16    # blocks of so many rows and more are multiplied by matmuls (measured on the v5e: PERF.md, PR 39)


def _per_value_head(a, heads: int, axis: int):
    """``a`` with its key heads along ``axis`` repeated, each for the group of
    value heads it serves; ``a`` itself where there are as many already."""
    return a if a.shape[axis] == heads else jnp.repeat(a, heads // a.shape[axis], axis=axis)


def delta_rule_step(q, k, v, log_alpha, beta, state):
    """One token for every row of a batch. ``q``, ``k`` ``[..., dk]``,
    ``log_alpha`` ``[..., dk]`` or ``[..., 1]``, ``v`` ``[..., dv]``, ``beta``
    ``[...]``, ``state`` ``[..., dk, dv]``, all float32; with a head axis
    before the last, ``q`` and ``k`` may have fewer heads than ``v``. Returns
    ``(o [..., dv], state)``."""
    if q.ndim > 1:
        q, k = (_per_value_head(a, v.shape[-2], -2) for a in (q, k))
    state = state * jnp.exp(log_alpha)[..., None]
    seen = jnp.sum(state * k[..., None], axis=-2)                 # S^T k, on the VPU: exact float32
    u = beta[..., None] * (v - seen)
    state = state + k[..., None] * u[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


_mxu_matmul = functools.partial(jnp.matmul, precision=_HI)


def _vpu_matmul(a, b):
    """``a @ b`` as a broadcast product and a sum: exact float32, and for blocks
    under ``_MXU_FROM`` rows faster than a matmul that fills a corner of a tile."""
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)


def _unit_lower_inverse(m):
    """``(I + m)^-1`` for strictly lower-triangular ``m`` ``[..., C, C]`` by
    halving: ``[[A, 0], [L, B]]^-1 = [[A^-1, 0], [-B^-1 L A^-1, B^-1]]``, the
    two diagonal blocks inverted as one batch (one after the other where ``C``
    is odd), down to 1 x 1. ``log2(C)`` levels of two products each; a row of
    zeros in ``m`` gives that row of the identity exactly."""
    c = m.shape[-1]
    if c == 1:
        return jnp.ones_like(m)
    h = c // 2
    if c % 2:
        top, bot = _unit_lower_inverse(m[..., :h, :h]), _unit_lower_inverse(m[..., h:, h:])
    else:
        both = _unit_lower_inverse(jnp.stack([m[..., :h, :h], m[..., h:, h:]], axis=-3))
        top, bot = both[..., 0, :, :], both[..., 1, :, :]
    matmul = _vpu_matmul if h < _MXU_FROM else _mxu_matmul
    low = -matmul(bot, matmul(m[..., h:, :h], top))
    upper = jnp.zeros(m.shape[:-2] + (h, c - h), m.dtype)
    return jnp.concatenate([jnp.concatenate([top, upper], axis=-1), jnp.concatenate([low, bot], axis=-1)], axis=-2)


def delta_rule_chunked(q, k, v, log_alpha, beta, state, *, chunk: int = 64):
    """A run of ``T`` tokens of one sequence, every head at once. ``q``,
    ``k`` ``[H, T, dk]`` (or fewer, shared, heads), ``log_alpha`` ``[H, T, dk]``
    or ``[H, T, 1]``, ``v`` ``[H, T, dv]``, ``beta`` ``[H, T]``, ``state``
    ``[H, dk, dv]``, all float32; ``T`` a multiple of ``chunk``. Returns
    ``(o [H, T, dv], state)``: the same numbers as ``T`` calls of
    :func:`delta_rule_step`, up to float32 rounding."""
    q, k = (_per_value_head(a, v.shape[0], 0) for a in (q, k))
    H, T, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    if T % c:
        raise ValueError(f"{T} tokens are no multiple of the inner chunk {c}")
    n = T // c
    q, k, v, log_alpha = (a.reshape(H, n, c, a.shape[-1]) for a in (q, k, v, log_alpha))
    beta = beta.reshape(H, n, c)

    scalar_decay = log_alpha.shape[-1] == 1 and dk > 1
    # [H, n, C, dk], <= 0; one decay a head is summed with the rows as the minor axis: over [..., C, 1] the chip may pad a row to a tile
    g = jnp.cumsum(log_alpha[..., 0], axis=2)[..., None] if scalar_decay else jnp.cumsum(log_alpha, axis=2)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # exp(g_t - g_s) for s <= t, 0 above the diagonal; never over 1
    rel = jnp.where((s_idx <= t_idx)[None, None, :, :, None], g[:, :, :, None, :] - g[:, :, None, :, :], -jnp.inf)
    if scalar_decay:
        # one decay a head: it leaves the sum over the channels, which is then a matmul ([C, C, dk] is never made)
        k_t, pair = jnp.swapaxes(k, -1, -2), jnp.exp(rel[..., 0])
        a_kk = jnp.matmul(k, k_t, precision=_HI) * pair                               # [H, n, C, C]: G_t/G_s k_t . k_s
        a_qk = jnp.matmul(q, k_t, precision=_HI) * pair
    else:
        kd = k[:, :, None, :, :] * jnp.exp(rel)                                     # [H, n, C(t), C(s), dk]
        a_kk = jnp.sum(k[:, :, :, None, :] * kd, axis=-1)                           # [H, n, C, C]: k_t . Diag(G_t/G_s) k_s
        a_qk = jnp.sum(q[:, :, :, None, :] * kd, axis=-1)
    strict = (s_idx < t_idx)[None, None]
    m = jnp.where(strict, beta[..., None] * a_kk, 0.0)
    decay = jnp.exp(g)
    inv = _unit_lower_inverse(m)                                                    # inv @ r is the x with (I + m) x = r
    w, u0 = _mxu_matmul(inv, beta[..., None] * k * decay), _mxu_matmul(inv, beta[..., None] * v)   # u = u0 - w S_0
    g_end = g[:, :, -1:, :]                                                         # [H, n, 1, dk]
    k_tail = k * jnp.exp(g_end - g)                                                 # Diag(G_C/G_s) k_s
    q_in = q * decay

    def one_chunk(s0, xs):
        w_c, u0_c, a_c, q_c, kt_c, ge_c = xs
        u = u0_c - jnp.matmul(w_c, s0, precision=_HI)                               # [H, C, dv]
        o = jnp.matmul(q_c, s0, precision=_HI) + jnp.matmul(a_c, u, precision=_HI)
        s1 = jnp.exp(ge_c)[:, 0, :, None] * s0 + jnp.matmul(jnp.swapaxes(kt_c, -1, -2), u, precision=_HI)
        return s1, o

    per_chunk = tuple(jnp.moveaxis(a, 1, 0) for a in (w, u0, a_qk, q_in, k_tail, g_end))
    state, o = jax.lax.scan(one_chunk, state, per_chunk)
    return jnp.moveaxis(o, 0, 1).reshape(H, T, dv), state

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
of overflowing. The ``u`` of a chunk solve a unit lower-triangular system; it
is solved once for all chunks together (the WY form: ``u = U - W S_0`` with
``W``, ``U`` free of the state) by forward substitution in float32, and only
the three small products with the carried state run chunk after chunk.
Everything here is float32 at ``"highest"`` matmul precision: on the TPU a
float32 product otherwise runs in one bfloat16 pass, and the state is what
carries a sequence's history.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["delta_rule_step", "delta_rule_chunked"]

_HI = jax.lax.Precision.HIGHEST


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


def _solve_unit_lower(m, rhs):
    """``x`` with ``(I + m) x = rhs`` for strictly lower-triangular ``m``
    ``[..., C, C]`` and ``rhs`` ``[..., C, n]``, by forward substitution: row
    ``t`` needs rows ``< t`` only, and rows not yet written are zero."""
    c = m.shape[-2]

    def row(t, x):
        m_t = jax.lax.dynamic_slice_in_dim(m, t, 1, axis=-2)                        # [..., 1, C]
        r_t = jax.lax.dynamic_slice_in_dim(rhs, t, 1, axis=-2)                      # [..., 1, n]
        x_t = r_t - jnp.matmul(m_t, x, precision=_HI)
        return jax.lax.dynamic_update_slice_in_dim(x, x_t, t, axis=-2)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(rhs))


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

    g = jnp.cumsum(log_alpha, axis=2)                                               # [H, n, C, dk], <= 0
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # exp(g_t - g_s) for s <= t, 0 above the diagonal; never over 1
    rel = jnp.where((s_idx <= t_idx)[None, None, :, :, None], g[:, :, :, None, :] - g[:, :, None, :, :], -jnp.inf)
    if log_alpha.shape[-1] == 1 and dk > 1:
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
    wu = _solve_unit_lower(m, jnp.concatenate([beta[..., None] * k * decay, beta[..., None] * v], axis=-1))
    w, u0 = wu[..., :dk], wu[..., dk:]                                              # u = u0 - w S_0
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

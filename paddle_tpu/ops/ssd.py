"""The Mamba-2 state-space mixer's recurrence (the state-space duality,
arXiv:2405.21060) in plain ``jax.numpy`` / ``lax``: a one-token step for decode,
the chunkwise form for prefill, and the depthwise causal convolution with bias
that feeds both.

Per head ``h`` the state is a matrix ``S [P, N]`` (float32; ``P`` channels, ``N``
state), ``S_0 = 0``, with one scalar decay a head and ``B_t``, ``C_t`` ``[N]``
shared by every head of a group:

    a_t  = exp(dt_t * A)                    A < 0, dt_t > 0 (after its softplus)
    S_t  = a_t S_{t-1} + dt_t x_t (x) B_t
    y_t  = S_t C_t + D x_t

No correction and no solve (the delta rule's ``ops/delta_rule.py`` has both):
the chunkwise form is masked matmuls and nothing else. With ``g`` the running
sum of ``dt * A`` inside an inner chunk of ``Q`` rows,

    Y   = ((C B^T) o L) (dt * X) + exp(g) * (C S_0),   L[t, s] = exp(g_t - g_s) for s <= t, else 0
    S_Q = exp(g_Q) S_0 + sum_s exp(g_Q - g_s) dt_s x_s (x) B_s

and never a division by a cumulative decay: every exponent is a difference
``g_t - g_s <= 0`` for ``s <= t``, so a strong decay underflows to 0 instead of
overflowing. ``C B^T`` is one product a group, shared by its heads.

A position with ``dt = 0`` decays nothing (``exp(0) = 1``) and writes nothing:
it leaves the state bitwise as it was. That is how the padding past a final
chunk's last row (``n_valid``) and the slots of a decode batch that are not
``active`` are masked; what such a position reads out is finite and is the
caller's to drop.

Everything here is float32 at ``"highest"`` matmul precision: on the TPU a
float32 product otherwise runs in one bfloat16 pass, and the state is what
carries a sequence's history. The recurrence's products are a fiftieth of the
mixer's projections (4.2 against 204.5 MFLOP a token at 128 heads of 64 x 128),
so the six passes cost little.

``ssd_step`` and ``ssd_chunked`` go through the kernel registry
(``kernels.ssd_step.picked`` / ``.fallback``, ``kernels.ssd_chunked.*``): one
implementation each today, the ``lax`` forms below, on every device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import registry

__all__ = ["causal_conv", "ssd_step", "ssd_chunked", "ssd_step_lax", "ssd_chunked_lax"]

_HI = jax.lax.Precision.HIGHEST


def causal_conv(window, w, b):
    """``silu(b + sum_j w[j] * window[j : j + T])``: the depthwise causal
    convolution over time with bias, then SiLU. ``window [..., K - 1 + T, C]``
    is the ``K - 1`` inputs before the run (the tail a slot carries; zeros at a
    sequence's start) and then the run; ``w [K, C]`` with tap ``K - 1`` on the
    current token; ``b [C]``. Float32 ``[..., T, C]``."""
    K = w.shape[0]
    T = window.shape[-2] - (K - 1)
    x, w32 = window.astype(jnp.float32), w.astype(jnp.float32)
    return jax.nn.silu(b.astype(jnp.float32) + sum(x[..., j:j + T, :] * w32[j] for j in range(K)))


def _by_group(a, groups: int, axis: int):
    """``a`` with its head axis split ``[groups, heads a group]``."""
    return a.reshape(a.shape[:axis] + (groups, a.shape[axis] // groups) + a.shape[axis + 1:])


def ssd_step_lax(x, dt, A, B, C, D, state, active=None):
    """One token for every row of a batch: ``x [b, H, P]``, ``dt [b, H]``
    (positive), ``A``, ``D`` ``[H]``, ``B``, ``C`` ``[b, G, N]`` (head ``h``
    takes group ``h // (H / G)``), ``state [b, H, P, N]``, all float32;
    ``active [b]`` (None: every row) masks a row's decay and write. Returns
    ``(y [b, H, P], state)``."""
    G = B.shape[-2]
    if active is not None:
        dt = jnp.where(active[:, None], dt, 0.0)
    s = _by_group(state, G, 1)                                                     # [b, G, Hg, P, N]
    decay, write = _by_group(jnp.exp(dt * A), G, 1), _by_group(x * dt[..., None], G, 1)
    s = s * decay[..., None, None] + write[..., None] * B[:, :, None, None, :]      # on the VPU: exact float32
    y = jnp.sum(s * C[:, :, None, None, :], axis=-1).reshape(x.shape)
    return y + D[:, None] * x, s.reshape(state.shape)


def ssd_chunked_lax(x, dt, A, B, C, D, state, n_valid=None, *, chunk: int = 256):
    """A run of ``T`` tokens of one sequence, every head at once: ``x [T, H,
    P]``, ``dt [T, H]``, ``A``, ``D`` ``[H]``, ``B``, ``C`` ``[T, G, N]``,
    ``state [H, P, N]`` (the ``S_0`` handed in), all float32; rows at
    ``n_valid`` and after (None: no such rows) are padding. ``T`` need be no
    multiple of ``chunk``: the run is padded with masked rows. Returns ``(y
    [T, H, P], state)``: the same numbers as ``T`` calls of
    :func:`ssd_step_lax`, up to float32 rounding, with the state as the last
    valid row left it."""
    T, H, P = x.shape
    G, N = B.shape[-2:]
    Q = min(int(chunk), T)
    if n_valid is not None:
        dt = jnp.where((jnp.arange(T) < n_valid)[:, None], dt, 0.0)
    pad = (-T) % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in (x, dt, B, C))
    n = (T + pad) // Q
    heads_first = lambda a: jnp.moveaxis(a.reshape((n, Q) + a.shape[1:]), 1, 2)   # noqa: E731  [n, H | G, Q, ...]
    xd, g = heads_first(x * dt[..., None]), jnp.cumsum(heads_first(dt * A), axis=-1)  # [n, H, Q, P]; [n, H, Q], <= 0
    Bc, Cc = heads_first(B), heads_first(C)                                        # [n, G, Q, N]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # exp(g_t - g_s) for s <= t, 0 above the diagonal; never over 1
    L = jnp.exp(jnp.where(s_idx <= t_idx, g[..., :, None] - g[..., None, :], -jnp.inf))   # [n, H, Q, Q]
    cb = jnp.matmul(Cc, jnp.swapaxes(Bc, -1, -2), precision=_HI)                   # [n, G, Q, Q]: one product a group
    M = (_by_group(L, G, 1) * cb[:, :, None]).reshape(L.shape)
    y = jnp.matmul(M, xd, precision=_HI)                                           # [n, H, Q, P], within the chunk
    g_end = g[..., -1:]                                                            # [n, H, 1]
    x_tail = xd * jnp.exp(g_end - g)[..., None]                                    # exp(g_Q - g_s) dt_s x_s
    decay_in = jnp.exp(g)

    def one_chunk(s0, xs):
        c_c, b_c, xt_c, din_c, ge_c = xs
        s0g = _by_group(s0, G, 0)                                                  # [G, Hg, P, N]
        read = jnp.einsum("gqk,ghpk->ghqp", c_c, s0g, precision=_HI).reshape(H, Q, P)     # C S_0
        wrote = jnp.einsum("ghqp,gqk->ghpk", _by_group(xt_c, G, 0), b_c, precision=_HI).reshape(H, P, N)
        return jnp.exp(ge_c)[..., None] * s0 + wrote, din_c[..., None] * read

    state, carried = jax.lax.scan(one_chunk, state, (Cc, Bc, x_tail, decay_in, g_end))
    y = jnp.moveaxis(y + carried, 2, 1).reshape(T + pad, H, P)[:T]
    return y + D[:, None] * x[:T], state


def ssd_step(x, dt, A, B, C, D, state, active=None):
    """The ``ssd_step`` registry entry's choice for this call."""
    return registry.select("ssd_step", x, B, state).fn(x, dt, A, B, C, D, state, active)


def ssd_chunked(x, dt, A, B, C, D, state, n_valid=None, *, chunk: int = 256):
    """The ``ssd_chunked`` registry entry's choice for this call."""
    return registry.select("ssd_chunked", x, B, state, chunk=int(chunk)).fn(x, dt, A, B, C, D, state, n_valid, chunk=int(chunk))


registry.define_kernel("ssd_step")
registry.register("ssd_step", "lax", ssd_step_lax,
                  doc="decay, rank-one write and read-out of every slot's state in one elementwise pass (any device)")
registry.define_kernel("ssd_chunked")
registry.register("ssd_chunked", "lax", ssd_chunked_lax,
                  doc="the state-space duality's masked matmuls an inner chunk, the state carried by a scan (any device)")

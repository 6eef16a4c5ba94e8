"""Dropless top-k routing over gated experts, for a layer that is *told which
experts it holds*.

The router scores every expert of the model and keeps the ``k`` largest — by
``sigmoid`` scores, the weights normalised over the chosen, or by logits, the
weights a softmax over the chosen ``k`` (:func:`route_topk` has both
scorings); this layer computes the part of
the result that its own experts ``[first, first + count)`` give, for the
(token, expert) pairs routed to them, and nothing for the others: on one chip
of an expert-parallel group that is the chip's partial result, and no exchange
runs. With ``held = (0, n_experts)`` it is the whole layer. No pair is ever
dropped: there is no capacity.

The held pairs are sorted by expert, so each expert's rows are one run, and
the two projections are grouped matmuls over the runs with static shapes (the
worst case: every pair of the batch lands here, ``T * k`` rows). The grouped
matmul is the ``grouped_matmul`` registry entry's choice: on the TPU, for
bf16 operands with no mesh, the Pallas kernel of ``ops/grouped_matmul.py``,
which visits only the (row tile, expert) pairs that hold rows — so a decode
step reads an expert's weights only if a token chose it — with a row tile
sized to the run a batch of this size is expected to give an expert (``T * k``
pairs over the router's width); everywhere else ``lax.ragged_dot``, which XLA
compiles for the TPU to a kernel of the same visiting order with 512-row
tiles (PERF.md §6, PR 30 and PR 31, have why neither is ``ops/moe_pallas.py``,
whose kernel pads every expert's run to a capacity). The pairs go back to
token order through the ``moe_combine`` registry entry
(``ops/moe_combine.py``): on the TPU, for a float32 result with no mesh, a
kernel that reads the held pairs' rows once and adds each, weighted, into its
token's row; everywhere else a slot-major gather and a sum over the major
axis. Rows of no run are left out by selection (they are unspecified), and
``[T, k, D]`` is never formed: ``k = 10`` pads to 16 sublanes there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import grouped_matmul as _grouped
from .moe_combine import combine

__all__ = ["route_topk", "dropless_experts", "gated_ffn"]


def route_topk(x, router_w, *, top_k: int, norm_topk: bool = True, scale: float = 1.0, scoring: str = "sigmoid"):
    """``(weights [T, k] float32, experts [T, k] int32)`` over all of the
    router's outputs, scored in float32 one of two ways. ``"sigmoid"``: the
    ``k`` largest sigmoid scores, their weights normalised over the chosen
    (``norm_topk``). ``"softmax_topk"``: the ``k`` largest logits, their
    weights a softmax over those ``k`` logits (which sum to one: ``norm_topk``
    says nothing there). Either way times ``scale``."""
    if scoring not in ("sigmoid", "softmax_topk"):
        raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax_topk'")
    with jax.named_scope("moe_router"):
        logits = jnp.matmul(x, router_w, preferred_element_type=jnp.float32)
        if scoring == "softmax_topk":
            top, idx = jax.lax.top_k(logits, int(top_k))
            w = jax.nn.softmax(top, axis=-1)
        else:
            w, idx = jax.lax.top_k(jax.nn.sigmoid(logits), int(top_k))
            if norm_topk:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w * scale, idx.astype(jnp.int32)


def _gated(h, f: int, limit):
    """``SiLU(gate) * up`` of ``h = [gate | up]``; with ``limit`` (a
    ``swiglu_limit``) the gate clamped from above and the up-projection on
    both sides first."""
    if limit is None:
        return jax.nn.silu(h[..., :f]) * h[..., f:]
    return jax.nn.silu(jnp.minimum(h[..., :f], limit)) * jnp.clip(h[..., f:], -limit, limit)


def gated_ffn(x, w_gate_up, w_down, limit=None):
    """``W_down(SiLU(W_gate x) * W_up x)`` with gate and up packed side by
    side in ``w_gate_up [D, 2F]``; ``limit`` clamps them (:func:`_gated`)."""
    h = jnp.matmul(x, w_gate_up, preferred_element_type=jnp.float32)
    f = w_down.shape[-2]
    return jnp.matmul(_gated(h, f, limit).astype(x.dtype), w_down, preferred_element_type=jnp.float32)


def dropless_experts(x, weights, experts, w_gate_up, w_down, *, held, n_experts=None, limit=None):
    """The held experts' part of the routed result.

    ``x [T, D]``; ``weights``/``experts`` ``[T, k]`` from :func:`route_topk`;
    ``w_gate_up [count, D, 2F]``, ``w_down [count, F, D]`` the held experts'
    weights; ``held = (first, count)``; ``n_experts`` the router's width
    (``count`` if not given: the layer holds them all), from which the
    grouped matmul takes the run it should expect; ``limit`` clamps the gated
    product's two factors (:func:`_gated`). The sorted rows go back to token
    order as ``sum_j weights[t, j] * row(t, j)`` in float32 over the held
    pairs of each token, the others' rows selected out, never multiplied
    (``ops/moe_combine.py``). Returns ``(y [T, D]
    float32, stats int32[2])`` with ``stats = (pairs routed to a held expert,
    held experts with at least one pair)``."""
    first, count = int(held[0]), int(held[1])
    T, k = experts.shape
    with jax.named_scope("moe_routed"):
        local = experts - first
        mine = (local >= 0) & (local < count)
        key = jnp.where(mine, local, count).reshape(-1)                 # absent experts sort last
        order = jnp.argsort(key, stable=True)                           # [T*k]: held pairs first, by expert
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        rows = jnp.take(x, order // k, axis=0)                          # [T*k, D]
        # one selection for both projections (and so one per compiled program: every layer's call has these shapes)
        expected_run = T * k / int(n_experts or count)
        matmul = _grouped.select(rows, w_gate_up, w_down, expected_run=expected_run)
        h = matmul(rows, w_gate_up, sizes, expected_run=expected_run)
        f = w_down.shape[-2]
        a = _gated(h, f, limit).astype(x.dtype)
        y = matmul(a, w_down, sizes, expected_run=expected_run)
        # back to token order: row r holds pair order[r], the held pairs' rows first; the rows past them belong to
        # no run, so whatever they hold is left out
        y = combine(y, order, mine, weights)
        stats = jnp.stack([jnp.sum(mine.astype(jnp.int32)), jnp.sum((sizes > 0).astype(jnp.int32))])
    return y, stats

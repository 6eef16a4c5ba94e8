"""Sorted pair rows back to token order: ``out[t] = sum_j weights[t, j] *
y[row of pair (t, j)]`` over the slots ``j`` of token ``t`` that ``mine[t, j]``
keeps.

``y [M, D]`` float32 is the second grouped matmul's result
(``ops/moe_dropless.py``): row ``r`` holds pair ``order[r]`` (pair ``t * k +
j``), the kept pairs' rows first, by expert. The rows past them belong to no
run and are **unspecified** (``ops/grouped_matmul.py``), so they are left out
by selection, never by a product with zero. The products and the sum over a
token's ``k`` are float32 in both forms.

``[T, k, D]`` is never formed. The TPU tiles the last two dimensions
(8, 128), so with ``k = 10`` that shape is a copy into 16 padded sublanes and
its sum a reduction across them (PERF.md §6, PR 37). The ``xla`` form gathers
slot-major, ``[k T, D]``, which is ``[k, T, D]`` byte for byte wherever ``T``
is a multiple of 8, and sums over the major axis in plain vector adds: two
passes over all ``M`` rows, in slot order. The ``pallas_rows`` form reads only
the kept rows, which are the first of ``y``: it walks them a block at a time
(a block past them is never fetched), keeps the ``[T, D]`` result in VMEM for
the whole call, adds ``weight * row`` into its token's row — the ``k`` terms
of a token in expert order — and writes the result once. (Mosaic copies no
single row of an (8, 128)-tiled array, so no gather by token tile: PERF.md
§6, PR 37.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry
from .decode_attention import _LANES, _under_mesh
from .grouped_matmul import _largest_divisor

__all__ = ["combine", "combine_lax", "combine_rows", "combine_available", "set_interpret"]

_INTERPRET = False  # run the pallas_call in interpreter mode (CPU parity tests)
_ROWS_MAX = 256              # rows of y a grid step reads, at most
_RESULT_BYTES = 32 << 20     # the [T, D] result, which stays in VMEM for the whole call
_SCALAR_BYTES = 512 << 10    # order and weights, 8 bytes a pair, in the chip's 1 MiB of scalar memory


def set_interpret(on: bool) -> bool:
    """Route the ``pallas_call`` through the Pallas interpreter (the CPU
    parity tests). Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def combine_lax(y, order, mine, weights):
    """:func:`combine` in plain ``lax``: every device, every dtype, a mesh."""
    T, k = mine.shape
    at = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))     # pair p sits at row at[p]
    y = jnp.take(y, at.reshape(T, k).T.reshape(-1), axis=0).reshape(k, T, -1)
    return jnp.sum(jnp.where(mine.T[..., None], y * weights.T[..., None], 0.0), axis=0)


def _rows(M: int):
    """Rows of ``y`` a grid step reads: whole sublane tiles that divide ``M``
    (400 pairs of a decode step: 200), all of a small ``M`` that has no such
    divisor, or None."""
    return _largest_divisor(M, 8, _ROWS_MAX) or (M if M <= _ROWS_MAX else None)


def combine_available(y, order, mine, weights) -> bool:
    """Registry predicate: float32 ``y [M, D]`` with ``D`` in whole lane
    tiles and ``M`` in blocks of rows (:func:`_rows`), a ``[T, D]`` result
    that fits ``_RESULT_BYTES`` and pairs that fit ``_SCALAR_BYTES``, on a TPU
    (or in interpret mode) with no mesh (a Mosaic kernel is not partitioned
    automatically)."""
    if y.ndim != 2 or mine.ndim != 2 or y.dtype != jnp.float32 or weights.dtype != jnp.float32:
        return False
    M, D = y.shape
    if D % _LANES or _rows(M) is None or mine.shape[0] * D * 4 > _RESULT_BYTES or M * 8 > _SCALAR_BYTES:
        return False
    if _INTERPRET:
        return True
    from ..device import is_tpu

    return is_tpu() and not _under_mesh()


def _kernel(kept_ref, order_ref, w_ref, y_ref, out_ref, acc, sem, *, k, tm, blocks):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def add_row(r, carry):
        row = b * tm + r

        @pl.when(row < kept_ref[0])            # a row past the kept ones is never read: left out, not multiplied by zero
        def _():
            pair = order_ref[row]
            token = pl.ds(pair // k, 1)
            acc[token, :] = acc[token, :] + w_ref[pair] * y_ref[pl.ds(r, 1), :]

        return carry

    @pl.when(b * tm < kept_ref[0])
    def _():
        jax.lax.fori_loop(0, tm, add_row, None)

    @pl.when(b == blocks - 1)
    def _():
        copy = pltpu.make_async_copy(acc, out_ref, sem)
        copy.start()
        copy.wait()


def combine_rows(y, order, mine, weights):
    """:func:`combine` that reads only the kept pairs' rows, which ``order``
    puts first. The custom call is named ``moe_combine``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, D = y.shape
    T, k = mine.shape
    tm = _rows(M)
    kept = jnp.sum(mine.astype(jnp.int32)).reshape(1)
    call = pl.pallas_call(
        functools.partial(_kernel, k=k, tm=tm, blocks=M // tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // tm,),
            # a block past the kept rows is the last kept one again: not fetched
            in_specs=[pl.BlockSpec((tm, D), lambda b, kept, order, w: (jnp.minimum(b, jnp.maximum(kept[0] - 1, 0) // tm), 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((T, D), jnp.float32), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        # every block adds into the one result: in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=(T + 2 * tm) * D * 4 + (4 << 20)),
        # XLA bills a custom call its whole operands: say what one call moves when every pair is kept
        cost_estimate=pl.CostEstimate(flops=2 * M * D, transcendentals=0, bytes_accessed=(M + T) * D * 4),
        name="moe_combine",
        interpret=_INTERPRET,
    )
    return call(kept, order.astype(jnp.int32), weights.reshape(-1), y)


def combine(y, order, mine, weights):
    """``[T, D]`` float32 from ``y [M, D]``, ``order [M]`` (row ``r`` holds
    pair ``order[r]``, the kept pairs first) and ``mine`` / ``weights``
    ``[T, k]``: the kernel where :func:`combine_available` holds,
    :func:`combine_lax` everywhere else. One selection per distinct set of
    shapes (``kernels.moe_combine.picked`` / ``.fallback``)."""
    return registry.dispatch("moe_combine", y, order, mine, weights)


registry.define_kernel(
    "moe_combine", cache_key=lambda: ("interpret", _INTERPRET, "mesh", _under_mesh()))
registry.register(
    "moe_combine", "pallas_rows", combine_rows, available=combine_available,
    doc="weighted sum of a token's kept pair rows, each fetched once from HBM (TPU, float32, no mesh)")
registry.register(
    "moe_combine", "xla", combine_lax, fallback=True,
    doc="slot-major gather and a sum over the major axis (any dtype, any device, a mesh)")

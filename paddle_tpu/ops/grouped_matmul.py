"""Grouped matmul over runs of rows: ``out[r] = lhs[r] @ rhs[g]`` for the rows
``r`` of run ``g``, with a row tile sized to the runs.

``lhs [M, K]`` holds the runs back to back from row 0 (run ``g`` has
``group_sizes[g]`` rows); ``rhs [G, K, N]``; the result is float32 ``[M, N]``.
``sum(group_sizes)`` may be less than ``M``: the rows past the last run belong
to no group, are not computed, and come back **unspecified** (the caller
masks them, as ``ops/moe_dropless.py`` does). It is the contract of
``lax.ragged_dot`` on those operands, rows of no group apart.

XLA compiles ``lax.ragged_dot`` to a Mosaic kernel with 512-row tiles, so a
run of three rows — what a decode batch gives each expert — costs a whole
masked 512-row tile of MXU work (PERF.md §6, PR 31). Here the grid walks the
(row tile, group) pairs that hold rows, found from ``group_sizes`` on the
device and handed to the index maps by scalar prefetch (the layout of
``jax.experimental.pallas.ops.tpu.megablox``): a group with no rows is never
read, a row tile is ``tm`` rows, and the step is bound by the bytes of the
groups that were hit. ``tm``, ``tk`` and ``tn`` come from the shapes and the
dtype (:func:`plan`); nothing is tuned by hand at a call site.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry
from .decode_attention import _LANES, _sublanes, _under_mesh
from .flash_attention import _NN, _dot32

__all__ = ["grouped_matmul", "grouped_matmul_available", "plan", "select", "set_interpret"]

_INTERPRET = False  # run the pallas_call in interpreter mode (CPU parity tests)
_ROW_TILE_MAX = 128          # one pass of the MXU's rows
_BLOCK_BYTES = 6 << 20       # one weight block of one buffer slot in VMEM (two slots)
_VMEM_LIMIT = 32 << 20


def set_interpret(on: bool) -> bool:
    """Route the ``pallas_call`` through the Pallas interpreter (the CPU
    parity tests). Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def _largest_divisor(n: int, step: int, limit: int):
    """The largest multiple of ``step`` that divides ``n`` and is at most
    ``limit``, or None."""
    best = None
    for d in range(step, min(n, limit) + 1, step):
        if n % d == 0:
            best = d
    return best


def plan(M: int, K: int, N: int, expected_run: float, dtype):
    """``(tm, tk, tn)`` for ``[M, K] x [G, K, N]`` whose runs are expected to
    be ``expected_run`` rows long, or None if the kernel cannot tile it.

    ``tm``: the power of two next above eight expected runs, from one packed
    sublane tile of the dtype (16 rows in bf16) to 128. Up to there the
    MXU's time on a tile stays under the time its weight block takes to
    arrive, so masked rows cost nothing and fewer, longer tiles mean fewer
    grid steps; XLA's 512 are past it (PERF.md §6, PR 31, has the sweep);
    halved from there until it divides ``M``, down to the sublane tile.
    ``tk``: the whole contraction where a lane tile's width of it fits
    ``_BLOCK_BYTES``, so that a run which straddles a row-tile edge visits its
    weight block twice and fetches it once (a block is fetched again only
    when its index changes); ``tn``: the widest block of N's lane tiles that
    then fits."""
    tm = _sublanes(dtype)
    while tm < min(8 * expected_run, _ROW_TILE_MAX):
        tm *= 2
    while tm > _sublanes(dtype) and M % tm:     # 400 pairs (40 tokens x 10) are no multiple of 64: 16 divides them
        tm //= 2
    item = jnp.dtype(dtype).itemsize
    tk = _largest_divisor(K, _LANES, _BLOCK_BYTES // (_LANES * item))
    if M % tm or tk is None:
        return None
    tn = _largest_divisor(N, _LANES, _BLOCK_BYTES // (tk * item))
    if tn is None:
        return None
    return tm, tk, tn


def grouped_matmul_available(lhs, *rhs, expected_run: float) -> bool:
    """Registry predicate for ``lhs [M, K]`` and every ``rhs [G, K_i, N_i]``
    the caller will multiply ``M`` sorted rows by (one selection serves a
    chain of projections): bf16 operands of shapes :func:`plan` can tile, on a
    TPU (or in interpret mode) with no mesh (a Mosaic kernel is not
    partitioned automatically)."""
    if lhs.ndim != 2 or any(r.ndim != 3 for r in rhs):
        return False
    if any(a.dtype != jnp.bfloat16 for a in (lhs,) + rhs):
        return False
    if any(plan(lhs.shape[0], r.shape[1], r.shape[2], expected_run, lhs.dtype) is None for r in rhs):
        return False
    if _INTERPRET:
        return True
    from ..device import is_tpu

    return is_tpu() and not _under_mesh()


def _visits(group_sizes, m: int, tm: int):
    """The (row tile, group) pairs that hold rows, in row order:
    ``(offsets int32[G + 1], group_of int32[V], tile_of int32[V], n)`` with
    ``V = m / tm + G - 1`` the most there can be and ``n`` how many there
    are; entries past ``n`` repeat the last pair's tile and are not run."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    v = jnp.arange(m // tm + G - 1, dtype=jnp.int32)
    group_of = jnp.minimum(jnp.sum(v[:, None] >= visit_ends[None, :], axis=1), G - 1).astype(jnp.int32)
    tile_of = first[group_of] + v - (visit_ends - tiles)[group_of]
    tile_of = jnp.clip(tile_of, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_of, tile_of, visit_ends[-1]


def _kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref, *acc, tm, tiles_k):
    from jax.experimental import pallas as pl

    v, kk = pl.program_id(1), pl.program_id(2)

    def store(result):
        # rows of the tile that lie in this visit's group; the others keep what an earlier visit of the tile left
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, result.shape, 0)
        out_ref[...] = jnp.where((row >= offsets_ref[g]) & (row < offsets_ref[g + 1]), result, out_ref[...])

    part = _dot32(lhs_ref[...], rhs_ref[...], _NN)
    if tiles_k == 1:
        store(part)
        return
    acc_ref, = acc

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = part

    @pl.when(kk > 0)
    def _():
        acc_ref[...] += part

    @pl.when(kk == tiles_k - 1)
    def _():
        store(acc_ref[...])


def grouped_matmul(lhs, rhs, group_sizes, *, expected_run: float, tiling=None):
    """``[M, N]`` float32, row ``r`` of run ``g`` holding ``lhs[r] @ rhs[g]``;
    rows past the last run unspecified. ``expected_run`` (rows a run is
    expected to hold: pairs over the router's width) sizes the row tile;
    ``tiling = (tm, tk, tn)`` overrides :func:`plan` (the tile sweep and the
    tests). The custom call is named ``moe_grouped_<tm>``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    G, _, N = rhs.shape
    tm, tk, tn = tiling or plan(M, K, N, expected_run, lhs.dtype)
    tiles_k, tiles_n = K // tk, N // tn
    offsets, group_of, tile_of, n_visits = _visits(group_sizes, M, tm)
    item = jnp.dtype(lhs.dtype).itemsize

    call = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # N outermost: a row tile's result block stays in VMEM across the visits (groups) that share the tile
            grid=(tiles_n, n_visits, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lambda n, v, k, off, grp, tile: (tile[v], k)),
                      pl.BlockSpec((None, tk, tn), lambda n, v, k, off, grp, tile: (grp[v], k, n))],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, k, off, grp, tile: (tile[v], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] if tiles_k > 1 else [],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        # XLA bills a custom call its whole operands: say what one call moves when every group is hit once
        cost_estimate=pl.CostEstimate(flops=2 * M * K * N, transcendentals=0,
                                      bytes_accessed=(M * K * tiles_n + G * K * N) * item + M * N * 4),
        name=f"moe_grouped_{tm}",
        interpret=_INTERPRET,
    )
    return call(offsets, group_of, tile_of, lhs, rhs)


def _ragged_dot(lhs, rhs, group_sizes, *, expected_run=None):
    """XLA's grouped matmul, where the kernel declines. bf16 operands at the
    default precision, as ``_dot32`` pins the kernels': XLA compiles it for the
    TPU to a Mosaic kernel, and Mosaic refuses a higher one on bf16 (a
    process-wide "highest" must not reach it)."""
    precision = jax.lax.Precision.DEFAULT if lhs.dtype == jnp.bfloat16 else None
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, precision=precision, preferred_element_type=jnp.float32)


def select(lhs, *rhs, expected_run: float):
    """``matmul(lhs, rhs, group_sizes, expected_run=)`` for ``M`` sorted rows
    and every ``rhs`` they (and what follows from them) will be multiplied
    by: the kernel where :func:`grouped_matmul_available` holds for all of
    them, ``lax.ragged_dot`` everywhere else. One selection per distinct set
    of shapes (``kernels.grouped_matmul.picked`` / ``.fallback``)."""
    return registry.select("grouped_matmul", lhs, *rhs, expected_run=expected_run).fn


registry.define_kernel(
    "grouped_matmul", cache_key=lambda: ("interpret", _INTERPRET, "mesh", _under_mesh()))
registry.register(
    "grouped_matmul", "pallas_runs", grouped_matmul, available=grouped_matmul_available,
    doc="grouped matmul whose row tile fits the runs; a group with no rows is never read (TPU, bf16, no mesh)")
registry.register(
    "grouped_matmul", "xla", _ragged_dot, fallback=True,
    doc="lax.ragged_dot: XLA's grouped matmul, 512-row tiles on the TPU (any dtype, any device, a mesh)")

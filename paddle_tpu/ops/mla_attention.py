"""Latent (MLA) attention over the serving engine's latent cache: what a slot
caches of a token is one row ``[c | k_r]`` — the normalised key/value latent
(``rank`` numbers) and the one rotated key every head shares (``rope``
numbers) — with no head axis: ``cache [B, S, width]``, ``width`` at least
``rank + rope`` and padded with zeros beyond (to whole lanes: the device's
tiled layout pads the last axis so anyway, and a kernel can copy whole rows
only of an axis that is whole tiles).

**Decode**, absorbed: the caller has folded each head's key up-projection into
its query (``q'_h = W_uk,h^T q_nope,h``), so a query row is ``[q'_h | q_rope,h]``
over the cached row itself, the values are the row's first ``rank`` numbers,
and all ``H`` heads attend the *same* rows: per cached row ``2 (rank + rope +
rank) H`` operations for ``(rank + rope)`` numbers read. :func:`latent_decode`
is one Pallas kernel a slot: it streams the slot's rows up to ``pos`` through
VMEM in blocks (double-buffered DMA), merges the new row into the block it
falls in and writes only that tile back (the cache is aliased to the output),
and runs an online softmax with the heads as the rows of two MXU products a
block. Blocks past a slot's depth and slots that are not active are never
read. :func:`latent_decode_lax` is the same contract in ``lax`` — it reads
every row of every slot, whatever the depth — for devices the kernel does not
serve.

**Prefill**, un-absorbed, :func:`latent_prefill`: a chunk's queries against the
slot's rows up to their own, the keys and values of each block of the context
expanded from its latents as the block is visited (``k_nope = W_uk c``, ``v =
W_uv c``) and an online softmax across blocks, so that neither the expanded
keys nor the scores of the whole context are ever held (64 heads x 1,024 x
32,768 float32 scores would be 8.6 GB). Un-absorbed because a chunk's ``C``
queries share each expanded block: ``2 (dn + rope + dv)`` operations a score
against the absorbed form's ``2 (2 rank + rope)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import registry
from .decode_attention import _LANES, _sublanes, _under_mesh

__all__ = ["latent_decode", "latent_decode_lax", "latent_decode_available", "latent_prefill", "decode", "prefill",
           "set_interpret"]

_INTERPRET = False  # run the pallas_call in interpreter mode (CPU parity tests)
_BLOCK_BYTES = 1 << 20  # one block of cached rows of one buffer slot in VMEM
_PREFILL_BLOCK = 1024   # rows of the context a prefill step expands at most


def set_interpret(on: bool) -> bool:
    """Route the ``pallas_call`` through the Pallas interpreter (the CPU
    parity tests). Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def _plan(S: int, width: int, dtype):
    """(rows per streamed block, tile granularity along S) for a cache of this
    shape, or None if the kernel cannot tile it. The granularity is a packed
    sublane tile: what a write-back must cover to stay tile-aligned in HBM."""
    item = jnp.dtype(dtype).itemsize
    if item not in (2, 4):
        return None
    tile = _sublanes(dtype)
    if S % tile:
        return None
    stored = -(-width // _LANES) * _LANES * item        # a row as VMEM holds it: the lanes padded
    rows = tile
    while S % (rows * 2) == 0 and rows * 2 * stored <= _BLOCK_BYTES:
        rows *= 2
    return rows, tile


def latent_decode_available(q, row, cache, *, rank: int) -> bool:
    """Registry predicate: a TPU (or interpret mode), no mesh, float32 or
    bfloat16 rows of a context the kernel can tile, the latent's width and
    the row's multiples of the lanes (the rotated key is sliced off behind
    the latent, and a DMA takes whole tiles)."""
    if cache.ndim != 3 or q.ndim != 3 or cache.dtype != q.dtype:
        return False
    if jnp.dtype(cache.dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if _plan(cache.shape[1], cache.shape[2], cache.dtype) is None:
        return False
    if _INTERPRET:
        return True
    from ..device import is_tpu

    return rank % _LANES == 0 and cache.shape[2] % _LANES == 0 and is_tpu() and not _under_mesh()


def _dot(a, b, contract):
    """``a x b`` in f32; bf16 operands at the default precision, as ``_dot32``
    pins the flash kernels' (Mosaic refuses a higher one on bf16)."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision, preferred_element_type=jnp.float32)


def _kernel(pos_ref, act_ref, q_ref, new_ref, c_in, o_ref, c_out, buf, rsem, wsem, *, rank, rows, tile, seq):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    heads = q_ref.shape[0]
    pos = pos_ref[b]

    def fetch(blk, slot):
        return pltpu.make_async_copy(c_in.at[b, pl.ds(blk * rows, rows)], buf.at[slot], rsem.at[slot])

    def write_back(slot, off, start):
        return pltpu.make_async_copy(buf.at[slot, pl.ds(off, tile)], c_out.at[b, pl.ds(start, tile)], wsem.at[0])

    @pl.when(act_ref[b] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(act_ref[b] != 0)
    def _():
        # never past the cache, whatever ``pos`` holds: a DMA has no bounds check
        n_blocks = jnp.minimum(pos // rows + 1, seq // rows)
        fetch(0, 0).start()
        start = (pos // tile) * tile                       # the tile the new row falls in

        def body(blk, carry):
            m, l, acc = carry
            slot = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                fetch(blk + 1, 1 - slot).start()

            fetch(blk, slot).wait()
            here = (start >= blk * rows) & (start < (blk + 1) * rows)
            off = pl.multiple_of(jnp.where(here, start - blk * rows, 0), tile)

            # write before attend: the new row goes into the block in VMEM, and its tile back to the cache
            @pl.when(here)
            def _():
                view = buf.at[slot, pl.ds(off, tile)]
                where = start + jax.lax.broadcasted_iota(jnp.int32, view.shape, 0)
                view[...] = jnp.where(where == pos, new_ref[...], view[...])
                write_back(slot, off, start).start()

            c, kr = buf[slot, :, :rank], buf[slot, :, rank:]
            s = _dot(q_ref[:, :rank], c, ((1,), (1,))) + _dot(q_ref[:, rank:], kr, ((1,), (1,)))    # [heads, rows]
            k_pos = blk * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= pos, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + _dot(p.astype(c.dtype), c, ((1,), (0,)))                            # [heads, rank]

            @pl.when(here)
            def _():
                write_back(slot, off, start).wait()

            return m_new, l, acc

        init = (jnp.full((heads, 1), -jnp.inf, jnp.float32), jnp.zeros((heads, 1), jnp.float32),
                jnp.zeros((heads, rank), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
        o_ref[...] = (acc / l).astype(o_ref.dtype)


def latent_decode(q, row, cache, pos, active, *, rank: int):
    """Write-and-attend for one latent layer, one token a slot.

    ``q [B, H, width]``: each head's absorbed query beside its rotated rope
    query (zeros beyond), already scaled; ``row [B, width]`` the token's new
    cache row; ``cache [B, S, width]``; ``pos [B]`` int32 the row's index;
    ``active [B]`` bool gates a slot's write — an inactive slot's rows stay
    bitwise untouched and its output is zero. Every head attends the slot's
    rows up to ``pos``, the new one included, and takes the rows' first
    ``rank`` numbers as values. Returns ``(o_lat [B, H, rank], cache)`` with
    the cache aliased to the input. The call is scoped ``mla_core``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, width = cache.shape
    H = q.shape[1]
    rows, tile = _plan(S, width, cache.dtype)
    item = jnp.dtype(cache.dtype).itemsize
    hp = -(-H // _sublanes(q.dtype)) * _sublanes(q.dtype)
    q = jnp.pad(q, ((0, 0), (0, hp - H), (0, 0)))
    whole = pl.BlockSpec(memory_space=pl.ANY)

    call = pl.pallas_call(
        functools.partial(_kernel, rank=int(rank), rows=rows, tile=tile, seq=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, hp, width), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, 1, width), lambda b, *_: (b, 0, 0)), whole],
            out_specs=[pl.BlockSpec((None, hp, int(rank)), lambda b, *_: (b, 0, 0)), whole],
            scratch_shapes=[pltpu.VMEM((2, rows, width), cache.dtype), pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, hp, int(rank)), q.dtype), jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        # operands: pos, active, q, row, cache
        input_output_aliases={4: 1},
        # XLA bills a custom call its whole operands: say what one call moves (every slot's rows at full depth)
        cost_estimate=pl.CostEstimate(flops=2 * B * hp * S * (width + int(rank)), transcendentals=B * hp * S,
                                      bytes_accessed=B * (S + tile) * width * item + 2 * B * hp * width * item),
        name="mla_decode",
        interpret=_INTERPRET,
    )
    with jax.named_scope("mla_core"):
        o, cache = call(pos.astype(jnp.int32), active.astype(jnp.int32), q, row[:, None].astype(cache.dtype), cache)
    return o[:, :H], cache


def latent_decode_lax(q, row, cache, pos, active, *, rank: int):
    """:func:`latent_decode`'s contract in ``lax``: the rows written slot by
    slot (scope ``cache_write``), then every row of every slot scored and
    masked by the slot's position (scope ``mla_core``)."""
    B, S, _ = cache.shape

    def write(c, u, p, a):
        cur = jax.lax.dynamic_slice(c, (p, 0), u.shape)
        return jax.lax.dynamic_update_slice(c, jnp.where(a, u, cur), (p, 0))

    with jax.named_scope("cache_write"):
        cache = jax.vmap(write)(cache, row[:, None].astype(cache.dtype), pos, active)
    with jax.named_scope("mla_core"):
        scores = jnp.einsum("bhw,bsw->bhs", q, cache, preferred_element_type=jnp.float32)
        visible = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1) <= pos[:, None]
        prob = jax.nn.softmax(jnp.where(visible[:, None], scores, -jnp.inf), axis=-1).astype(cache.dtype)
        o = jnp.einsum("bhs,bsc->bhc", prob, cache[:, :, :rank], preferred_element_type=jnp.float32)
        o = jnp.where(active[:, None, None], o, 0.0)
    return o.astype(q.dtype), cache


def latent_prefill(q_nope, q_rope, cache, w_uk, w_uv, slot, start, *, rank: int, block: int = _PREFILL_BLOCK):
    """A chunk's ``C`` queries of slot ``slot`` at ``start`` against the slot's
    rows ``[0, start + C)`` (the chunk's own already written), row ``i``
    attending rows up to ``start + i``.

    ``q_nope [C, H, dn]`` and ``q_rope [C, H, rope]`` (rotated), both already
    scaled; ``cache [B, S, width]``; ``w_uk [rank, H, dn]`` and ``w_uv
    [rank, H, dv]`` the key and value up-projections. The context is walked in
    blocks of ``gcd(block, S)`` rows: each block's keys and values are
    expanded from its latents, scored, and folded into an online softmax; no
    block past ``start + C`` is read. Returns ``att [C, H, dv]`` float32.
    Scoped ``mla_core``."""
    C, H, _ = q_nope.shape
    S = cache.shape[1]
    dv = w_uv.shape[-1]
    bk = math.gcd(int(block), S)
    with jax.named_scope("mla_core"):
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (C, bk), 0)

        def body(j, carry):
            m, l, acc = carry
            lat = jax.lax.dynamic_slice(cache, (slot, j * bk, 0), (1, bk, cache.shape[2]))[0]
            c, kr = lat[:, :rank], lat[:, rank:rank + q_rope.shape[-1]]
            k = jnp.einsum("sc,chd->shd", c, w_uk)
            v = jnp.einsum("sc,chd->shd", c, w_uv)
            s = (jnp.einsum("qhd,shd->hqs", q_nope, k, preferred_element_type=jnp.float32)
                 + jnp.einsum("qhr,sr->hqs", q_rope, kr, preferred_element_type=jnp.float32))
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (C, bk), 1)
            s = jnp.where((k_pos <= q_pos)[None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("hqs,shd->hqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((H, C, 1), -jnp.inf, jnp.float32), jnp.zeros((H, C, 1), jnp.float32),
                jnp.zeros((H, C, dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (start + C + bk - 1) // bk, body, init)
        return jnp.swapaxes(acc / l, 0, 1)


def decode(q, row, cache, pos, active, *, rank: int):
    """The ``mla_decode`` registry entry's choice for this call."""
    return registry.select("mla_decode", q, row, cache, rank=int(rank)).fn(q, row, cache, pos, active, rank=int(rank))


def prefill(q_nope, q_rope, cache, w_uk, w_uv, slot, start, *, rank: int):
    """The ``mla_prefill`` registry entry's choice for this call."""
    impl = registry.select("mla_prefill", q_nope, q_rope, cache, w_uk, w_uv, rank=int(rank))
    return impl.fn(q_nope, q_rope, cache, w_uk, w_uv, slot, start, rank=int(rank))


registry.define_kernel("mla_decode", cache_key=lambda: ("interpret", _INTERPRET, "mesh", _under_mesh()))
registry.register("mla_decode", "pallas_latent", latent_decode, available=latent_decode_available,
                  doc="absorbed write-and-attend over the latent cache, aliased in place (TPU, no mesh)")
registry.register("mla_decode", "lax", latent_decode_lax, fallback=True,
                  doc="absorbed attention over every row of the latent cache (any device, a mesh)")
registry.define_kernel("mla_prefill")
registry.register("mla_prefill", "lax_blocked", latent_prefill,
                  doc="un-absorbed chunk attention, keys and values expanded block by block of the context (any device)")

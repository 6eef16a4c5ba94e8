"""Decode attention over the serving engine's stacked KV cache: one Pallas
kernel that writes a slot's new rows into the cache **as it is stored** and
attends the slot's live rows, with the cache aliased to the kernel's output.

The lax formulation it replaces (``models/gpt.py:_slot_write_attend``) cuts a
layer out of the stacked ``[L, B, H, S, dh]`` cache, scatters the new rows
slot by slot, hands the whole layer to a q=1 dot that wants its own operand
layout, and stacks the layers again: on the v5e the decode step moved 9x to
17x the bytes it has to read (PERF.md §5). Here the cache never leaves HBM
whole: per (slot, block of heads) the kernel streams the slot's K and V
blocks up to ``pos + W`` through VMEM (double-buffered DMA), merges the
window's W new rows into the block they fall in, writes only the touched
tile back, and runs an online softmax in f32 over bf16 x bf16 -> f32
products. Blocks past a slot's depth and slots that are not active are never
read.

Layout. The device stores ``[..., S, dh]`` with ``dh`` in the lanes when
``dh`` fills them (a multiple of 128) and with ``S`` in the lanes otherwise
(head size 64: ``{3,4,2,1,0}``, so that 64 does not pad to 128). The kernel
takes the cache in the order it is stored — for the S-minor layout as
``swapaxes(cache, -1, -2)``, which is a bitcast there — and picks its block
shapes, tile granularity and contraction forms from ``dh``, ``S``, ``H`` and
the dtype. One kernel, no per-model switch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry

_INTERPRET = False  # run the pallas_call in interpreter mode (CPU parity tests)
_LANES = 128
_BLOCK_BYTES = 1 << 19  # one K (or V) block of one buffer slot in VMEM


def set_interpret(on: bool) -> bool:
    """Route the ``pallas_call`` through the Pallas interpreter (the CPU
    parity tests). Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def _under_mesh() -> bool:
    from ..distributed.fleet import fleet

    return fleet.multi_device_mesh is not None


def _s_minor(dh: int) -> bool:
    """Whether the device stores ``[..., S, dh]`` with S in the lanes."""
    return dh % _LANES != 0


def _sublanes(dtype) -> int:
    """Rows of one packed sublane tile: 8 of 32 bits, so 16 in bf16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _tiles_touched(window: int, tile: int) -> int:
    """How many ``tile``-aligned tiles a window of ``window`` rows can span."""
    return (window + tile - 2) // tile + 1


def _plan(H: int, S: int, dh: int, dtype):
    """(heads per grid step, rows per streamed block, tile granularity along
    S) for a cache of this shape, or None if the kernel cannot tile it.

    The granularity is what a write-back must cover to stay tile-aligned in
    HBM: a lane tile (128 positions) when S is in the lanes, a packed
    sublane tile (8 rows of 32 bits: 16 in bf16) when ``dh`` is."""
    item = jnp.dtype(dtype).itemsize
    if item not in (2, 4):
        return None
    tile = _LANES if _s_minor(dh) else _sublanes(dtype)
    if S % tile or dh % 8:
        return None
    hb = H
    while hb % 2 == 0 and hb * tile * dh * item > _BLOCK_BYTES:
        hb //= 2
    rows = tile
    while S % (rows * 2) == 0 and hb * rows * 2 * dh * item <= _BLOCK_BYTES:
        rows *= 2
    return hb, rows, tile


def decode_attention_available(cache, *, packed: bool, window: int) -> bool:
    """Registry predicate. ``cache`` is the stacked ``[L, B, H, S, dh]``
    payload; ``packed`` says it is the int8 pack's (the kernel reads plain
    arrays only). A TPU (or interpret mode), no mesh (a Mosaic kernel is not
    partitioned automatically and the engine never meshes), and a shape the
    kernel can tile."""
    if packed or cache.ndim != 5:
        return False
    _, _, H, S, dh = cache.shape
    if jnp.dtype(cache.dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if _plan(H, S, dh, cache.dtype) is None or window > S:
        return False
    if _INTERPRET:
        return True
    from ..device import is_tpu

    return is_tpu() and not _under_mesh()


def _heads_dot(a, b, contract):
    """``a [heads, m, .] x b [heads, ., .] -> [heads, m, n]`` in f32, one
    matmul a head. bf16 operands at the default precision, as ``_dot32`` pins
    the flash kernels' (Mosaic refuses a higher one on bf16)."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))), precision=precision,
                               preferred_element_type=jnp.float32)


def _kernel(layer_ref, pos_ref, act_ref, q_ref, kn_ref, vn_ref, ck_in, cv_in,
            o_ref, ck_out, cv_out, kbuf, vbuf, rsem, wsem, *, window, rows, tile, seq, s_minor, group=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hb = pl.program_id(0), pl.program_id(1)
    heads, wp, dh = q_ref.shape
    layer, pos = layer_ref[0], pos_ref[b]
    h0 = hb * heads
    n_tiles = _tiles_touched(window, tile)
    s_axis = 2 if s_minor else 1                # where S sits in a block [heads, ., .]

    def at_s(ref, lead, start, size):
        """``ref[lead..., h-range or :, S-range]`` in the stored order."""
        sl = pl.ds(start, size)
        return ref.at[lead + ((slice(None), sl) if s_minor else (sl, slice(None)))]

    def fetch(blk, slot):
        src = (layer, b, pl.ds(h0, heads))
        return [pltpu.make_async_copy(at_s(c, src, blk * rows, rows), buf.at[slot], rsem.at[i, slot])
                for i, (c, buf) in enumerate(((ck_in, kbuf), (cv_in, vbuf)))]

    def write_back(slot, off, start, t):
        dst = (layer, b, pl.ds(h0, heads))
        return [pltpu.make_async_copy(at_s(buf, (slot, slice(None)), off, tile),
                                      at_s(c, dst, start, tile), wsem.at[i, t])
                for i, (c, buf) in enumerate(((ck_out, kbuf), (cv_out, vbuf)))]

    @pl.when(act_ref[b] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(act_ref[b] != 0)
    def _():
        # never past the cache, whatever ``pos`` holds: a DMA has no bounds check
        n_blocks = jnp.minimum((pos + window + rows - 1) // rows, seq // rows)
        for dma in fetch(0, 0):
            dma.start()

        def touched(blk, t):
            """The window's t-th tile: its first row, that row's offset in
            block ``blk``, and whether the tile lies in that block at all."""
            start = (pos // tile + t) * tile
            here = ((start < pos + window) & (start >= blk * rows)
                    & (start < (blk + 1) * rows) & (start < seq))
            return start, pl.multiple_of(start - blk * rows, tile), here

        def body(blk, carry):
            m, l, acc = carry
            slot = blk % 2

            @pl.when(blk + 1 < n_blocks)
            def _():
                for dma in fetch(blk + 1, 1 - slot):
                    dma.start()

            for dma in fetch(blk, slot):
                dma.wait()

            # write before attend: the window's rows go into the block in
            # VMEM, and the tile they fall in goes back to the cache
            for t in range(n_tiles):
                start, off, here = touched(blk, t)

                @pl.when(here)
                def _(start=start, off=off, t=t):
                    shape = (heads, dh, tile) if s_minor else (heads, tile, dh)
                    where = start + jax.lax.broadcasted_iota(jnp.int32, shape, s_axis)
                    for buf, new in ((kbuf, kn_ref), (vbuf, vn_ref)):
                        view = at_s(buf, (slot, slice(None)), off, tile)
                        cur = view[...]
                        for w in range(window):
                            row = new[:, :, w:w + 1] if s_minor else new[:, w:w + 1, :]
                            cur = jnp.where(where == pos + w, row, cur)
                        view[...] = cur
                    for dma in write_back(slot, off, start, t):
                        dma.start()

            k, v = kbuf[slot], vbuf[slot]
            s = _heads_dot(q_ref[...], k, ((2,), (1,)) if s_minor else ((2,), (2,)))   # [heads, wp, rows]
            k_pos = blk * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            q_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            q_pos = pos + (q_row if group == 1 else q_row // group)    # a group's rows share a window row
            s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            pv = _heads_dot(p.astype(v.dtype), v, ((2,), (2,)) if s_minor else ((2,), (1,)))   # [heads, wp, dh]
            acc = acc * alpha + pv

            for t in range(n_tiles):
                start, off, here = touched(blk, t)

                @pl.when(here)
                def _(start=start, off=off, t=t):
                    for dma in write_back(slot, off, start, t):
                        dma.wait()

            return m_new, l, acc

        init = (jnp.full((heads, wp, 1), -jnp.inf, jnp.float32),
                jnp.zeros((heads, wp, 1), jnp.float32),
                jnp.zeros((heads, wp, dh), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
        o_ref[...] = (acc / l).astype(o_ref.dtype)


def decode_attention(q, k, v, cache_k, cache_v, pos, active, layer, group: int = 1):
    """Write-and-attend for one layer of the stacked cache.

    ``q``/``k``/``v`` ``[B, H, W, dh]``: the window's queries and its new
    keys and values (``group`` > 1, grouped-query attention: ``H`` counts the
    key/value heads and ``q`` is ``[B, H, W * group, dh]``, window row ``j``'s
    ``group`` query heads at rows ``j * group ...``; they share the padded
    16-row operand a single query row leaves mostly empty); ``cache_k``/``cache_v`` ``[L, B, H, S, dh]``; ``pos``
    ``[B]`` int32, each slot's write index for window row 0; ``active``
    ``[B]`` bool (None: every slot) gates a slot's write — an inactive
    slot's rows stay bitwise untouched and its output is zero; ``layer`` the
    layer's index (a Python int or a traced scalar). Row j of the window
    attends the slot's rows up to ``pos + j``, the window's own included
    (write before attend). Returns ``(att [B, H, W, dh], cache_k, cache_v)``
    with the caches aliased to the inputs. The call is scoped ``attn_core``,
    like the lax formulation's attention."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, B, H, S, dh = cache_k.shape
    W, q_rows = k.shape[2], q.shape[2]
    heads, rows, tile = _plan(H, S, dh, cache_k.dtype)
    s_minor = _s_minor(dh)
    item = jnp.dtype(cache_k.dtype).itemsize
    wp = -(-q_rows // _sublanes(q.dtype)) * _sublanes(q.dtype)   # the window, padded to a packed sublane tile

    q = q * jnp.asarray(1.0 / (dh ** 0.5), q.dtype)       # as the lax formulation scales it
    q = jnp.pad(q, ((0, 0), (0, 0), (0, wp - q_rows), (0, 0)))
    if s_minor:
        # the cache in the order it is stored; the new rows as columns
        cache_k, cache_v = jnp.swapaxes(cache_k, -1, -2), jnp.swapaxes(cache_v, -1, -2)
        k, v = jnp.swapaxes(k, -1, -2), jnp.swapaxes(v, -1, -2)
        new_spec = pl.BlockSpec((None, heads, dh, W), lambda b, h, *_: (b, h, 0, 0))
        buf_shape = (2, heads, dh, rows)
    else:
        new_spec = pl.BlockSpec((None, heads, W, dh), lambda b, h, *_: (b, h, 0, 0))
        buf_shape = (2, heads, rows, dh)
    row_spec = pl.BlockSpec((None, heads, wp, dh), lambda b, h, *_: (b, h, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    active = jnp.ones((B,), jnp.int32) if active is None else active.astype(jnp.int32)
    n_tiles = _tiles_touched(W, tile)

    call = pl.pallas_call(
        functools.partial(_kernel, window=W, rows=rows, tile=tile, seq=S, s_minor=s_minor, group=int(group)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // heads),
            in_specs=[row_spec, new_spec, new_spec, whole, whole],
            out_specs=[row_spec, whole, whole],
            scratch_shapes=[pltpu.VMEM(buf_shape, cache_k.dtype), pltpu.VMEM(buf_shape, cache_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)), pltpu.SemaphoreType.DMA((2, n_tiles))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, wp, dh), q.dtype),
                   jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
                   jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype)],
        # operands: layer, pos, active, q, k, v, cache_k, cache_v
        input_output_aliases={6: 1, 7: 2},
        # XLA bills a custom call its whole operands: say what one call moves
        # (every slot's rows at full depth, the touched tiles written)
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * wp * S * dh,
            transcendentals=B * H * wp * S,
            bytes_accessed=2 * B * H * (S + n_tiles * tile) * dh * item + 4 * B * H * wp * dh * item),
        name="decode_attn",
        interpret=_INTERPRET,
    )
    with jax.named_scope("attn_core"):
        att, cache_k, cache_v = call(jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32), active,
                                     q, k, v, cache_k, cache_v)
    if s_minor:
        cache_k, cache_v = jnp.swapaxes(cache_k, -1, -2), jnp.swapaxes(cache_v, -1, -2)
    return att[:, :, :q_rows], cache_k, cache_v


registry.define_kernel(
    "decode_attention", cache_key=lambda: ("interpret", _INTERPRET, "mesh", _under_mesh()))
registry.register(
    "decode_attention", "pallas_aliased", decode_attention, available=decode_attention_available,
    doc="write-and-attend on the stacked cache, aliased in place (TPU, plain-array cache, no mesh)")

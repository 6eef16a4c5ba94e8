"""EVA attention's decode step over the serving engine's two row buffers: a
*window ring* of exact rows and a *summary table* of one pooled row a chunk.

EVA (arXiv:2302.04542, as EvaByte uses it) attends a query at position ``t``
in window ``w = t // W`` to two sets of rows with one softmax: the exact keys
and values of its own window up to itself, and one summary row for every
chunk of ``c`` rows of the windows before. A chunk's summary is its rows
pooled by a softmax under one learned vector ``pool`` a head (already scaled
by ``1 / sqrt(d)``):

    alpha_m = softmax_m(pool . k_m),   k~ = sum_m alpha_m k_m,   v~ = sum_m alpha_m v_m

Per layer a slot holds the ring ``[H, W, d]`` (row ``t mod W``: rows of an
earlier window are never attended, so nothing is zeroed) and the table ``[H,
S / c, d]`` (row ``t // c``). One decode step of one layer (:func:`eva_decode`):

1. writes the token's key and value into ring row ``pos mod W``;
2. where ``pos mod c == c - 1`` the token closes its chunk: the chunk's ``c``
   rows, read back from the ring (whoever wrote them), are pooled in float32
   and the summary goes into table row ``pos // c`` — attended only once the
   window has ended;
3. attends table rows ``[0, n_sum)`` and ring rows ``[0, n_ring)`` (the caller
   counts them: ``W / c`` summaries for each window before, and the window's
   rows up to the token's own) with one softmax, scores ``q . k / sqrt(d)`` and
   the running sums in float32.

Two implementations behind the registry (kernel ``eva_decode``):
``pallas_aliased`` on the TPU — per slot it reads and writes back only the
ring tile and the table tile the step writes, and streams only the blocks of
each range that hold live rows (the last block of a range is moved back to
end on the range's last tile, so no tile past either count is read), with both
buffers aliased to its outputs — and ``lax`` elsewhere (the CPU, a mesh),
which reads whole layers. Inactive slots are left bitwise as they were and
give zeros. The call is scoped ``eva_core``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry
from .decode_attention import _heads_dot, _sublanes, _under_mesh

__all__ = ["eva_decode", "eva_decode_lax", "eva_decode_pallas"]

_INTERPRET = False   # run the pallas_call in interpreter mode (CPU parity tests)
_LANES = 128
_BLOCK_ROWS = 128    # rows of a range streamed through VMEM at a time (all heads: 1 MiB of bf16 at H 32, d 128)


def set_interpret(on: bool) -> bool:
    """Route the ``pallas_call`` through the Pallas interpreter. Returns the
    prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def _plan(W: int, R: int, chunk: int, dtype):
    """(rows a streamed block, rows of the ring tile a write covers, rows of a
    table tile) for these buffers, or None if the kernel cannot tile them. A
    write covers a packed sublane tile and a whole chunk: the larger of the
    two, when one divides the other."""
    if jnp.dtype(dtype).itemsize not in (2, 4):
        return None
    tile = _sublanes(dtype)
    span = max(tile, chunk)
    if span % tile or span % chunk or W % span or R % tile:
        return None
    rows = tile
    while rows * 2 <= _BLOCK_ROWS and W % (rows * 2) == 0 and R % (rows * 2) == 0:
        rows *= 2
    return rows, span, tile


def eva_decode_available(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, *, chunk):
    """Registry predicate: a TPU (or interpret mode), no mesh, ``d`` in whole
    lanes, and buffers the kernel can tile."""
    if ring_k.ndim != 5 or sum_k.ndim != 5:
        return False
    _, _, _, W, d = ring_k.shape
    if jnp.dtype(ring_k.dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if _plan(W, sum_k.shape[3], chunk, ring_k.dtype) is None:
        return False
    if _INTERPRET:
        return True
    from ..device import is_tpu

    return d % _LANES == 0 and is_tpu() and not _under_mesh()


# ------------------------------------------------------------------ lax
def eva_decode_lax(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, *, chunk):
    """The step in ``jax.numpy``: ``q``, ``k``, ``v`` ``[B, H, d]``; ``ring_*``
    ``[L, B, H, W, d]``; ``sum_*`` ``[L, B, H, S / chunk, d]``; ``pos``,
    ``n_sum``, ``n_ring`` ``[B]`` int32; ``active [B]`` bool; ``layer`` an
    int or a traced scalar; ``pool [H, d]`` float32. Returns ``(att [B, H,
    d] in q's dtype, ring_k, ring_v, sum_k, sum_v)``."""
    _, B, H, W, d = ring_k.shape
    R = sum_k.shape[3]
    slots = jnp.arange(B)
    row, c = pos % W, pos // chunk
    first = (row // chunk) * chunk
    closing = active & (pos % chunk == chunk - 1)

    def write(buf, new):
        lay = jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)                     # [B, H, W, d]
        old = lay[slots, :, row]
        return lay.at[slots, :, row].set(jnp.where(active[:, None, None], new.astype(lay.dtype), old))

    rk, rv = write(ring_k, k), write(ring_v, v)

    def chunk_rows(lay):
        return jax.vmap(lambda x, f: jax.lax.dynamic_slice_in_dim(x, f, chunk, axis=1))(lay, first).astype(jnp.float32)

    kc, vc = chunk_rows(rk), chunk_rows(rv)                                                   # [B, H, chunk, d]
    alpha = jax.nn.softmax(jnp.einsum("bhcd,hd->bhc", kc, pool.astype(jnp.float32)), axis=-1)

    def pool_into(buf, rows):
        lay = jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)                     # [B, H, R, d]
        summary = jnp.einsum("bhc,bhcd->bhd", alpha, rows).astype(lay.dtype)
        old = lay[slots, :, c % R]
        return lay.at[slots, :, c % R].set(jnp.where(closing[:, None, None], summary, old))

    sk, sv = pool_into(sum_k, kc), pool_into(sum_v, vc)

    scale = 1.0 / (d ** 0.5)
    s_sum = jnp.einsum("bhd,bhrd->bhr", q, sk, preferred_element_type=jnp.float32) * scale
    s_ring = jnp.einsum("bhd,bhrd->bhr", q, rk, preferred_element_type=jnp.float32) * scale
    s_sum = jnp.where(jnp.arange(R)[None, None] < n_sum[:, None, None], s_sum, -jnp.inf)
    s_ring = jnp.where(jnp.arange(W)[None, None] < n_ring[:, None, None], s_ring, -jnp.inf)
    m = jnp.maximum(jnp.max(s_sum, axis=-1, keepdims=True), jnp.max(s_ring, axis=-1, keepdims=True))
    p_sum, p_ring = jnp.exp(s_sum - m), jnp.exp(s_ring - m)
    l = jnp.sum(p_sum, axis=-1, keepdims=True) + jnp.sum(p_ring, axis=-1, keepdims=True)
    acc = (jnp.einsum("bhr,bhrd->bhd", p_sum.astype(sv.dtype), sv, preferred_element_type=jnp.float32)
           + jnp.einsum("bhr,bhrd->bhd", p_ring.astype(rv.dtype), rv, preferred_element_type=jnp.float32))
    att = jnp.where(active[:, None, None], acc / l, 0.0).astype(q.dtype)
    put = lambda buf, lay: jax.lax.dynamic_update_index_in_dim(buf, lay, layer, 0)  # noqa: E731
    return att, put(ring_k, rk), put(ring_v, rv), put(sum_k, sk), put(sum_v, sv)


# ------------------------------------------------------------------ pallas
def _kernel(layer_ref, pos_ref, act_ref, nsum_ref, nring_ref, q_ref, kn_ref, vn_ref, pool_ref,
            rk_in, rv_in, sk_in, sv_in, o_ref, rk_out, rv_out, sk_out, sv_out,
            kbuf, vbuf, rtk, rtv, stk, stv, rsem, tsem, *, rows, span, tile, chunk, window, table):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    heads, wp, d = q_ref.shape
    layer, pos = layer_ref[0], pos_ref[b]
    at = lambda ref, start, size: ref.at[layer, b, :, pl.ds(start, size)]  # noqa: E731

    @pl.when(act_ref[b] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(act_ref[b] != 0)
    def _():
        row, c = pos % window, pos // chunk
        r0 = pl.multiple_of((row // span) * span, span)                 # the ring tile the token's row falls in
        t0 = pl.multiple_of(((c % table) // tile) * tile, tile)         # the table tile its chunk's summary falls in
        reads = [pltpu.make_async_copy(at(src, r0, span), dst, tsem.at[i]) for i, (src, dst) in enumerate(((rk_in, rtk), (rv_in, rtv)))]
        reads += [pltpu.make_async_copy(at(src, t0, tile), dst, tsem.at[2 + i]) for i, (src, dst) in enumerate(((sk_in, stk), (sv_in, stv)))]
        for dma in reads:
            dma.start()
        for dma in reads:
            dma.wait()

        # 1. the token's row into its ring tile, and the tile back
        where = r0 + jax.lax.broadcasted_iota(jnp.int32, (heads, span, d), 1)

        def put_row(buf, new):
            buf[...] = jnp.where(where == row, new[...].astype(buf.dtype), buf[...])

        put_row(rtk, kn_ref)
        put_row(rtv, vn_ref)
        writes = [pltpu.make_async_copy(src, at(dst, r0, span), tsem.at[4 + i]) for i, (src, dst) in enumerate(((rtk, rk_out), (rtv, rv_out)))]

        # 2. a closing chunk's summary: its rows of the tile (the token's own among them) pooled in float32
        closing = pos % chunk == chunk - 1

        @pl.when(closing)
        def _():
            kt, vt = rtk[...].astype(jnp.float32), rtv[...].astype(jnp.float32)
            logits = jnp.sum(kt * pool_ref[...], axis=-1, keepdims=True)                    # [heads, span, 1]
            ours = (r0 + jax.lax.broadcasted_iota(jnp.int32, (heads, span, 1), 1)) // chunk == row // chunk
            logits = jnp.where(ours, logits, -jnp.inf)
            e = jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))
            alpha = e / jnp.sum(e, axis=1, keepdims=True)
            at_row = (t0 + jax.lax.broadcasted_iota(jnp.int32, (heads, tile, d), 1)) == c % table

            def put_summary(buf, rows_):
                summary = jnp.sum(alpha * rows_, axis=1, keepdims=True)                     # [heads, 1, d]
                buf[...] = jnp.where(at_row, summary.astype(buf.dtype), buf[...])

            put_summary(stk, kt)
            put_summary(stv, vt)

        for dma in writes:
            dma.start()
        sum_writes = [pltpu.make_async_copy(src, at(dst, t0, tile), tsem.at[6 + i]) for i, (src, dst) in enumerate(((stk, sk_out), (stv, sv_out)))]

        @pl.when(closing)
        def _():
            for dma in sum_writes:
                dma.start()

        for dma in writes:
            dma.wait()

        @pl.when(closing)
        def _():
            for dma in sum_writes:
                dma.wait()

        # 3. both ranges, one online softmax; a range's blocks stream double-buffered, the last one ending on the
        # range's last tile
        scale = 1.0 / (d ** 0.5)

        def attend(kin, vin, n, carry):
            n_blocks = (n + rows - 1) // rows
            last = jnp.maximum(((n + tile - 1) // tile) * tile - rows, 0)

            def start_of(blk):
                return pl.multiple_of(jnp.minimum(blk * rows, last), tile)

            def fetch(blk, slot):
                return [pltpu.make_async_copy(at(src, start_of(blk), rows), buf.at[slot], rsem.at[i, slot])
                        for i, (src, buf) in enumerate(((kin, kbuf), (vin, vbuf)))]

            @pl.when(n_blocks > 0)
            def _():
                for dma in fetch(0, 0):
                    dma.start()

            def body(blk, carry):
                m, l, acc = carry
                slot = blk % 2

                @pl.when(blk + 1 < n_blocks)
                def _():
                    for dma in fetch(blk + 1, 1 - slot):
                        dma.start()

                for dma in fetch(blk, slot):
                    dma.wait()
                kb, vb = kbuf[slot], vbuf[slot]
                s = _heads_dot(q_ref[...], kb, ((2,), (2,))) * scale                         # [heads, wp, rows]
                idx = start_of(blk) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
                s = jnp.where((idx < n) & (idx >= blk * rows), s, -jnp.inf)                  # a moved-back block's earlier rows are counted once
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + _heads_dot(p.astype(vb.dtype), vb, ((2,), (1,)))
                return m_new, l, acc

            return jax.lax.fori_loop(0, n_blocks, body, carry)

        init = (jnp.full((heads, wp, 1), -jnp.inf, jnp.float32), jnp.zeros((heads, wp, 1), jnp.float32),
                jnp.zeros((heads, wp, d), jnp.float32))
        carry = attend(sk_out, sv_out, nsum_ref[b], init)
        _, l, acc = attend(rk_out, rv_out, nring_ref[b], carry)
        o_ref[...] = (acc / l).astype(o_ref.dtype)


def eva_decode_pallas(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, *, chunk):
    """:func:`eva_decode_lax`'s step as one Pallas kernel a layer, both buffers
    aliased to its outputs (the arguments and result alike)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, B, H, W, d = ring_k.shape
    R = sum_k.shape[3]
    rows, span, tile = _plan(W, R, chunk, ring_k.dtype)
    item = jnp.dtype(ring_k.dtype).itemsize
    wp = _sublanes(q.dtype)                                              # the query row, padded to a packed sublane tile
    qp = jnp.pad(q[:, :, None], ((0, 0), (0, 0), (0, wp - 1), (0, 0)))
    row_spec = pl.BlockSpec((None, H, wp, d), lambda b, *_: (b, 0, 0, 0))
    new_spec = pl.BlockSpec((None, H, 1, d), lambda b, *_: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec((H, 1, d), lambda b, *_: (0, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    active = jnp.ones((B,), jnp.int32) if active is None else active.astype(jnp.int32)
    dt = ring_k.dtype
    call = pl.pallas_call(
        functools.partial(_kernel, rows=rows, span=span, tile=tile, chunk=int(chunk), window=W, table=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[row_spec, new_spec, new_spec, pool_spec, whole, whole, whole, whole],
            out_specs=[row_spec, whole, whole, whole, whole],
            scratch_shapes=[pltpu.VMEM((2, H, rows, d), dt), pltpu.VMEM((2, H, rows, d), dt),
                            pltpu.VMEM((H, span, d), dt), pltpu.VMEM((H, span, d), dt),
                            pltpu.VMEM((H, tile, d), dt), pltpu.VMEM((H, tile, d), dt),
                            pltpu.SemaphoreType.DMA((2, 2)), pltpu.SemaphoreType.DMA((8,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, wp, d), q.dtype)] + [jax.ShapeDtypeStruct(a.shape, a.dtype)
                                                                   for a in (ring_k, ring_v, sum_k, sum_v)],
        # operands: layer, pos, active, n_sum, n_ring, q, k, v, pool, ring_k, ring_v, sum_k, sum_v
        input_output_aliases={9: 1, 10: 2, 11: 3, 12: 4},
        # XLA bills a custom call its whole operands: say what one call moves at most (every slot's ranges whole, its
        # two tiles read and written)
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * wp * (W + R) * d, transcendentals=B * H * wp * (W + R),
            bytes_accessed=2 * B * H * (W + R + 2 * span + 2 * tile) * d * item),
        name="eva_decode",
        interpret=_INTERPRET,
    )
    att, ring_k, ring_v, sum_k, sum_v = call(
        jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32), active, n_sum.astype(jnp.int32),
        n_ring.astype(jnp.int32), qp, k[:, :, None], v[:, :, None], pool.astype(jnp.float32)[:, None],
        ring_k, ring_v, sum_k, sum_v)
    return att[:, :, 0], ring_k, ring_v, sum_k, sum_v


def eva_decode(q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring, *, chunk):
    """One layer's decode step (module docstring) through the registry, scoped
    ``eva_core``: ``(att [B, H, d], ring_k, ring_v, sum_k, sum_v)``."""
    args = (q, k, v, ring_k, ring_v, sum_k, sum_v, pos, active, layer, pool, n_sum, n_ring)
    with jax.named_scope("eva_core"):
        return registry.select("eva_decode", *args, chunk=int(chunk)).fn(*args, chunk=int(chunk))


registry.define_kernel("eva_decode", cache_key=lambda: ("interpret", _INTERPRET, "mesh", _under_mesh()))
registry.register("eva_decode", "pallas_aliased", eva_decode_pallas, available=eva_decode_available,
                  doc="EVA decode: ring write, chunk summary and one softmax over live summary and ring tiles, aliased (TPU, no mesh)")
registry.register("eva_decode", "lax", eva_decode_lax, fallback=True,
                  doc="EVA decode in jax.numpy over whole layers of the ring and the table (any device)")

"""Fused sort-based MoE dispatch/combine — the Pallas kernel tier's MoE op.

Parity target: the reference's MoE hot path (incubate/distributed/models/moe
``global_scatter``/``global_gather`` collectives + per-expert FFNs,
operators/collective/global_scatter_op.cu.cc). The dense GShard composite in
:mod:`paddle_tpu.distributed.moe` routes with a ``[T·K, E]`` one-hot +
cumsum (O(T·K·E) work) and pushes a padded ``[E, capacity, D]`` dispatch
buffer plus its ``[E, capacity, H]`` hidden activations through HBM on every
step. This module replaces that with:

1. **dispatch**: a stable argsort of the T·K (token, expert) pairs by
   expert id — O(TK·log TK) — yielding contiguous per-expert token runs;
   each pair's queue position is its offset from the run start (a
   length-E cumsum), so capacity dropping keeps the dense path's exact
   arrival-order semantics without the [T·K, E] cumsum.
2. **expert FFN**: ONE fused Pallas grouped-matmul kernel over the sorted
   runs — both projections and the activation per row block, streamed over
   H tiles, hidden activations living only in VMEM. The expert weights for
   a block are chosen by static grid arithmetic (each expert's run is
   padded to a whole number of row blocks), so there is no gather inside
   the kernel and no [rows, H] hidden buffer in HBM.
3. **combine**: a weighted scatter-add back to token order.

A ``custom_vjp`` makes it train: the backward is a Pallas kernel pair (a
dx/db2 kernel and a dw1/db1/dw2 kernel, mirroring the flash-attention
dq / dk-dv split so every output block is revisited only on consecutive
grid steps) that recomputes the hidden activations in VMEM instead of
saving them. Everything runs under the Pallas interpreter via
:func:`set_interpret` so CPU tier-1 pins fwd+grad parity against the dense
composite without a TPU.

Registered as implementation ``pallas_sorted`` of the ``moe`` kernel; the
dense composite registers itself as the ``dense`` fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry
from .flash_attention import _dot32

_BLOCK_ROWS = 128  # row-block (tokens) per grid step; experts pad to a multiple
_BLOCK_H = 512     # hidden tile streamed through VMEM
_INTERPRET = False

__all__ = ["moe_dispatch_combine", "moe_available", "set_interpret"]


def set_interpret(on: bool) -> bool:
    """Route the MoE ``pl.pallas_call``s through the Pallas interpreter —
    the CPU path tier-1 uses to pin kernel math against the dense
    composite without a TPU. Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block_sizes(capacity: int, hidden: int):
    """(row block, padded per-expert capacity, hidden tile). The row block
    adapts down for tiny capacities (tests) and the hidden tile to the
    largest 128-multiple divisor so big-H weights stream instead of
    needing a whole [D, H] residency in VMEM. Interpreter mode (no VMEM)
    takes whole-expert row blocks — fewer, larger grid steps."""
    if _INTERPRET:
        # whole-expert row blocks + untiled hidden: no VMEM bound off-TPU,
        # and the whole-problem shape routes _grouped_ffn through the
        # identical-math XLA reference lowering (the interpreter's
        # per-call ref-emulation tax would otherwise dominate)
        bm = _round_up(capacity, 8)
        return bm, bm, hidden
    bm = min(_BLOCK_ROWS, _round_up(capacity, 8))
    cap = _round_up(capacity, bm)
    if hidden <= _BLOCK_H:
        bh = hidden
    else:
        bh = max(b for b in (512, 256, 128) if hidden % b == 0)
    return bm, cap, bh


# The v5e compiler's default scoped-VMEM limit is 16 MiB per kernel; the
# weight-gradient kernel's tile is sized against it with a margin for what
# the estimate in _dw_hidden_tile leaves out.
_DW_VMEM_BUDGET = 15 << 20


def _dw_hidden_tile(bm: int, D: int, bh: int, itemsize: int) -> int:
    """Hidden tile for the weight-gradient kernel: the largest 128-multiple
    divisor of the forward tile ``bh`` whose working set fits the scoped
    VMEM budget. Unlike the forward and dx kernels, whose f32 blocks are
    [bm, ·], this kernel's f32 blocks are [D, t] — double-buffered dw1/dw2
    outputs plus their partials, seven such blocks in the compiler's own
    accounting — so at D = 1024 the forward's 512-wide tile is refused
    (16.32M bf16 / 18.00M f32 of the 16.00M limit) where 256 compiles. The
    coefficients are fitted to the sizes the v5e compiler reported at
    (D, t) = (1024, 512) and (2048, 256); tests/test_chip_compile.py holds
    the flagship width to it."""
    t = bh
    while t % 256 == 0:  # t/2 stays a legal 128-multiple lane block
        need = 7 * D * t * 4 + D * t * itemsize + 4 * bm * D * itemsize
        if need <= _DW_VMEM_BUDGET:
            break
        t //= 2
    return t


def _under_mesh() -> bool:
    from ..distributed.fleet import fleet

    return fleet.multi_device_mesh is not None


def moe_available(tokens, gate_vals, gate_idx, drop_mask, w1, b1, w2, b2, *,
                  capacity, activation) -> bool:
    """Availability predicate for the registry: interpret mode accepts any
    shape (the interpreter has no tiling constraints); on a TPU backend the
    model dims must be lane-aligned and the capacity big enough that row
    blocks are MXU-shaped."""
    E, D, H = (int(s) for s in w1.shape)
    if jnp.dtype(tokens.dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if H > _BLOCK_H and all(H % b for b in (512, 256, 128)):
        return False
    if _INTERPRET:
        return True
    from ..device import is_tpu

    if not is_tpu():
        return False
    if _under_mesh():
        # the dispatch is not written per shard: under a mesh the dense
        # composite, which GSPMD partitions, takes the call
        return False
    return D % 128 == 0 and H % 128 == 0 and capacity >= 8


# -- fused grouped-FFN kernels ----------------------------------------------


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _ffn_fwd_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, *, act, nh):
    """One (row block, hidden tile) cell: y += act(x @ w1_t + b1_t) @ w2_t,
    accumulated in the f32 output across the (inner) hidden-tile axis. The
    hidden activations never leave VMEM; the backward recomputes them
    (flash-style — HBM traffic, not flops, bounds the TPU hot path)."""
    from jax.experimental import pallas as pl

    hb = pl.program_id(1)
    x = x_ref[...]  # [bm, D]
    s = _dot32(x, w1_ref[...], _NN) + b1_ref[...]  # [bm, bh] f32
    h = act(s)
    part = _dot32(h.astype(x.dtype), w2_ref[...], _NN)  # [bm, D] f32

    @pl.when(hb == 0)
    def _init():
        o_ref[...] = part + b2_ref[...]

    @pl.when(hb > 0)
    def _acc():
        o_ref[...] += part


def _ffn_fwd_small_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, s_ref, *, act):
    """Single-hidden-tile forward (nh == 1): the pre-activation fits one
    block, so it is written out as the vjp residual — the backward then
    recomputes only the elementwise activation, matching the autodiffed
    composite's matmul count (the right trade on CPU interpret and small
    H, where flops beat HBM traffic as the bound)."""
    x = x_ref[...]
    s = _dot32(x, w1_ref[...], _NN) + b1_ref[...]
    s_ref[...] = s
    h = act(s)
    o_ref[...] = (_dot32(h.astype(x.dtype), w2_ref[...], _NN)
                  + b2_ref[...]).astype(o_ref.dtype)


def _reference_ffn_fwd(xg, w1, b1, w2, b2, act, E, cap):
    """Off-TPU lowering of the grouped FFN: the SAME math as the kernels
    (per-expert x@w1+b1 → act → @w2+b2 over the sorted/padded layout, f32
    accumulation, s saved as the vjp residual) as plain batched einsums.
    The Pallas interpreter pays a fixed ref-emulation/copy tax per call
    that swamps problems this small, so the interpret-mode registry path
    runs this lowering; the interpreted kernels themselves are pinned
    against it (and against the dense composite) by the tier-1 tests."""
    R, D = xg.shape
    xs = xg.reshape(E, cap, D)
    s = jnp.einsum("ecd,edh->ech", xs, w1, preferred_element_type=jnp.float32) + b1
    h = act(s)
    y = jnp.einsum("ech,ehd->ecd", h.astype(xg.dtype), w2,
                   preferred_element_type=jnp.float32) + b2
    return y.reshape(R, D).astype(xg.dtype), s


def _reference_ffn_bwd(xg, w1, b1, w2, b2, s, dy, act, E, cap):
    R, D = xg.shape
    xs = xg.reshape(E, cap, D)
    dys = dy.reshape(E, cap, D).astype(xg.dtype)
    h, act_vjp = jax.vjp(act, s)
    dp = jnp.einsum("ecd,ehd->ech", dys, w2, preferred_element_type=jnp.float32)
    dh = act_vjp(dp)[0]
    dx = jnp.einsum("ech,edh->ecd", dh.astype(xg.dtype), w1,
                    preferred_element_type=jnp.float32)
    dw1 = jnp.einsum("ecd,ech->edh", xs, dh.astype(xg.dtype),
                     preferred_element_type=jnp.float32)
    db1 = jnp.sum(dh, axis=1, keepdims=True)
    dw2 = jnp.einsum("ech,ecd->ehd", h.astype(xg.dtype), dys,
                     preferred_element_type=jnp.float32)
    db2 = jnp.sum(dys.astype(jnp.float32), axis=1, keepdims=True)
    return (dx.reshape(R, D).astype(xg.dtype), dw1.astype(w1.dtype),
            db1.astype(b1.dtype), dw2.astype(w2.dtype), db2.astype(b2.dtype))


def _ffn_bwd_fused_kernel(x_ref, dy_ref, s_ref, w1_ref, w2_ref,
                          dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref, *, act, bpe):
    """Single-hidden-tile backward (nh == 1): with no hidden-tile axis in
    the grid, dx (per row block) and the weight grads (per expert,
    consecutive row blocks) coexist in ONE kernel, fed by the saved
    pre-activation — only the elementwise activation is recomputed. The
    tiled two-kernel pair below handles nh > 1 with full recompute."""
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    x = x_ref[...]
    dy = dy_ref[...]
    h, act_vjp = jax.vjp(act, s_ref[...])
    dp = _dot32(dy, w2_ref[...], _NT)
    dh = act_vjp(dp)[0]
    dx_ref[...] = _dot32(dh.astype(x.dtype), w1_ref[...], _NT)
    dw1_p = _dot32(x, dh.astype(x.dtype), _TN)
    db1_p = jnp.sum(dh, axis=0, keepdims=True)
    dw2_p = _dot32(h.astype(x.dtype), dy, _TN)
    db2_p = jnp.sum(dy.astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(g % bpe == 0)
    def _init():
        dw1_ref[...] = dw1_p
        db1_ref[...] = db1_p
        dw2_ref[...] = dw2_p
        db2_ref[...] = db2_p

    @pl.when(g % bpe > 0)
    def _acc():
        dw1_ref[...] += dw1_p
        db1_ref[...] += db1_p
        dw2_ref[...] += dw2_p
        db2_ref[...] += db2_p


def _ffn_bwd_dx_kernel(x_ref, dy_ref, w1_ref, b1_ref, w2_ref, dx_ref, db2_ref, *, act, bpe):
    """dx = (act'(s) ∘ (dy @ w2ᵀ)) @ w1ᵀ accumulated over hidden tiles
    (inner axis); db2 = Σ_rows dy accumulated over the expert's row blocks
    (outer axis) — both outputs only ever revisited on consecutive steps."""
    from jax.experimental import pallas as pl

    g, hb = pl.program_id(0), pl.program_id(1)
    x = x_ref[...]
    dy = dy_ref[...]
    s = _dot32(x, w1_ref[...], _NN) + b1_ref[...]
    _, act_vjp = jax.vjp(act, s)
    dp = _dot32(dy, w2_ref[...], _NT)  # [bm, bh]
    dh = act_vjp(dp)[0]
    part = _dot32(dh.astype(x.dtype), w1_ref[...], _NT)  # [bm, D]

    @pl.when(hb == 0)
    def _init_dx():
        dx_ref[...] = part

    @pl.when(hb > 0)
    def _acc_dx():
        dx_ref[...] += part

    dy_sum = jnp.sum(dy.astype(jnp.float32), axis=0, keepdims=True)

    @pl.when((g % bpe == 0) & (hb == 0))
    def _init_db2():
        db2_ref[...] = dy_sum

    @pl.when((g % bpe > 0) & (hb == 0))
    def _acc_db2():
        db2_ref[...] += dy_sum


def _ffn_bwd_dw_kernel(x_ref, dy_ref, w1_ref, b1_ref, w2_ref,
                       dw1_ref, db1_ref, dw2_ref, *, act, bpe):
    """Weight grads per (hidden tile, expert) block, accumulated over the
    expert's row blocks — the grid runs hidden tiles OUTER / row blocks
    INNER so each dw block's revisits are consecutive."""
    from jax.experimental import pallas as pl

    g = pl.program_id(1)
    x = x_ref[...]
    dy = dy_ref[...]
    s = _dot32(x, w1_ref[...], _NN) + b1_ref[...]
    h, act_vjp = jax.vjp(act, s)
    dp = _dot32(dy, w2_ref[...], _NT)
    dh = act_vjp(dp)[0]
    dw1_p = _dot32(x, dh.astype(x.dtype), _TN)          # [D, bh]
    db1_p = jnp.sum(dh, axis=0, keepdims=True)          # [1, bh]
    dw2_p = _dot32(h.astype(x.dtype), dy, _TN)          # [bh, D]

    @pl.when(g % bpe == 0)
    def _init():
        dw1_ref[...] = dw1_p
        db1_ref[...] = db1_p
        dw2_ref[...] = dw2_p

    @pl.when(g % bpe > 0)
    def _acc():
        dw1_ref[...] += dw1_p
        db1_ref[...] += db1_p
        dw2_ref[...] += dw2_p


def _row_specs(bm, D, order):
    """BlockSpecs for the [rows, D] operands; ``order`` maps grid ids to
    (row block, hidden tile) — (g, hb) for the fwd/dx grids, (hb, g) for
    the dw grid."""
    from jax.experimental import pallas as pl

    g_of = (lambda a, b: a) if order == "g_outer" else (lambda a, b: b)
    return pl.BlockSpec((bm, D), lambda a, b, _g=g_of: (_g(a, b), 0))


def _expert_specs(D, bh, bpe, order):
    """BlockSpecs for the per-expert weight operands (w1/b1/w2): expert =
    row block // blocks-per-expert — static grid arithmetic, no gather."""
    from jax.experimental import pallas as pl

    if order == "g_outer":
        e_of, h_of = (lambda a, b: a // bpe), (lambda a, b: b)
    else:
        e_of, h_of = (lambda a, b: b // bpe), (lambda a, b: a)
    return [
        pl.BlockSpec((None, D, bh), lambda a, b: (e_of(a, b), 0, h_of(a, b))),
        pl.BlockSpec((None, 1, bh), lambda a, b: (e_of(a, b), 0, h_of(a, b))),
        pl.BlockSpec((None, bh, D), lambda a, b: (e_of(a, b), h_of(a, b), 0)),
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_ffn(xg, w1, b1, w2, b2, act, bm, bh):
    """act(xg @ w1[e] + b1[e]) @ w2[e] + b2[e] where e = row // (rows per
    expert); xg is the sorted/padded [E*cap, D] dispatch layout."""
    y, _ = _grouped_ffn_fwd(xg, w1, b1, w2, b2, act, bm, bh)
    return y


def _grouped_ffn_fwd(xg, w1, b1, w2, b2, act, bm, bh):
    from jax.experimental import pallas as pl

    R, D = xg.shape
    E, _, H = w1.shape
    bpe = (R // E) // bm
    nh = H // bh
    b2f = b2.astype(jnp.float32)
    if _INTERPRET and bpe == 1 and nh == 1:
        y, s = _reference_ffn_fwd(xg, w1, b1, w2, b2, act, E, R // E)
        return y, (xg, w1, b1, w2, b2, s)
    if nh == 1:
        y, s = pl.pallas_call(
            functools.partial(_ffn_fwd_small_kernel, act=act),
            grid=(R // bm,),
            in_specs=[
                pl.BlockSpec((bm, D), lambda g: (g, 0)),
                pl.BlockSpec((None, D, H), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, 1, H), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, H, D), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, 1, D), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, D), lambda g: (g, 0)),
                pl.BlockSpec((bm, H), lambda g: (g, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((R, D), xg.dtype),
                jax.ShapeDtypeStruct((R, H), jnp.float32),
            ],
            name="moe_fwd_small",
            interpret=_INTERPRET,
        )(xg, w1, b1, w2, b2f)
        return y, (xg, w1, b1, w2, b2, s)
    y = pl.pallas_call(
        functools.partial(_ffn_fwd_kernel, act=act, nh=nh),
        grid=(R // bm, nh),
        in_specs=[_row_specs(bm, D, "g_outer")] + _expert_specs(D, bh, bpe, "g_outer") + [
            pl.BlockSpec((None, 1, D), lambda g, hb, _bpe=bpe: (g // _bpe, 0, 0)),
        ],
        out_specs=_row_specs(bm, D, "g_outer"),
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        name="moe_fwd",
        interpret=_INTERPRET,
    )(xg, w1, b1, w2, b2f)
    return y.astype(xg.dtype), (xg, w1, b1, w2, b2, None)


def _grouped_ffn_bwd(act, bm, bh, res, dy):
    from jax.experimental import pallas as pl

    xg, w1, b1, w2, b2, s_res = res
    R, D = xg.shape
    E, _, H = w1.shape
    bpe = (R // E) // bm
    nh = H // bh
    dyc = dy.astype(xg.dtype)

    if _INTERPRET and bpe == 1 and nh == 1:
        return _reference_ffn_bwd(xg, w1, b1, w2, b2, s_res, dy, act, E, R // E)

    if nh == 1:
        dx, dw1, db1, dw2, db2 = pl.pallas_call(
            functools.partial(_ffn_bwd_fused_kernel, act=act, bpe=bpe),
            grid=(R // bm,),
            in_specs=[
                pl.BlockSpec((bm, D), lambda g: (g, 0)),
                pl.BlockSpec((bm, D), lambda g: (g, 0)),
                pl.BlockSpec((bm, H), lambda g: (g, 0)),
                pl.BlockSpec((None, D, H), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, H, D), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, D), lambda g: (g, 0)),
                pl.BlockSpec((None, D, H), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, 1, H), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, H, D), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
                pl.BlockSpec((None, 1, D), lambda g, _bpe=bpe: (g // _bpe, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((R, D), jnp.float32),
                jax.ShapeDtypeStruct((E, D, H), jnp.float32),
                jax.ShapeDtypeStruct((E, 1, H), jnp.float32),
                jax.ShapeDtypeStruct((E, H, D), jnp.float32),
                jax.ShapeDtypeStruct((E, 1, D), jnp.float32),
            ],
            name="moe_bwd_fused",
            interpret=_INTERPRET,
        )(xg, dyc, s_res, w1, w2)
        return (dx.astype(xg.dtype), dw1.astype(w1.dtype), db1.astype(b1.dtype),
                dw2.astype(w2.dtype), db2.astype(b2.dtype))

    dx, db2 = pl.pallas_call(
        functools.partial(_ffn_bwd_dx_kernel, act=act, bpe=bpe),
        grid=(R // bm, nh),
        in_specs=[_row_specs(bm, D, "g_outer"), _row_specs(bm, D, "g_outer")]
        + _expert_specs(D, bh, bpe, "g_outer"),
        out_specs=[
            _row_specs(bm, D, "g_outer"),
            pl.BlockSpec((None, 1, D), lambda g, hb, _bpe=bpe: (g // _bpe, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), jnp.float32),
            jax.ShapeDtypeStruct((E, 1, D), jnp.float32),
        ],
        name="moe_bwd_dx",
        interpret=_INTERPRET,
    )(xg, dyc, w1, b1, w2)

    bw = _dw_hidden_tile(bm, D, bh, jnp.dtype(xg.dtype).itemsize)
    dw1, db1, dw2 = pl.pallas_call(
        functools.partial(_ffn_bwd_dw_kernel, act=act, bpe=bpe),
        grid=(H // bw, R // bm),
        in_specs=[_row_specs(bm, D, "hb_outer"), _row_specs(bm, D, "hb_outer")]
        + _expert_specs(D, bw, bpe, "hb_outer"),
        out_specs=[
            pl.BlockSpec((None, D, bw), lambda hb, g, _bpe=bpe: (g // _bpe, 0, hb)),
            pl.BlockSpec((None, 1, bw), lambda hb, g, _bpe=bpe: (g // _bpe, 0, hb)),
            pl.BlockSpec((None, bw, D), lambda hb, g, _bpe=bpe: (g // _bpe, hb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, D, H), jnp.float32),
            jax.ShapeDtypeStruct((E, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((E, H, D), jnp.float32),
        ],
        name="moe_bwd_dw",
        interpret=_INTERPRET,
    )(xg, dyc, w1, b1, w2)

    return (dx.astype(xg.dtype), dw1.astype(w1.dtype), db1.astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(b2.dtype))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


# -- public op ---------------------------------------------------------------


def moe_dispatch_combine(tokens, gate_vals, gate_idx, drop_mask, w1, b1, w2, b2, *,
                         capacity, activation):
    """Sort-based dispatch → fused grouped FFN → weighted combine.

    tokens [T, D]; gate_vals/gate_idx [T, K] (top-k routing, k-major per
    token); drop_mask [T, K] bool or None (True = pair not dispatched, e.g.
    GShard random routing — it consumes no capacity); w1 [E, D, H], b1
    [E, 1, H], w2 [E, H, D], b2 [E, 1, D]. ``capacity`` is the per-expert
    token budget; overflow drops in arrival order, exactly matching the
    dense composite. Returns [T, D].
    """
    T, D = tokens.shape
    E, _, H = (int(s) for s in w1.shape)
    K = gate_idx.shape[1]
    N = T * K
    bm, cap, bh = _block_sizes(int(capacity), H)

    flat_e = gate_idx.reshape(-1).astype(jnp.int32)
    if drop_mask is not None:
        # dropped pairs sort past every real expert and never claim a slot
        flat_e = jnp.where(drop_mask.reshape(-1), E, flat_e)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    e_sorted = flat_e[order]
    tok_sorted = (order // K).astype(jnp.int32)
    gv_sorted = gate_vals.reshape(-1)[order]

    counts = jnp.bincount(flat_e, length=E + 1)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)  # [E+1] run starts
    pos = jnp.arange(N, dtype=jnp.int32) - starts[e_sorted]
    keep = (e_sorted < E) & (pos < capacity)
    slot = e_sorted * cap + pos

    # dispatch: one scatter of token ids + one gather of token rows
    # (row E*cap and token row T are the write-off lanes for dropped pairs)
    row_ids = jnp.full((E * cap,), T, jnp.int32)
    row_ids = row_ids.at[jnp.where(keep, slot, E * cap)].set(tok_sorted, mode="drop")
    xg = jnp.concatenate([tokens, jnp.zeros((1, D), tokens.dtype)])[row_ids]

    yg = _grouped_ffn(xg, w1, b1, w2, b2, activation, bm, bh)

    # combine: weighted scatter-add back to token order
    weights = jnp.where(keep, gv_sorted, jnp.zeros_like(gv_sorted))
    gathered = yg[jnp.where(keep, slot, 0)] * weights[:, None].astype(yg.dtype)
    return jnp.zeros((T, D), yg.dtype).at[tok_sorted].add(gathered)


registry.define_kernel(
    "moe", cache_key=lambda: ("interpret", _INTERPRET, "mesh", _under_mesh()))
registry.register(
    "moe", "pallas_sorted", moe_dispatch_combine, available=moe_available,
    doc="sort-based dispatch + fused Pallas grouped-FFN (TPU, or interpret mode)")

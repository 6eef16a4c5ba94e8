"""Flash attention: full Pallas TPU kernel pair (fwd + bwd) + jnp fallback.

Parity target: the reference's fused attention CUDA path
(paddle/fluid/operators/fused/fused_attention_op.cu,
fused_softmax_mask.cu.h). TPU-first: an online-softmax blocked kernel that
streams K/V tiles through VMEM, fp32 accumulation, MXU-shaped tiles.

The backward is a hand-written flash-attention-2 style kernel pair
(dq kernel + dk/dv kernel) over compact [b, h, s] f32 logsumexp/di
residuals. The jax library kernels (pallas/ops/tpu/flash_attention.py)
broadcast their per-row stats to [b, h, s, 128] and [b, h, s, block_k]
f32 tensors in HBM before every backward call — profiled at >20ms/step on
the flagship bench; these kernels keep the stats 1-D and recompute p
tiles in VMEM, which is what makes the fused step ~1.25x faster.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_BLOCK_Q = 512
_BLOCK_K = 512
_MAX_SEQ_VMEM = 4096  # whole-K/V-in-VMEM streaming bound
_INTERPRET = False  # run pallas_calls in interpreter mode (CPU parity tests)


def set_interpret(on: bool) -> bool:
    """Route every ``pl.pallas_call`` here through the Pallas interpreter —
    the CPU path tier-1 uses to test the kernel math against
    :func:`_reference_attention` without a TPU. Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior


def flash_attention_available(q_shape, k_shape=None) -> bool:
    """Kernel path needs TPU (or interpreter mode, for CPU parity runs) +
    tile-friendly shapes (seq multiple of the block size) +
    self-attention-like q/k lengths (the kernel derives K/V tiling from
    q's seq_len)."""
    from ..device import is_tpu

    if not is_tpu() and not _INTERPRET:
        return False
    if len(q_shape) != 4:
        return False
    b, s, h, d = q_shape
    if k_shape is not None and tuple(k_shape) != tuple(q_shape):
        return False
    # seq must be an exact multiple of the tile the kernels will pick
    # (min(_BLOCK_Q, s)) or rows/keys beyond grid*block are silently dropped
    block = min(_BLOCK_Q, s)
    return s >= 256 and s % block == 0 and s <= _MAX_SEQ_VMEM and d >= 64 and d % 8 == 0


def _reference_attention(q, k, v, causal):
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        s = logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# -- forward kernel ---------------------------------------------------------


def _dot32(a, b, dims):
    """Matmul in the input dtype (bf16 hits the MXU at full rate) with f32
    accumulation — the casts-to-f32-first form runs the MXU at 1/4 rate.
    Shared by every Pallas kernel in ``ops/``.

    A bf16 operand is exact in one MXU pass, and Mosaic refuses a higher
    contract precision on it ("Bad lhs type") — which a process-wide
    ``jax_default_matmul_precision`` would otherwise ask for; float32
    operands follow that setting."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # contract last dim of both (a @ b.T)
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k, seq_len, scale):
    from jax.experimental import pallas as pl

    q = q_ref[...]  # [block_q, d], input dtype
    block_q = q.shape[0]
    qi = pl.program_id(2)

    m = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[1]), jnp.float32)

    n_kblocks = seq_len // block_k
    if causal:
        n_kblocks_live = (qi * block_q) // block_k + (block_q + block_k - 1) // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_tile = k_ref[pl.dslice(kb * block_k, block_k), :]
        v_tile = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = _dot32(q, k_tile, _NT) * scale  # [block_q, block_k] f32
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + _dot32(p.astype(v_tile.dtype), v_tile, _NN)
        return m_new, l_new, acc_new

    if causal:
        m, l, acc = jax.lax.fori_loop(0, n_kblocks_live, body, (m, l, acc))
    else:
        m, l, acc = jax.lax.fori_loop(0, n_kblocks, body, (m, l, acc))

    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(l))[:, None]


def _flash_fwd(q, k, v, causal):
    """Returns (out, lse) with out [b,s,h,d] and lse [b,h,s] f32 (in
    scale-applied logit units)."""
    from jax.experimental import pallas as pl

    b, s, h, d = q.shape
    block_q = min(_BLOCK_Q, s)
    block_k = min(_BLOCK_K, s)
    scale = 1.0 / (d**0.5)

    # layout: [b, h, s, d] for contiguous per-head tiles
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    grid = (b, h, s // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, block_k=block_k, seq_len=s, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        name="flash_fwd",
        interpret=_INTERPRET,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse


# -- backward kernels -------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, *, causal, block_k, seq_len, scale):
    """dQ = (P ∘ (dO Vᵀ − di)) K · scale, streamed over K/V tiles."""
    from jax.experimental import pallas as pl

    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...]  # [block_q, 1]
    di = di_ref[...]
    block_q = q.shape[0]
    qi = pl.program_id(2)

    acc = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    n_kblocks = seq_len // block_k
    if causal:
        n_kblocks = (qi * block_q) // block_k + (block_q + block_k - 1) // block_k

    def body(kb, acc):
        k_tile = k_ref[pl.dslice(kb * block_k, block_k), :]
        v_tile = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = _dot32(q, k_tile, _NT) * scale  # scaled logits [block_q, block_k]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = _dot32(do, v_tile, _NT)  # [block_q, block_k]
        ds = (p * (dp - di)).astype(k_tile.dtype)
        return acc + _dot32(ds, k_tile, _NN)

    acc = jax.lax.fori_loop(0, n_kblocks, body, acc)
    dq_ref[...] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref, *, causal, block_q, seq_len, scale):
    """dV = Pᵀ dO;  dK = (P ∘ (dO Vᵀ − di))ᵀ Q · scale, streamed over Q tiles."""
    from jax.experimental import pallas as pl

    k = k_ref[...]
    v = v_ref[...]
    block_k = k.shape[0]
    ki = pl.program_id(2)

    dk = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    dv = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    n_qblocks = seq_len // block_q
    q_start = (ki * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q_tile = q_ref[pl.dslice(qb * block_q, block_q), :]
        do_tile = do_ref[pl.dslice(qb * block_q, block_q), :]
        lse = lse_ref[pl.dslice(qb * block_q, block_q), :]  # [block_q, 1]
        di = di_ref[pl.dslice(qb * block_q, block_q), :]
        s = _dot32(q_tile, k, _NT) * scale  # [block_q, block_k]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        pc = p.astype(do_tile.dtype)
        dv = dv + _dot32(pc, do_tile, _TN)
        dp = _dot32(do_tile, v, _NT)
        ds = (p * (dp - di)).astype(q_tile.dtype)
        dk = dk + _dot32(ds, q_tile, _TN)
        return dk, dv

    dk, dv = jax.lax.fori_loop(q_start, n_qblocks, body, (dk, dv))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal):
    from jax.experimental import pallas as pl

    b, s, h, d = q.shape
    block_q = min(_BLOCK_Q, s)
    block_k = min(_BLOCK_K, s)
    scale = 1.0 / (d**0.5)

    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    dot = jnp.swapaxes(do, 1, 2)
    ot = jnp.swapaxes(o, 1, 2)
    # di = rowsum(dO ∘ O) [b, h, s, 1] — a cheap fused reduction, f32
    di = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1, keepdims=True)

    row_specs = [
        pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        pl.BlockSpec((None, None, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((None, None, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, block_k=block_k, seq_len=s, scale=scale),
        grid=(b, h, s // block_q),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        name="flash_bwd_dq",
        interpret=_INTERPRET,
    )(qt, kt, vt, dot, lse, di)

    col_specs = [
        pl.BlockSpec((None, None, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
        pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        pl.BlockSpec((None, None, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
        pl.BlockSpec((None, None, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        pl.BlockSpec((None, None, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, block_q=block_q, seq_len=s, scale=scale),
        grid=(b, h, s // block_k),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        ],
        name="flash_bwd_dkv",
        interpret=_INTERPRET,
    )(qt, kt, vt, dot, lse, di)

    back = lambda x: jnp.swapaxes(x, 1, 2)
    return back(dq), back(dk), back(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, causal):
    out, _ = _flash_fwd(q, k, v, causal)
    return out


def _flash_vjp_fwd(q, k, v, causal):
    out, lse = _flash_fwd(q, k, v, causal)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _jax_library_flash(q, k, v, causal):
    """JAX's in-tree Pallas TPU flash kernels. Kept for comparison/debug
    (impl='lib') — its backward materializes [b,h,s,128]/[b,h,s,block_k]
    f32 stat broadcasts in HBM, measured slower than the in-repo pair."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention as _fa,
    )

    b, s, h, d = q.shape
    blk = min(512, s)
    sizes = BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk,
    )
    out = _fa(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
              causal=causal, sm_scale=1.0 / (d ** 0.5), block_sizes=sizes)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(q, k, v, causal=False, impl="auto"):
    """q/k/v: [batch, seq, heads, head_dim]; returns same layout.

    ``impl``: 'auto'/'own' use this module's kernel pair (flash fwd + flash
    bwd over compact lse/di residuals); 'lib' forces the jax library kernels.
    """
    if tuple(k.shape) != tuple(q.shape) or tuple(v.shape) != tuple(q.shape):
        raise ValueError(
            f"flash_attention requires equal q/k/v shapes (self-attention); got "
            f"q{tuple(q.shape)} k{tuple(k.shape)} v{tuple(v.shape)} — use "
            "scaled_dot_product_attention for cross-length attention")
    if impl == "lib":
        return _jax_library_flash(q, k, v, causal)
    return _flash(q, k, v, causal)


def flash_attention_qkv(qkv, causal=False):
    """Packed-projection form: ``qkv`` is [batch, seq, 3, heads, head_dim]
    (the qkv-matmul output reshaped, un-sliced). Dispatches to the packed
    flat-lane kernels (flash_attention_flat) when enabled, else slices and
    uses the classic kernel pair."""
    if qkv.ndim != 5 or qkv.shape[2] != 3:
        raise ValueError(f"flash_attention_qkv expects [b, s, 3, h, d]; got {tuple(qkv.shape)}")
    from . import flash_attention_flat as _flat

    if _flat.enabled(qkv.shape):
        return _flat.flash_packed(qkv, causal)
    return _flash(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], causal)

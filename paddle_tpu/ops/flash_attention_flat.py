"""Flat-lane flash attention kernels: zero-relayout attention for the GPT
trunk.

Motivation (round-4 profile, v5 lite, b=8 s=1024 h=16 d=64): the classic
kernels in flash_attention.py take [b, h, s, d] operands, so XLA inserts
~6-9ms/step of relayout copies between the qkv projection (whose natural
output is [b, s, 3·h·d]) and every kernel call. These kernels instead read
the projection output's layout directly:

- Operands stay [b, s, H] (H = h·d) or packed [b, s, 3H]; BlockSpecs carve
  the lane (H) dimension into head-groups of hg·d lanes, and the kernel
  statically slices each head's d columns. No transposes anywhere in the
  attention path. hg is chosen by _head_group: the largest of {8,4,2,1,h}
  dividing h whose lane block is 128-aligned (or full-dimension) AND whose
  bwd dq accumulator (s·hg·d f32) stays within _DQ_ELEM_BUDGET — Mosaic
  compile time blows up past that.
- The backward is ONE fused kernel (grid over k-blocks, inner loop over
  q-blocks): s and dp computed once (5 MXU dots vs 7 for a split dq/dkv
  pair), one exp instead of two. dq accumulates in f32 in a VMEM-resident
  [s, hg·d] output block across the sequential k-block grid steps; dk/dv
  are per-block. Backward block_k is 256 to stay inside the ~16MB VMEM.
- lse/di live as [b, h//hg, s, hg] f32 so each head-group's stats are one
  full-lane block; the kernel selects a head's column with a one-hot
  multiply (dynamic lane slicing is not portable Mosaic).
- The softmax scale is folded into q (and k for the dq dot) tiles — 1/8th
  the VPU work of scaling the [block_q, block_k] logits tile; the causal
  mask (iota+compare+select) only runs on diagonal-intersecting tiles.

Parity anchor: same as flash_attention.py (fused_attention_op.cu).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _dot32 as _dot

# module-level so the autotune sweep (incubate.autotune.tune_flash_blocks)
# can override; defaults chosen on v5 lite for the flagship shape
_BLOCK_Q = 512
_BLOCK_K_FWD = 512
_BLOCK_K_BWD = 256


def set_blocks(block_q=None, block_k_fwd=None, block_k_bwd=None):
    """Override kernel block sizes (autotune hook). Returns prior values."""
    global _BLOCK_Q, _BLOCK_K_FWD, _BLOCK_K_BWD
    prior = (_BLOCK_Q, _BLOCK_K_FWD, _BLOCK_K_BWD)
    if block_q:
        _BLOCK_Q = int(block_q)
    if block_k_fwd:
        _BLOCK_K_FWD = int(block_k_fwd)
    if block_k_bwd:
        _BLOCK_K_BWD = int(block_k_bwd)
    return prior
_MAX_SEQ = 2048
_INTERPRET = False  # run pallas_calls in interpreter mode (CPU parity tests)


def set_interpret(on: bool) -> bool:
    """Route the flat-kernel ``pl.pallas_call``s through the Pallas
    interpreter (CPU parity tests). Returns the prior setting."""
    global _INTERPRET
    prior = _INTERPRET
    _INTERPRET = bool(on)
    return prior
# Mosaic compile time blows up with the fused-bwd dq accumulator block
# (full-sequence [s, hg*d] f32, read-modify-write across k-steps): 1M elements
# did not compile in 20 min on-chip (2026-07-30); 512K compiles in seconds.
# The head-group size adapts so s*hg*d stays within this budget.
_DQ_ELEM_BUDGET = 512 * 1024


def _head_group(h, s, d, packed=False):
    # Largest divisor of h whose lane block is Mosaic-legal and whose bwd dq
    # accumulator fits the compile budget. A full-dimension (hg == h) lane
    # block is legal without 128-alignment ONLY for separate q/k/v operands —
    # in the packed [b, s, 3H] tensor an H-lane block sits at offsets H and 2H,
    # so it must be 128-aligned like any other block.
    for hg in range(min(h, 16), 0, -1):
        if h % hg != 0:
            continue
        aligned = (hg * d) % 128 == 0
        if (aligned or (hg == h and not packed)) and s * hg * d <= _DQ_ELEM_BUDGET:
            return hg
    return 0  # no viable grouping — enabled() rejects


def enabled(qkv_shape=None, packed=True) -> bool:
    """Gate for dispatch from flash_attention_qkv. On TPU backends only;
    FLAGS_flash_flat allows forcing the classic path. ``packed`` must match
    the wrapper being dispatched to (flash_packed vs flash_flat*)."""
    from ..device import is_tpu
    from ..framework.flags import flag

    if not is_tpu() and not _INTERPRET:
        return False
    if not flag("FLAGS_flash_flat"):
        return False
    if qkv_shape is not None:
        b, s, three, h, d = qkv_shape
        block = min(_BLOCK_Q, s)
        if not (s >= 256 and s % block == 0 and s <= _MAX_SEQ and 64 <= d <= 128 and d % 8 == 0):
            return False
        if _head_group(h, s, d, packed=packed) == 0:
            return False
    return True


_NT = ((1,), (1,))
_NN = ((1,), (0,))
_TN = ((0,), (0,))


# -- kernels ----------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, block_k, seq_len, scale, hg, d, has_bias):
    from jax.experimental import pallas as pl

    if has_bias:
        bias_ref, o_ref, lse_ref = rest  # bias [block_q, seq] additive, finite
    else:
        (o_ref, lse_ref), bias_ref = rest, None

    qi = pl.program_id(2)
    block_q = q_ref.shape[0]
    nkb = seq_len // block_k
    lse_cols = []
    for hi in range(hg):
        c0 = hi * d
        q = q_ref[:, c0:c0 + d] * jnp.asarray(scale, q_ref.dtype)
        m = jnp.full((block_q,), -jnp.inf, jnp.float32)
        l = jnp.zeros((block_q,), jnp.float32)
        acc = jnp.zeros((block_q, d), jnp.float32)

        def body(kb, carry, masked):
            m, l, acc = carry
            kt = k_ref[pl.dslice(kb * block_k, block_k), c0:c0 + d]
            vt = v_ref[pl.dslice(kb * block_k, block_k), c0:c0 + d]
            s = _dot(q, kt, _NT)  # scale pre-applied via q
            if has_bias:
                s = s + bias_ref[:, pl.dslice(kb * block_k, block_k)].astype(jnp.float32)
            if masked:
                qp = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                kp = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                s = jnp.where(qp >= kp, s, -jnp.inf)
            mn = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - mn[:, None])
            al = jnp.exp(m - mn)
            ln = al * l + jnp.sum(p, axis=-1)
            accn = acc * al[:, None] + _dot(p.astype(vt.dtype), vt, _NN)
            return mn, ln, accn

        if causal:
            n_full = (qi * block_q) // block_k  # strictly below the diagonal
            n_live = n_full + (block_q + block_k - 1) // block_k
            m, l, acc = jax.lax.fori_loop(0, n_full, lambda kb, c: body(kb, c, False), (m, l, acc))
            m, l, acc = jax.lax.fori_loop(n_full, n_live, lambda kb, c: body(kb, c, True), (m, l, acc))
        else:
            m, l, acc = jax.lax.fori_loop(0, nkb, lambda kb, c: body(kb, c, False), (m, l, acc))

        o_ref[:, c0:c0 + d] = (acc / l[:, None]).astype(o_ref.dtype)
        oh = (jax.lax.broadcasted_iota(jnp.int32, (1, hg), 1) == hi).astype(jnp.float32)
        lse_cols.append((m + jnp.log(l))[:, None] * oh)
    lse_ref[...] = sum(lse_cols)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *refs,
                causal, block_q, block_k, seq_len, scale, hg, d, has_bias):
    from jax.experimental import pallas as pl

    if has_bias:
        bias_ref, dq_ref, dk_ref, dv_ref = refs  # bias [seq, block_k]
    else:
        (dq_ref, dk_ref, dv_ref), bias_ref = refs, None

    ki = pl.program_id(2)
    nq = seq_len // block_q
    for hi in range(hg):
        c0 = hi * d
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (1, hg), 1) == hi).astype(jnp.float32)
        k = k_ref[:, c0:c0 + d]
        v = v_ref[:, c0:c0 + d]
        ks = k * jnp.asarray(scale, k.dtype)
        dk = jnp.zeros((block_k, d), jnp.float32)
        dv = jnp.zeros((block_k, d), jnp.float32)

        def body(qb, carry, masked):
            dk, dv = carry
            sl = pl.dslice(qb * block_q, block_q)
            qt = q_ref[sl, c0:c0 + d] * jnp.asarray(scale, k.dtype)
            dot_ = do_ref[sl, c0:c0 + d]
            lse = jnp.sum(lse_ref[sl, :] * onehot, axis=1, keepdims=True)
            di = jnp.sum(di_ref[sl, :] * onehot, axis=1, keepdims=True)
            s = _dot(qt, k, _NT)  # scale pre-applied via qt
            if has_bias:
                s = s + bias_ref[sl, :].astype(jnp.float32)
            p = jnp.exp(s - lse)
            if masked:
                qp = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                kp = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                p = jnp.where(qp >= kp, p, 0.0)
            pc = p.astype(dot_.dtype)
            dv = dv + _dot(pc, dot_, _TN)
            dp = _dot(dot_, v, _NT)
            ds = (p * (dp - di)).astype(k.dtype)
            dk = dk + _dot(ds, qt, _TN)       # scale carried by qt
            contrib = _dot(ds, ks, _NN)       # scale carried by ks
            prev = dq_ref[sl, c0:c0 + d]
            dq_ref[sl, c0:c0 + d] = jnp.where(ki == 0, contrib, prev + contrib)
            return dk, dv

        if causal:
            q_start = (ki * block_k) // block_q
            n_diag_end = ((ki + 1) * block_k + block_q - 1) // block_q
            dk, dv = jax.lax.fori_loop(q_start, jnp.minimum(n_diag_end, nq),
                                       lambda qb, c: body(qb, c, True), (dk, dv))
            dk, dv = jax.lax.fori_loop(n_diag_end, nq,
                                       lambda qb, c: body(qb, c, False), (dk, dv))
        else:
            dk, dv = jax.lax.fori_loop(0, nq, lambda qb, c: body(qb, c, False), (dk, dv))

        dk_ref[:, c0:c0 + d] = dk.astype(dk_ref.dtype)
        dv_ref[:, c0:c0 + d] = dv.astype(dv_ref.dtype)


# -- pallas_call wrappers ---------------------------------------------------
# Packed operands: qkv [b, s, 3H]; q/k/v column-block index g is offset by
# h//hg per tensor. Separate operands: three [b, s, H].


def _fwd_call(operands, b, s, h, d, dtype, causal, packed):
    from jax.experimental import pallas as pl

    hg = _head_group(h, s, d, packed=packed)
    if hg == 0:
        raise ValueError(f"flat flash kernels unsupported for h={h}, s={s}, d={d} "
                         f"(no head grouping within the compile budget); gate with enabled()")
    hd = hg * d
    G = h // hg  # column blocks per tensor
    block_q = min(_BLOCK_Q, s)
    block_k = min(_BLOCK_K_FWD, s)
    if s % block_q or s % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide s={s}; "
                         f"fix via set_blocks()")
    scale = 1.0 / (d ** 0.5)

    if packed:
        in_specs = [
            pl.BlockSpec((None, block_q, hd), lambda bi, gi, qi: (bi, qi, gi)),
            pl.BlockSpec((None, s, hd), lambda bi, gi, qi: (bi, 0, G + gi)),
            pl.BlockSpec((None, s, hd), lambda bi, gi, qi: (bi, 0, 2 * G + gi)),
        ]
    else:
        in_specs = [
            pl.BlockSpec((None, block_q, hd), lambda bi, gi, qi: (bi, qi, gi)),
            pl.BlockSpec((None, s, hd), lambda bi, gi, qi: (bi, 0, gi)),
            pl.BlockSpec((None, s, hd), lambda bi, gi, qi: (bi, 0, gi)),
        ]

    bias = None
    if len(operands) > (1 if packed else 3):
        *operands, bias = operands
        # additive bias [b, 1, s, s] (broadcast over heads); rows for this
        # q-block resident in VMEM
        in_specs.append(pl.BlockSpec((None, None, block_q, s), lambda bi, gi, qi: (bi, 0, qi, 0)))
    # packed mode: the q/k/v specs are three column-block views of the SAME
    # [b, s, 3H] tensor, so it must appear once per spec
    operands = tuple(operands) * 3 if packed else tuple(operands)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, block_k=block_k, seq_len=s,
                          scale=scale, hg=hg, d=d, has_bias=bias is not None),
        grid=(b, G, s // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bi, gi, qi: (bi, qi, gi)),
            pl.BlockSpec((None, None, block_q, hg), lambda bi, gi, qi: (bi, gi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), dtype),
            jax.ShapeDtypeStruct((b, G, s, hg), jnp.float32),
        ],
        name="flash_flat_fwd",
        interpret=_INTERPRET,
    )(*operands, *( [bias] if bias is not None else [] ))
    return out, lse


def _bwd_call(operands, b, s, h, d, dtype, o, lse, do, causal, packed):
    from jax.experimental import pallas as pl

    hg = _head_group(h, s, d, packed=packed)
    if hg == 0:
        raise ValueError(f"flat flash kernels unsupported for h={h}, s={s}, d={d} "
                         f"(no head grouping within the compile budget); gate with enabled()")
    hd = hg * d
    G = h // hg
    block_q = min(_BLOCK_Q, s)
    block_k = min(_BLOCK_K_BWD, s)
    if s % block_q or s % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide s={s}; "
                         f"fix via set_blocks()")
    scale = 1.0 / (d ** 0.5)

    # di = rowsum(dO ∘ O) reshaped to the [b, G, s, hg] stat layout
    di = jnp.sum(do.astype(jnp.float32).reshape(b, s, h, d)
                 * o.astype(jnp.float32).reshape(b, s, h, d), axis=-1)
    di = jnp.swapaxes(di.reshape(b, s, G, hg), 1, 2)  # [b, G, s, hg]

    fullH = lambda bi, gi, ki: (bi, 0, gi)
    blkH = lambda bi, gi, ki: (bi, ki, gi)
    stat = lambda bi, gi, ki: (bi, gi, 0, 0)
    if packed:
        qkv_specs = [
            pl.BlockSpec((None, s, hd), fullH),
            pl.BlockSpec((None, block_k, hd), lambda bi, gi, ki: (bi, ki, G + gi)),
            pl.BlockSpec((None, block_k, hd), lambda bi, gi, ki: (bi, ki, 2 * G + gi)),
        ]
    else:
        qkv_specs = [
            pl.BlockSpec((None, s, hd), fullH),
            pl.BlockSpec((None, block_k, hd), blkH),
            pl.BlockSpec((None, block_k, hd), blkH),
        ]

    bias = None
    if len(operands) > (1 if packed else 3):
        *operands, bias = operands
    operands = tuple(operands) * 3 if packed else tuple(operands)
    extra_specs = [
        pl.BlockSpec((None, s, hd), fullH),           # do
        pl.BlockSpec((None, None, s, hg), stat),      # lse
        pl.BlockSpec((None, None, s, hg), stat),      # di
    ]
    extra_ops = [do, lse, di]
    if bias is not None:
        # bias columns for this k-block, all q rows resident
        extra_specs.append(pl.BlockSpec((None, None, s, block_k), lambda bi, gi, ki: (bi, 0, 0, ki)))
        extra_ops.append(bias)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
                          seq_len=s, scale=scale, hg=hg, d=d, has_bias=bias is not None),
        grid=(b, G, s // block_k),
        in_specs=qkv_specs + extra_specs,
        out_specs=[
            pl.BlockSpec((None, s, hd), fullH),           # dq (f32 accumulator)
            pl.BlockSpec((None, block_k, hd), blkH),
            pl.BlockSpec((None, block_k, hd), blkH),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), jnp.float32),
            jax.ShapeDtypeStruct((b, s, h * d), dtype),
            jax.ShapeDtypeStruct((b, s, h * d), dtype),
        ],
        name="flash_flat_bwd",
        interpret=_INTERPRET,
    )(*operands, *extra_ops)
    return dq.astype(dtype), dk, dv


# -- custom-vjp entries -----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _flat_packed(qkv, hd_shape, causal):
    b, s, _ = qkv.shape
    h, d = hd_shape
    out, _ = _fwd_call((qkv,), b, s, h, d, qkv.dtype, causal, packed=True)
    return out


def _flat_packed_fwd(qkv, hd_shape, causal):
    b, s, _ = qkv.shape
    h, d = hd_shape
    out, lse = _fwd_call((qkv,), b, s, h, d, qkv.dtype, causal, packed=True)
    return out, (qkv, out, lse)


def _flat_packed_bwd(hd_shape, causal, res, g):
    qkv, o, lse = res
    b, s, _ = qkv.shape
    h, d = hd_shape
    dq, dk, dv = _bwd_call((qkv,), b, s, h, d, qkv.dtype, o, lse, g, causal, packed=True)
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


_flat_packed.defvjp(_flat_packed_fwd, _flat_packed_bwd)


def flash_packed(qkv, causal=False):
    """qkv: [b, s, 3, h, d] (or [b, s, 3H] with heads given) — returns
    [b, s, h, d] to match flash_attention_qkv's contract."""
    b, s, three, h, d = qkv.shape
    flat = qkv.reshape(b, s, 3 * h * d)  # no-op relayout: d is already minor
    out = _flat_packed(flat, (h, d), causal)
    return out.reshape(b, s, h, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flat(q, k, v, hd_shape, causal):
    b, s, _ = q.shape
    h, d = hd_shape
    out, _ = _fwd_call((q, k, v), b, s, h, d, q.dtype, causal, packed=False)
    return out


def _flat_fwd(q, k, v, hd_shape, causal):
    b, s, _ = q.shape
    h, d = hd_shape
    out, lse = _fwd_call((q, k, v), b, s, h, d, q.dtype, causal, packed=False)
    return out, (q, k, v, out, lse)


def _flat_bwd(hd_shape, causal, res, g):
    q, k, v, o, lse = res
    b, s, _ = q.shape
    h, d = hd_shape
    return _bwd_call((q, k, v), b, s, h, d, q.dtype, o, lse, g, causal, packed=False)


_flat.defvjp(_flat_fwd, _flat_bwd)


def flash_flat(q, k, v, causal=False):
    """q/k/v: [b, s, h, d]; flat-lane kernel path, returns [b, s, h, d]."""
    b, s, h, d = q.shape
    out = _flat(q.reshape(b, s, h * d), k.reshape(b, s, h * d), v.reshape(b, s, h * d),
                (h, d), causal)
    return out.reshape(b, s, h, d)


# -- masked / GQA envelope (reference fused_attention_op.cu attn_mask path,
#    fused_softmax_mask.cu.h) -------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flat_masked(q, k, v, bias, hd_shape, causal):
    b, s, _ = q.shape
    h, d = hd_shape
    out, _ = _fwd_call((q, k, v, bias), b, s, h, d, q.dtype, causal, packed=False)
    return out


def _flat_masked_fwd(q, k, v, bias, hd_shape, causal):
    b, s, _ = q.shape
    h, d = hd_shape
    out, lse = _fwd_call((q, k, v, bias), b, s, h, d, q.dtype, causal, packed=False)
    return out, (q, k, v, bias, out, lse)


def _flat_masked_bwd(hd_shape, causal, res, g):
    q, k, v, bias, o, lse = res
    b, s, _ = q.shape
    h, d = hd_shape
    dq, dk, dv = _bwd_call((q, k, v, bias), b, s, h, d, q.dtype, o, lse, g, causal, packed=False)
    return dq, dk, dv, jnp.zeros_like(bias)  # masks are non-trainable inputs


_flat_masked.defvjp(_flat_masked_fwd, _flat_masked_bwd)


def mask_supported(b, s, h, d, mask_shape) -> bool:
    """Additive [b|1, 1, s, s] masks with FINITE entries (use -1e30, not
    -inf); full-row mask residency bounds s."""
    if s > 1024:
        return False
    ms = tuple(mask_shape)
    return len(ms) == 4 and ms[1] == 1 and ms[2] == s and ms[3] == s and ms[0] in (1, b)


def flash_flat_masked(q, k, v, mask, causal=False):
    """Masked attention through the flat kernels. ``mask``: additive bias
    [b|1, 1, s, s] (bool masks must be converted to 0/-1e30 by the caller).
    Grads flow to q/k/v; the mask gets zeros (non-trainable)."""
    b, s, h, d = q.shape
    if mask.shape[0] == 1 and b > 1:
        mask = jnp.broadcast_to(mask, (b,) + mask.shape[1:])
    out = _flat_masked(q.reshape(b, s, h * d), k.reshape(b, s, h * d),
                       v.reshape(b, s, h * d), mask, (h, d), causal)
    return out.reshape(b, s, h, d)


def flash_flat_gqa(q, k, v, causal=False, mask=None):
    """Grouped/multi-query attention: k/v have h_kv heads with h % h_kv == 0.
    KV heads are expanded to the query head count before the kernel (one
    bandwidth-bound repeat; the kernels then run the standard path) — the
    envelope contract of the reference's GQA-capable fused attention."""
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv != 0:
        raise ValueError(f"GQA needs h_kv | h; got h={h}, h_kv={h_kv}")
    r = h // h_kv
    if r > 1:
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    if mask is not None:
        return flash_flat_masked(q, k, v, mask, causal)
    return flash_flat(q, k, v, causal)

"""Attention functionals.

Parity: the reference's fused attention CUDA ops
(paddle/fluid/operators/fused/fused_attention_op.cu,
fused_softmax_mask.cu.h) — on TPU the hot path is the Pallas
flash-attention kernel (paddle_tpu/ops/flash_attention.py); the jnp
path below is the reference implementation XLA fuses on its own.

Kernel selection goes through :mod:`paddle_tpu.ops.registry`: two kernels
are registered here —

- ``sdpa``: the eager/staged scaled-dot-product entry point. Impls:
  ``flash`` (classic Pallas pair: no mask, no dropout), ``flash_flat_gqa``
  (flat-lane kernels: additive/bool masks + grouped KV), ``xla`` fallback.
- ``attention_core``: GPT's pure-array packed-qkv causal core. Impls:
  ``flash_packed`` (flat-lane packed, zero-relayout), ``flash`` (classic
  pair over slices), ``xla`` fallback.

The hand-rolled ``flag(...) and available(...)`` dance each call site used
to carry lives in the impls' availability predicates; selection is cached
per call signature with ``kernels.{sdpa,attention_core}.*`` counters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...framework import random as _random
from ...framework.flags import flag
from ...ops import registry as _registry
from ...tensor._helpers import ensure_tensor, op, unwrap


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None):
    """q/k/v: [batch, seq, heads, head_dim] (paddle layout).

    Dispatches through the ``sdpa`` kernel registry entry: the Pallas
    flash kernel on TPU when FLAGS_use_flash_attention is set and shapes
    are tile-friendly, the flat-lane masked/GQA kernels for supported
    masks, the jnp reference otherwise.
    """
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)

    dropping = dropout_p > 0.0 and training
    p = dropout_p if training else 0.0
    aux = [ensure_tensor(attn_mask)] if attn_mask is not None else []
    if dropping:
        aux.append(_random.key_tensor())
        aux.append(_random.train_flag_tensor())
    has_mask = attn_mask is not None

    def fn(qq, kk, vv, *extra):
        mask = extra[0] if has_mask else None
        drop_key = extra[-2] if dropping else None
        train = extra[-1] if dropping else None
        return _registry.dispatch("sdpa", qq, kk, vv, mask, is_causal, p, drop_key, train)

    return op(fn, q, k, v, *aux, _name="sdpa")


def _sdpa_reference(q, k, v, mask=None, causal=False, dropout_p=0.0, drop_key=None, train=None):
    # [B, S, H, D] -> [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    if dropout_p > 0.0 and drop_key is not None:
        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, probs.shape)
        scale = 1.0 / (1.0 - dropout_p)
        if train is not None:  # captured program flipped to inference
            keep = keep | (train == 0)
            scale = jnp.where(train == 0, 1.0, scale)
        probs = jnp.where(keep, probs * scale, 0.0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


# -- kernel registrations ----------------------------------------------------


def _fleet_mesh():
    from ...distributed.fleet import fleet  # imports this package: not at module level

    return fleet.multi_device_mesh


def _selection_state():
    # interpret-mode toggles and the fleet mesh live outside the flag
    # registry; fold them into the selection-cache key so set_interpret()
    # and fleet.init() re-run the predicates
    from ...ops import flash_attention as _fa
    from ...ops import flash_attention_flat as _flat

    mesh = _fleet_mesh()
    return (_fa._INTERPRET, _flat._INTERPRET,
            None if mesh is None else tuple(mesh.shape.items()))


def _kernel_shard(b, h):
    """How a Pallas attention kernel runs under the fleet mesh.

    Mosaic kernels cannot be partitioned automatically ("wrap the call in a
    shard_map" is the TPU compiler's answer), so in a program sharded over
    the fleet mesh the kernel runs per shard: batch over dp×sdp, heads over
    mp. Returns ``(mesh, b_local, h_local)`` — ``mesh`` None when no
    multi-device mesh is active and the kernel is called directly — or None
    when it cannot run per shard (a live pp or sep axis, batch or heads
    that do not divide); the XLA composite, which GSPMD partitions itself,
    then takes the call."""
    mesh = _fleet_mesh()
    if mesh is None:
        return None, b, h
    n = mesh.shape
    nb = n["dp"] * n["sdp"]
    if n["pp"] > 1 or n["sep"] > 1 or b % nb or h % n["mp"]:
        return None
    return mesh, b // nb, h // n["mp"]


_BSHD = P(("dp", "sdp"), None, "mp", None)          # q/k/v/out [b, s, h, d]
_PACKED = P(("dp", "sdp"), None, None, "mp", None)  # qkv [b, s, 3, h, d]


def _per_shard(b, h, fn, in_specs, out_specs):
    """``fn`` as it must be called for a [b, ·, h, ·] problem: wrapped in a
    shard_map over the fleet mesh when one is active, else ``fn`` itself."""
    shard = _kernel_shard(b, h)
    if shard is None or shard[0] is None:
        return fn
    return jax.shard_map(fn, mesh=shard[0], in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _sdpa_flash_available(q, k, v, mask, causal, dropout_p, drop_key, train):
    from ...ops.flash_attention import flash_attention_available

    if (mask is not None or dropout_p != 0.0 or not flag("FLAGS_use_flash_attention")
            or len(q.shape) != 4 or tuple(k.shape) != tuple(q.shape)):
        return False
    b, s, h, d = q.shape
    shard = _kernel_shard(b, h)
    return shard is not None and flash_attention_available((shard[1], s, shard[2], d))


def _sdpa_flash(q, k, v, mask, causal, dropout_p, drop_key, train):
    from ...ops.flash_attention import flash_attention

    return _per_shard(q.shape[0], q.shape[2],
                      lambda q, k, v: flash_attention(q, k, v, causal=causal),
                      (_BSHD, _BSHD, _BSHD), _BSHD)(q, k, v)


def _sdpa_flat_available(q, k, v, mask, causal, dropout_p, drop_key, train):
    # masked / GQA envelope: additive [b|1, 1, s, s] masks (bool masks become
    # 0/-1e30) and h_kv | h grouped KV run through the flat-lane kernels when
    # FLAGS_flash_flat is on (reference fused_attention_op.cu attn_mask path)
    from ...ops import flash_attention_flat as _flat

    if mask is None or dropout_p != 0.0 or not flag("FLAGS_use_flash_attention"):
        return False
    if _fleet_mesh() is not None:
        return False  # masked/grouped operands are not split per shard here
    b, s, h, d = q.shape
    kv_ok = tuple(k.shape) == tuple(q.shape) or (
        k.shape[0] == b and k.shape[1] == s and h % k.shape[2] == 0 and k.shape[3] == d)
    return (_flat.enabled((b, s, 3, h, d), packed=False) and kv_ok
            and _flat.mask_supported(b, s, h, d, tuple(mask.shape)))


def _sdpa_flat(q, k, v, mask, causal, dropout_p, drop_key, train):
    from ...ops import flash_attention_flat as _flat

    if mask.dtype == jnp.bool_:
        mask = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    return _flat.flash_flat_gqa(q, k, v, causal=causal, mask=mask)


_registry.define_kernel(
    "sdpa", flags=("FLAGS_use_flash_attention", "FLAGS_flash_flat"),
    cache_key=_selection_state)
_registry.register(
    "sdpa", "flash", _sdpa_flash, available=_sdpa_flash_available,
    doc="classic Pallas flash pair (self-attn, no mask/dropout, tile-friendly seq)")
_registry.register(
    "sdpa", "flash_flat_gqa", _sdpa_flat, available=_sdpa_flat_available,
    doc="flat-lane masked/GQA flash kernels (additive or bool [b|1,1,s,s] mask)")
_registry.register(
    "sdpa", "xla", _sdpa_reference, fallback=True,
    doc="jnp reference composite (any mask/dropout/shape)")


def _core_shard(qkv, dropout_p):
    """``_kernel_shard`` for a packed [b, s, 3, h, d] call the Pallas
    kernels may take (no dropout, flash on), else None."""
    if dropout_p != 0.0 or not flag("FLAGS_use_flash_attention"):
        return None
    return _kernel_shard(qkv.shape[0], qkv.shape[3])


def _core_flat_available(qkv, dropout_p, drop_key):
    from ...ops import flash_attention_flat as _flat

    shard = _core_shard(qkv, dropout_p)
    _, s, _, _, d = qkv.shape
    return shard is not None and _flat.enabled((shard[1], s, 3, shard[2], d))


def _core_flat(qkv, dropout_p, drop_key):
    from ...ops import flash_attention_flat as _flat

    return _per_shard(qkv.shape[0], qkv.shape[3],
                      lambda x: _flat.flash_packed(x, causal=True),
                      (_PACKED,), _BSHD)(qkv)


def _core_flash_available(qkv, dropout_p, drop_key):
    from ...ops.flash_attention import flash_attention_available

    shard = _core_shard(qkv, dropout_p)
    _, s, _, _, d = qkv.shape
    return shard is not None and flash_attention_available((shard[1], s, shard[2], d))


def _core_flash(qkv, dropout_p, drop_key):
    from ...ops.flash_attention import _flash

    return _per_shard(qkv.shape[0], qkv.shape[3],
                      lambda x: _flash(x[:, :, 0], x[:, :, 1], x[:, :, 2], True),
                      (_PACKED,), _BSHD)(qkv)


def _core_xla(qkv, dropout_p, drop_key):
    return _sdpa_reference(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], None, True,
                           dropout_p, drop_key)


_registry.define_kernel(
    "attention_core", flags=("FLAGS_use_flash_attention", "FLAGS_flash_flat"),
    cache_key=_selection_state)
_registry.register(
    "attention_core", "flash_packed", _core_flat, available=_core_flat_available,
    doc="flat-lane packed-qkv kernels (zero-relayout reads via index maps)")
_registry.register(
    "attention_core", "flash", _core_flash, available=_core_flash_available,
    doc="classic Pallas flash pair over packed-qkv slices")
_registry.register(
    "attention_core", "xla", _core_xla, fallback=True,
    doc="jnp reference over packed-qkv slices (handles attention dropout)")

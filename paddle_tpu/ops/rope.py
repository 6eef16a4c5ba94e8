"""Rotary positions: the YaRN frequency table and the rotation of pairs, in
plain ``jax.numpy``, with the pairs taken either of two ways.

A vector of ``dim`` channels at position ``t`` is rotated pair by pair, pair
``i`` turning by the angle ``t * inv_freq[i]``. **Interleaved** pairs
(``rope_interleave``; GigaChat 3.5's latent attention) are channels ``(2i, 2i +
1)``:

    y[2i]     = x[2i] cos - x[2i + 1] sin
    y[2i + 1] = x[2i + 1] cos + x[2i] sin

**Half-split** pairs (``rotate_half``, the Llama lineage; EvaByte) are channels
``(i, i + dim / 2)``:

    y[i]           = x[i] cos - x[i + dim / 2] sin
    y[i + dim / 2] = x[i + dim / 2] cos + x[i] sin

Both are registered under the kernel ``rope`` (``lax_interleaved``,
``lax_half_split``); :func:`rotate` picks by its ``pairs`` argument.

``inv_freq`` is YaRN's (arXiv:2309.00071, as DeepSeek-V3's modelling code
computes it): ``theta^(-2i/dim)`` for the pairs that turn more than
``beta_fast`` times over the original context, that divided by ``factor`` for
those that turn less than ``beta_slow`` times, a linear ramp between. It is a
host constant of ``dim / 2`` float32 numbers, baked into the program that uses
it; the angles are computed from the positions in float32 inside the program
(one rounding of ``t * inv_freq``: 2e-3 rad at position 32,768 for the fastest
pair), so no table of the context's length is held anywhere.

The softmax scale YaRN pairs with a stretched context (``mscale``) is the
attention layer's to apply: :func:`yarn_mscale` gives it.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from . import registry

__all__ = ["yarn_inv_freq", "yarn_mscale", "rope_angles", "apply_rope", "apply_rope_half", "rotate"]


def yarn_inv_freq(dim: int, theta: float, factor: float = 1.0, beta_fast: float = 32.0, beta_slow: float = 1.0,
                  original_max_position: int = 4096) -> np.ndarray:
    """``inv_freq [dim / 2]`` float32. ``factor`` 1 is plain RoPE."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / (float(theta) ** (i / dim))
    if float(factor) == 1.0:
        return extra.astype(np.float32)

    def correction_dim(turns):
        return dim * math.log(original_max_position / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                              # 1: the pair turns fast enough to keep its frequency
    return (extra / float(factor) * (1.0 - keep) + extra * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 for ``factor <= 1``)."""
    return 1.0 if factor <= 1 else 0.1 * float(mscale) * math.log(float(factor)) + 1.0


def rope_angles(positions, inv_freq):
    """``(cos, sin)`` ``[..., dim / 2]`` float32 of integer ``positions [...]``."""
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope(x, cos, sin):
    """``x [..., dim]`` with its interleaved pairs rotated; ``cos``/``sin``
    ``[..., dim / 2]`` broadcast against ``x``'s leading axes. Float32 inside,
    the result in ``x``'s dtype."""
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rope_half(x, cos, sin):
    """``x [..., dim]`` with its half-split pairs ``(i, i + dim / 2)``
    rotated; otherwise as :func:`apply_rope`."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def rotate(x, cos, sin, pairs: str = "interleaved"):
    """The rotation through the registry (``kernels.rope.picked``):
    ``pairs`` ``"interleaved"`` (:func:`apply_rope`) or ``"half"``
    (:func:`apply_rope_half`)."""
    if pairs not in ("interleaved", "half"):
        raise ValueError(f"pairs {pairs!r}: 'interleaved' or 'half'")
    kw = {} if pairs == "interleaved" else {"pairs": pairs}
    return registry.select("rope", x, cos, sin, **kw).fn(x, cos, sin)


registry.define_kernel("rope")
registry.register("rope", "lax_interleaved", apply_rope, available=lambda x, cos, sin, pairs="interleaved": pairs == "interleaved",
                  doc="interleaved-pair rotation in jax.numpy, float32 inside (any device, any dtype)")
registry.register("rope", "lax_half_split", apply_rope_half, available=lambda x, cos, sin, pairs="interleaved": pairs == "half",
                  doc="half-split-pair rotation (rotate_half) in jax.numpy, float32 inside (any device, any dtype)")

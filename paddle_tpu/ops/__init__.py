"""paddle_tpu.ops — the Pallas kernel tier and its registry.

Public surface: the flash-attention kernel families (classic pair +
flat-lane/packed), the serving engine's aliased decode attention, latent (MLA)
attention over a latent cache, rotary positions, the Mamba-2 state-space scan
(``ssd``), the grouped matmul of the dropless experts, the fused layer norm,
the fused sort-based MoE dispatch/combine, and the kernel registry every
``nn`` layer dispatches through (``registry.dispatch(<kernel>, ...)`` with
per-signature selection caching and an XLA-composite fallback).

Note: the ``flash_attention`` *function* is reached as
``ops.flash_attention.flash_attention`` — rebinding it here would shadow
the submodule name existing imports rely on.
"""
from . import (  # noqa: F401
    decode_attention, eva_attention, flash_attention, flash_attention_flat, grouped_matmul, layer_norm, mla_attention,
    moe_pallas, registry, rope, ssd,
)
from .flash_attention import flash_attention_available, flash_attention_qkv  # noqa: F401
from .flash_attention_flat import flash_flat, flash_flat_gqa, flash_packed  # noqa: F401
from .layer_norm import layer_norm_fused  # noqa: F401
from .moe_pallas import moe_available, moe_dispatch_combine  # noqa: F401
from .registry import (  # noqa: F401
    define_kernel,
    dispatch,
    implementations,
    kernel_table,
    kernels,
    register,
)

__all__ = [
    "decode_attention", "eva_attention", "flash_attention", "flash_attention_flat", "grouped_matmul", "layer_norm", "mla_attention",
    "moe_pallas", "registry", "rope", "ssd",
    "flash_attention_available", "flash_attention_qkv",
    "flash_flat", "flash_flat_gqa", "flash_packed",
    "layer_norm_fused",
    "moe_available", "moe_dispatch_combine",
    "define_kernel", "register", "dispatch", "implementations",
    "kernels", "kernel_table",
]

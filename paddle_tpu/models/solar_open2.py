"""Solar Open 2 (``model_type: solar_open2``): a pre-norm decoder whose layers
alternate one softmax-attention layer with three linear-attention layers, each
followed by a sparse expert layer — served, whole or as *one chip's share* of
an expert- and head-parallel group, through the serving engine.

Every layer is ``h += Mixer(RMSNorm(h)); h += MoE(RMSNorm(h))``; there are no
positions at all (``use_rope: false``), no biases, an untied embedding and
output head, and a final RMSNorm.

- **Gated GQA layer** (``gqa_layers``): ``Hq`` query heads on ``Hkv`` key/value
  heads of ``head_dim``; ``o = softmax(q k^T / sqrt(d) + causal) v``;
  ``y = W_o (o * sigmoid(W_g x))``.
- **Linear layer**, the gated delta rule with per-channel decay (Kimi Delta
  Attention; ``ops/delta_rule.py`` has the recurrence): ``q, k, v`` through a
  depthwise causal convolution over time (kernel 4) and SiLU, ``q`` and ``k``
  L2-normalised per head, a decay ``alpha = exp(-exp(A_log) softplus(W_f x +
  dt_bias))`` per channel through a low-rank ``W_f``, a write strength ``beta =
  2 sigmoid(W_beta x)`` per head, and ``y = W_o (RMSNorm_head(o) * sigmoid(W_g
  x))`` with a low-rank ``W_g``. A slot's state is one float32 matrix a head
  and the last three inputs of the convolution.
- **Experts**: sigmoid scores over all ``router_experts``, the
  ``num_experts_per_tok`` largest, weights normalised over the chosen; the
  experts *held here* (``held_experts = (first, count)``) compute their part,
  dropless (``ops/moe_dropless.py``), and one shared expert is added.

One chip's share holds some of the query, key/value and linear heads (their
columns of the projections and rows of ``W_o``), some of the experts, and
rows ``[0, vocab_size)`` of the vocabulary; what the absent heads and experts
would have added is left out and the partial result goes on to the next layer
(the model-configs guide, section 4). Nothing here stands in for absent chips.

Serving: :class:`SolarOpen2Decoder` is the model's face to ``DecodeEngine``
(``models/decoder.py``): per slot, key/value rows for the GQA layers (no reset
at admission) and, for the linear layers, the matrix state and the
convolution's tail (zeroed inside the slot's first prefill program). The
state and the gates stay float32; the weights and activations take the
model's dtype.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import registry as _registry
from ..ops.delta_rule import delta_rule_chunked, delta_rule_step
from ..ops.moe_dropless import dropless_experts, gated_ffn, route_topk
from .decoder import BufferSpec, Decoder

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM", "SolarOpen2Decoder"]

_INNER_CHUNK = 64     # the chunkwise delta rule's inner chunk


class SolarOpen2Config:
    """Sizes of the model, or of the share of it held here. The head counts,
    ``vocab_size`` and ``held_experts`` are what *this* holder has;
    ``router_experts`` is the router's full width."""

    def __init__(self, *, hidden_size: int, num_hidden_layers: int, gqa_layers: Sequence[int],
                 num_attention_heads: int, num_key_value_heads: int, head_dim: int,
                 linear_num_heads: int, linear_head_dim: int, short_conv_kernel_size: int = 4,
                 vocab_size: int, moe_intermediate_size: int, router_experts: int,
                 held_experts: Optional[Tuple[int, int]] = None, n_shared_experts: int = 1,
                 num_experts_per_tok: int = 8, norm_topk_prob: bool = True, routed_scaling_factor: float = 1.0,
                 rms_norm_eps: float = 1e-5, low_rank: int = 128, allow_neg_eigval: bool = True,
                 max_position_embeddings: int = 1 << 20, state_dtype: str = "float32"):
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.gqa_layers = tuple(int(i) for i in gqa_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.linear_num_heads = int(linear_num_heads)
        self.linear_head_dim = int(linear_head_dim)
        self.short_conv_kernel_size = int(short_conv_kernel_size)
        self.vocab_size = int(vocab_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.router_experts = int(router_experts)
        self.held_experts = (0, self.router_experts) if held_experts is None else (int(held_experts[0]), int(held_experts[1]))
        self.n_shared_experts = int(n_shared_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.low_rank = int(low_rank)
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.max_position_embeddings = int(max_position_embeddings)
        self.state_dtype = str(state_dtype)     # what a slot's matrix state is *stored* in; the update runs in float32
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of the key/value heads")
        if any(not 0 <= i < self.num_hidden_layers for i in self.gqa_layers):
            raise ValueError(f"gqa_layers {self.gqa_layers} outside the {self.num_hidden_layers} layers")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.router_experts):
            raise ValueError(f"held_experts {self.held_experts} outside the router's {self.router_experts}")

    @classmethod
    def from_config_file(cls, cfg: dict) -> "SolarOpen2Config":
        """From a configuration file of the benchmark: the source's keys at
        the top level; ``n_routed_experts`` and the head counts are what is
        held here, the router's width is the published count."""
        lin = cfg["linear_attn_config"]
        return cls(
            hidden_size=cfg["hidden_size"], num_hidden_layers=cfg["num_hidden_layers"], gqa_layers=cfg["gqa_layers"],
            num_attention_heads=cfg["num_attention_heads"], num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
            short_conv_kernel_size=lin["short_conv_kernel_size"], vocab_size=cfg["vocab_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            router_experts=cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]),
            held_experts=cfg.get("held_experts"), n_shared_experts=cfg["n_shared_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling_factor=cfg["routed_scaling_factor"], rms_norm_eps=cfg["rms_norm_eps"],
            low_rank=cfg.get("assumed", {}).get("low_rank", lin["head_dim"]),
            allow_neg_eigval=cfg["kda_allow_neg_eigval"], max_position_embeddings=cfg["max_position_embeddings"],
            state_dtype=cfg.get("serving", {}).get("state_dtype", "float32"))

    # ------------------------------------------------------------- layout
    @property
    def linear_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers) if i not in self.gqa_layers)

    def fingerprint(self) -> tuple:
        return ("solar_open2", self.hidden_size, self.num_hidden_layers, self.gqa_layers, self.num_attention_heads,
                self.num_key_value_heads, self.head_dim, self.linear_num_heads, self.linear_head_dim,
                self.short_conv_kernel_size, self.vocab_size, self.moe_intermediate_size, self.router_experts,
                self.held_experts, self.n_shared_experts, self.num_experts_per_tok, self.norm_topk_prob,
                self.routed_scaling_factor, self.rms_norm_eps, self.low_rank, self.allow_neg_eigval, self.state_dtype)

    def weight_shapes(self) -> Dict[str, tuple]:
        """Every weight by name. Per-layer weights are stacked: ``[L, ...]``
        over all layers for the norms and the experts, over the GQA layers
        for ``attn_*`` and over the linear layers for ``lin_*``."""
        D, L, F = self.hidden_size, self.num_hidden_layers, self.moe_intermediate_size
        Lg, Ll = len(self.gqa_layers), len(self.linear_layers)
        q, kv = self.num_attention_heads * self.head_dim, self.num_key_value_heads * self.head_dim
        lin, R, K = self.linear_num_heads * self.linear_head_dim, self.low_rank, self.short_conv_kernel_size
        held = self.held_experts[1]
        return {
            "embed": (self.vocab_size, D), "head": (self.vocab_size, D), "final_norm": (D,),
            "norm1": (L, D), "norm2": (L, D), "router": (L, D, self.router_experts),
            "experts_gate_up": (L, held, D, 2 * F), "experts_down": (L, held, F, D),
            "shared_gate_up": (L, D, 2 * F * self.n_shared_experts), "shared_down": (L, F * self.n_shared_experts, D),
            "attn_q": (Lg, D, q), "attn_kv": (Lg, D, 2 * kv), "attn_gate": (Lg, D, q), "attn_out": (Lg, q, D),
            "lin_qkv": (Ll, D, 3 * lin), "lin_conv": (Ll, K, 3 * lin), "lin_f_down": (Ll, D, R),
            "lin_f_up": (Ll, R, lin), "lin_dt_bias": (Ll, lin), "lin_a_log": (Ll, self.linear_num_heads),
            "lin_beta": (Ll, D, self.linear_num_heads), "lin_g_down": (Ll, D, R), "lin_g_up": (Ll, R, lin),
            "lin_out_norm": (Ll, self.linear_head_dim), "lin_out": (Ll, lin, D),
        }


# kept float32 whatever the model's dtype: the decay's parameters
F32_WEIGHTS = ("lin_dt_bias", "lin_a_log")
# held as one array a layer (a tuple over the layers, indexed like a stack):
# the grouped matmul takes a layer's experts whole, and a slice of a stack
# would be copied out for it, 1.26 GB a layer a step
PER_LAYER_WEIGHTS = ("experts_gate_up", "experts_down")


def init_weights(cfg: SolarOpen2Config, seed: int, dtype: str = "bfloat16"):
    """Every weight from ``seed``, on the device, in ``dtype``, in one jitted
    call. Matrices N(0, 0.02); norm scales 1 + N(0, 0.02) (so that a dropped
    scale shows); ``A_log = log U(1, 16)`` and ``dt_bias = softplus^-1(U(1e-3,
    0.1))``: the decay starts near 1 and the state carries history."""
    make = _weight_maker(tuple(sorted(cfg.weight_shapes().items())), str(dtype))
    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


@functools.lru_cache(maxsize=None)
def _weight_maker(shapes: tuple, dtype: str):
    dt = jnp.dtype(dtype)

    def one(name, shape, k):
        if name == "lin_a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))  # noqa: PTA304 (jax.random, a key folded from the seed)
        if name == "lin_dt_bias":
            dt0 = jax.random.uniform(k, shape, jnp.float32, 1e-3, 0.1)  # noqa: PTA304 (jax.random, a key folded from the seed)
            return dt0 + jnp.log(-jnp.expm1(-dt0))                      # softplus^-1
        if name in PER_LAYER_WEIGHTS:
            return tuple(one("", shape[1:], jax.random.fold_in(k, layer)) for layer in range(shape[0]))
        w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        if name in ("norm1", "norm2", "final_norm", "lin_out_norm"):
            w = 1.0 + w
        return w.astype(dt)

    def make(key):
        return {shapes[i][0]: one(shapes[i][0], shapes[i][1], jax.random.fold_in(key, i)) for i in range(len(shapes))}

    return jax.jit(make)


# ------------------------------------------------------------------ pieces
def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _layer(p: dict, prefix: str, i: int) -> dict:
    """Entry ``i`` of every stacked weight whose name starts with ``prefix``."""
    return {k: v[i] for k, v in p.items() if k.startswith(prefix)}


def _moe(cfg, p: dict, layer: int, x, routed=None, scoring: str = "sigmoid"):
    """``(routed part of the held experts + shared expert, stats)`` for rows
    ``x [T, D]``, the router scored by ``scoring`` (``route_topk`` has the two).
    ``routed``, a list, is handed the experts each row chose (``[T, k]``): what
    :func:`chunk_routing` and :func:`decode_probe` report."""
    w, idx = route_topk(x, p["router"][layer], top_k=cfg.num_experts_per_tok,
                        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor, scoring=scoring)
    y, stats = dropless_experts(x, w, idx, p["experts_gate_up"][layer], p["experts_down"][layer],
                                held=cfg.held_experts, n_experts=cfg.router_experts)
    with jax.named_scope("moe_shared"):
        y = y + gated_ffn(x, p["shared_gate_up"][layer], p["shared_down"][layer])
    if routed is not None:
        routed.append(idx)  # noqa: PTA104 (a host list filled while tracing)
    return y.astype(x.dtype), stats


def _gqa_project(cfg, lp, x):
    """``q [T, Hkv, G, d]``, ``k``/``v`` ``[T, Hkv, d]`` and the output gate
    ``[T, Hq * d]`` (float32; None for a layer that has no ``attn_gate``) of
    rows ``x [T, D]``."""
    T = x.shape[0]
    Hq, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        q = jnp.matmul(x, lp["attn_q"]).reshape(T, Hkv, Hq // Hkv, d)
        kv = jnp.matmul(x, lp["attn_kv"]).reshape(T, 2, Hkv, d)
        gate = None
        if "attn_gate" in lp:
            gate = jax.nn.sigmoid(jnp.matmul(x, lp["attn_gate"], preferred_element_type=jnp.float32))
    return q, kv[:, 0], kv[:, 1], gate


def _gqa_out(lp, att, gate, dtype):
    with jax.named_scope("attn_out"):
        if gate is not None:
            att = att.astype(jnp.float32) * gate
        return jnp.matmul(att.astype(dtype), lp["attn_out"])


def _gqa_chunk(cfg, lp, x, ck, cv, li, slot, start):
    """The GQA mixer over ``C`` tokens ``x [C, D]`` of one slot at ``start``
    against the stacked cache ``[Lg, B, Hkv, S, d]``: the chunk's keys and
    values are written in place, then every row attends the slot's rows up to
    its own. Returns ``(y [C, D], ck, cv)``."""
    C = x.shape[0]
    Hkv, d, S = cfg.num_key_value_heads, cfg.head_dim, ck.shape[3]
    q, k, v, gate = _gqa_project(cfg, lp, x)
    with jax.named_scope("cache_write"):
        ck = jax.lax.dynamic_update_slice(ck, jnp.swapaxes(k, 0, 1)[None, None], (li, slot, 0, start, 0))
        cv = jax.lax.dynamic_update_slice(cv, jnp.swapaxes(v, 0, 1)[None, None], (li, slot, 0, start, 0))
    with jax.named_scope("cache_read"):
        rk = jax.lax.dynamic_slice(ck, (li, slot, 0, 0, 0), (1, 1, Hkv, S, d))[0, 0]
        rv = jax.lax.dynamic_slice(cv, (li, slot, 0, 0, 0), (1, 1, Hkv, S, d))[0, 0]
    with jax.named_scope("attn_core"):
        scale = jnp.asarray(1.0 / math.sqrt(d), q.dtype)
        scores = jnp.einsum("chgd,hsd->hgcs", q * scale, rk, preferred_element_type=jnp.float32)
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (C, S), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (C, S), 1)
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, -jnp.inf)
        prob = jax.nn.softmax(scores, axis=-1).astype(rv.dtype)
        att = jnp.einsum("hgcs,hsd->chgd", prob, rv, preferred_element_type=jnp.float32).reshape(C, -1)
    return _gqa_out(lp, att, gate, x.dtype), ck, cv


def _gqa_write_attend(q, k, v, ck, cv, pos, active, li):
    """The lax write-and-attend of one GQA layer for a decode batch (where the
    ``decode_attention`` kernel declines): ``q [B, Hkv, G, d]``, ``k``/``v``
    ``[B, Hkv, 1, d]``; the stacked cache ``[Lg, B, Hkv, S, d]``. Scores times
    ``d^-1/2``, as the kernel's."""
    B, Hkv, G, d = q.shape
    S = ck.shape[3]

    def write(c, u, p, a):
        cur = jax.lax.dynamic_slice(c, (0, p, 0), u.shape)
        return jax.lax.dynamic_update_slice(c, jnp.where(a, u, cur), (0, p, 0))

    with jax.named_scope("cache_write"):
        lk = jax.vmap(write)(ck[li], k, pos, active)
        lv = jax.vmap(write)(cv[li], v, pos, active)
        ck = jax.lax.dynamic_update_slice(ck, lk[None], (li, 0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, lv[None], (li, 0, 0, 0, 0))
    with jax.named_scope("attn_core"):
        scale = jnp.asarray(1.0 / math.sqrt(d), q.dtype)
        scores = jnp.einsum("bhgd,bhsd->bhgs", q * scale, lk, preferred_element_type=jnp.float32)
        visible = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1) <= pos[:, None]
        scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
        prob = jax.nn.softmax(scores, axis=-1).astype(lv.dtype)
        att = jnp.einsum("bhgs,bhsd->bhgd", prob, lv, preferred_element_type=jnp.float32)
    return att.astype(q.dtype), ck, cv


def _gqa_decode(cfg, lp, x, ck, cv, li, pos, active, scale=None):
    """The GQA mixer for one token of every slot, ``x [B, D]``. Kernel and lax
    form alike scale the scores by ``d^-1/2``: a model with a ``scale`` of its
    own has ``q`` times ``scale d^1/2`` first."""
    B = x.shape[0]
    q, k, v, gate = _gqa_project(cfg, lp, x)
    if scale is not None:
        with jax.named_scope("attn_qkv"):
            q = q * jnp.asarray(scale * math.sqrt(cfg.head_dim), q.dtype)
    impl = _registry.select("decode_attention", ck, packed=False, window=1)
    if impl.fallback:
        att, ck, cv = _gqa_write_attend(q, k[:, :, None], v[:, :, None], ck, cv, pos, active, li)
    else:
        # a key/value head's group of query heads rides the kernel's padded window rows
        att, ck, cv = impl.fn(q, k[:, :, None], v[:, :, None], ck, cv, pos, active, li, group=q.shape[2])
    return _gqa_out(lp, att.reshape(B, -1), gate, x.dtype), ck, cv


def _linear_project(cfg, lp, x):
    """The linear layer's projections of rows ``x [T, D]``: the convolution's
    input ``qkv [T, 3 * H * d]``, and in float32 ``log_alpha [T, H, d]``,
    ``beta [T, H]`` and the output gate ``[T, H * d]``."""
    T = x.shape[0]
    H, d = cfg.linear_num_heads, cfg.linear_head_dim
    with jax.named_scope("linear_proj"):
        qkv = jnp.matmul(x, lp["lin_qkv"])
        f = jnp.matmul(jnp.matmul(x, lp["lin_f_down"]), lp["lin_f_up"], preferred_element_type=jnp.float32)
        dt = jax.nn.softplus(f + lp["lin_dt_bias"]).reshape(T, H, d)
        log_alpha = -jnp.exp(lp["lin_a_log"])[None, :, None] * dt
        beta = jax.nn.sigmoid(jnp.matmul(x, lp["lin_beta"], preferred_element_type=jnp.float32))
        if cfg.allow_neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.sigmoid(jnp.matmul(jnp.matmul(x, lp["lin_g_down"]), lp["lin_g_up"],
                                         preferred_element_type=jnp.float32))
    return qkv, log_alpha, beta, gate


def _conv_heads(cfg, lp, window):
    """Depthwise causal convolution + SiLU over ``window [..., K - 1 + T, 3 H d]``
    (the ``K - 1`` inputs before the run, then the run), and the per-head
    normalisation: ``q, k [..., T, H, d]`` L2-normalised (``q`` also scaled by
    ``d^-1/2``), ``v``; float32."""
    K, H, d = cfg.short_conv_kernel_size, cfg.linear_num_heads, cfg.linear_head_dim
    T = window.shape[-2] - (K - 1)
    w = lp["lin_conv"].astype(jnp.float32)
    x = window.astype(jnp.float32)
    y = sum(x[..., j:j + T, :] * w[j] for j in range(K))                  # tap K-1 is the current token
    y = jax.nn.silu(y).reshape(y.shape[:-1] + (3, H, d))
    q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    return l2(q) * (d ** -0.5), l2(k), v


def _linear_out(cfg, lp, o, gate, dtype):
    """``W_o (RMSNorm_head(o) * gate)`` for ``o [T, H, d]`` float32."""
    with jax.named_scope("linear_out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        o = o * lp["lin_out_norm"].astype(jnp.float32)
        return jnp.matmul((o.reshape(o.shape[0], -1) * gate).astype(dtype), lp["lin_out"])


def _linear_chunk(cfg, lp, x, state, tail, n_valid):
    """The linear mixer over ``C`` tokens ``x [C, D]`` of one sequence, from
    ``state [H, d, d]`` (float32) and the convolution's ``tail [K - 1, 3 H d]``.
    Rows at ``n_valid`` and after are padding: they decay nothing, write
    nothing and leave the tail alone. Returns ``(y [C, D], state, tail)``."""
    C = x.shape[0]
    K = cfg.short_conv_kernel_size
    qkv, log_alpha, beta, gate = _linear_project(cfg, lp, x)
    with jax.named_scope("linear_core"):
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=0)              # [K-1+C, 3Hd]
        q, k, v = _conv_heads(cfg, lp, window)
        valid = jnp.arange(C) < n_valid
        log_alpha = jnp.where(valid[:, None, None], log_alpha, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
        inner = min(_INNER_CHUNK, C)
        pad = (-C) % inner                  # padding rows: alpha 1, beta 0
        heads_first = lambda a: jnp.pad(jnp.moveaxis(a, 0, 1), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        o, state = delta_rule_chunked(heads_first(q), heads_first(k), heads_first(v), heads_first(log_alpha),
                                      heads_first(beta), state, chunk=inner)
        o = jnp.moveaxis(o[:, :C], 0, 1)                                             # [C, H, d]
        tail = jax.lax.dynamic_slice_in_dim(window, n_valid, K - 1, axis=0).astype(tail.dtype)
    return _linear_out(cfg, lp, o, gate, x.dtype), state, tail


def _linear_decode(cfg, lp, x, state, tail, active):
    """The linear mixer for one token of every slot: ``x [B, D]``, ``state
    [B, H, d, d]``, ``tail [B, K - 1, 3 H d]``. A slot that is not active
    keeps its state and tail bitwise."""
    qkv, log_alpha, beta, gate = _linear_project(cfg, lp, x)
    with jax.named_scope("linear_core"):
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv[:, None]], axis=1)      # [B, K, 3Hd]
        q, k, v = _conv_heads(cfg, lp, window)
        log_alpha = jnp.where(active[:, None, None], log_alpha, 0.0)
        beta = jnp.where(active[:, None], beta, 0.0)
        o, state = delta_rule_step(q[:, 0], k[:, 0], v[:, 0], log_alpha, beta, state)
        tail = jnp.where(active[:, None, None], window[:, 1:].astype(tail.dtype), tail)
    return _linear_out(cfg, lp, o, gate, x.dtype), state, tail


def _head(cfg, p, h):
    with jax.named_scope("norm"):
        h = _rms_norm(h, p["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("head_loss"):
        return jnp.matmul(h, p["head"].T, preferred_element_type=jnp.float32)


def _admitting(start):
    """A slot's first prefill program: the one that runs at position 0."""
    return start == 0


def _layers(cfg, p: dict, cache, ids, gqa, linear, routed=None, *, moe=_moe, embed_scale=None, residual_scale=None,
            stream_dtype=None):
    """Both forwards' walk from token ids ``[T]`` to hidden rows ``[T, D]``: the
    embedding, then ``norm -> mixer -> residual -> norm -> experts -> residual``
    a layer. ``cache`` is the engine's flat tuple of buffers: ``k``, ``v``, a
    state a linear layer, a conv tail a linear layer. The forward's two mixers,
    ``gqa(gi, x, ck, cv) -> (y, ck, cv)`` and ``linear(li, x, state, tail) ->
    (y, state, tail)``, are told which GQA or linear layer this is and handed
    its buffers whole. Returns ``(h, cache, stats int32[2])`` with the routed
    experts' load summed over the layers.

    A family that shares the walk (``models/granite_moe_hybrid.py``) names its
    own expert layer ``moe`` and, where it has them, a factor on the embedding
    and one on every sub-layer's output before the residual sum, and a type
    the residual stream is held in beside the model's (a sub-layer's input is
    cast back to the model's)."""
    n = len(cfg.linear_layers)
    ck, cv, states, tails = cache[0], cache[1], list(cache[2:2 + n]), list(cache[2 + n:2 + 2 * n])
    with jax.named_scope("embed"):
        h = jnp.take(p["embed"], ids, axis=0)
        dtype = h.dtype
        if stream_dtype is not None:
            h = h.astype(stream_dtype)
        if embed_scale is not None:
            h = h * embed_scale

    def normed(h, name, layer):
        with jax.named_scope("norm"):
            return _rms_norm(h, p[name][layer], cfg.rms_norm_eps).astype(dtype)

    def added(h, y):
        return h + (y if residual_scale is None else residual_scale * y.astype(h.dtype))

    gi = li = 0
    stats = jnp.zeros((2,), jnp.int32)
    for layer in range(cfg.num_hidden_layers):  # noqa: PTA104 (static unroll, host loop bound)
        x = normed(h, "norm1", layer)
        if layer in cfg.gqa_layers:
            y, ck, cv = gqa(gi, x, ck, cv)
            gi += 1
        else:
            y, states[li], tails[li] = linear(li, x, states[li], tails[li])  # noqa: PTA104 (static unroll, host loop bound)
            li += 1
        h = added(h, y)
        x = normed(h, "norm2", layer)
        y, s = moe(cfg, p, layer, x, routed)
        stats = stats + s
        h = added(h, y)
    return h, (ck, cv, *states, *tails), stats


def _chunk_forward(cfg: SolarOpen2Config, p: dict, cache, ids, slot, start, n_valid, want_rows, routed=None):
    """``C`` tokens ``ids [C]`` of slot ``slot`` at ``start`` through every
    layer. At ``start == 0`` the slot is being admitted: its state and tail
    start from zero, whatever an earlier request left there. ``want_rows``:
    ``None`` (no logits), a traced row index (that row's logits ``[1, V]``) or
    ``"all"`` (``[C, V]``). Returns ``(logits | None, cache)``."""
    fresh = _admitting(start)

    def gqa(gi, x, ck, cv):
        return _gqa_chunk(cfg, _layer(p, "attn_", gi), x, ck, cv, gi, slot, start)

    def linear(li, x, states, tails):
        st = jax.lax.dynamic_slice_in_dim(states, slot, 1, axis=0)[0].astype(jnp.float32)
        tl = jax.lax.dynamic_slice_in_dim(tails, slot, 1, axis=0)[0]
        st, tl = jnp.where(fresh, 0.0, st), jnp.where(fresh, jnp.zeros_like(tl), tl)
        y, st, tl = _linear_chunk(cfg, _layer(p, "lin_", li), x, st, tl, n_valid)
        return (y, jax.lax.dynamic_update_slice(states, st[None].astype(states.dtype), (slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(tails, tl[None], (slot, 0, 0)))

    h, cache, _ = _layers(cfg, p, cache, ids, gqa, linear, routed)
    if want_rows is None:
        return None, cache
    if not isinstance(want_rows, str):
        h = jax.lax.dynamic_slice_in_dim(h, want_rows, 1, axis=0)
    return _head(cfg, p, h), cache


def _decode_forward(cfg: SolarOpen2Config, p: dict, cache, tok, pos, active, routed=None):
    """One token of every slot: ``tok``, ``pos`` ``[B]``; writes gated by
    ``active``. Returns ``(logits [B, V], cache, stats int32[2])`` with the
    routed experts' load summed over the layers."""
    def gqa(gi, x, ck, cv):
        return _gqa_decode(cfg, _layer(p, "attn_", gi), x, ck, cv, gi, pos, active)

    def linear(li, x, state, tail):
        y, st, tail = _linear_decode(cfg, _layer(p, "lin_", li), x, state.astype(jnp.float32), tail, active)
        return y, st.astype(state.dtype), tail

    h, cache, stats = _layers(cfg, p, cache, tok, gqa, linear, routed)
    return _head(cfg, p, h), cache, stats


def chunk_routing(cfg: SolarOpen2Config, p: dict, cache, ids, slot, start, n_valid):
    """The chunk forward of the engine's prefill programs, also saying which
    experts every row chose in every layer: ``(cache, experts [L, C, k])``.
    For a comparison that has to follow the program's routing where two
    scores tie within rounding (``benchmark/families/solar_open2.py``)."""
    routed = []
    _, cache = _chunk_forward(cfg, p, cache, ids, slot, start, n_valid, None, routed)
    return cache, jnp.stack(routed)


def decode_probe(cfg: SolarOpen2Config, p: dict, cache, tok, pos, active):
    """The decode forward of the engine's decode program with its routing:
    ``(logits [B, V], experts [L, B, k])``; the buffers are not kept."""
    routed = []
    logits, _, _ = _decode_forward(cfg, p, cache, tok, pos, active, routed)
    return logits, jnp.stack(routed)


# ------------------------------------------------------------------ decoder
class SolarOpen2Decoder(Decoder):
    """The model through the serving engine's interface."""

    recurrent = True
    n_stats = 2
    stat_counters = ("infer.moe.assignments_local", "infer.moe.experts_hit")

    def __init__(self, model: "SolarOpen2ForCausalLM"):
        self.cfg = model.cfg
        self._weights = model.weights
        self.vocab_size = model.cfg.vocab_size
        self.max_positions = model.cfg.max_position_embeddings
        self.dtype = model.weights["embed"].dtype

    def params(self, int8: bool = False):
        if int8:
            raise NotImplementedError("SolarOpen2 has no int8 weights")
        return dict(self._weights)

    def fingerprint(self) -> tuple:
        return self.cfg.fingerprint()

    def buffer_specs(self, slots: int, rows: int, kv_dtype=None):
        c = self.cfg
        B, Lg, Ll = int(slots), len(c.gqa_layers), len(c.linear_layers)
        kv = (Lg, B, c.num_key_value_heads, int(rows), c.head_dim)
        H, d = c.linear_num_heads, c.linear_head_dim
        dt = str(self.dtype)
        # the state and the tail are one buffer a linear layer: a decode step
        # rewrites each whole, and a stack of them would be copied to be rebuilt
        return (BufferSpec("k", kv, dt, 1, False), BufferSpec("v", kv, dt, 1, False),
                *(BufferSpec(f"state{i}", (B, H, d, d), c.state_dtype, 0, True) for i in range(Ll)),
                *(BufferSpec(f"conv{i}", (B, c.short_conv_kernel_size - 1, 3 * H * d), dt, 0, True) for i in range(Ll)))

    def prefill(self, p, cache, ids, length, slot):
        # a whole padded prompt: one chunk at start 0, into the fresh slot
        return _chunk_forward(self.cfg, p, cache, ids[0], slot, jnp.int32(0), length, length - 1)

    def chunk(self, p, cache, ids, slot, start, last_row=None):
        C = ids.shape[1]
        n_valid = jnp.int32(C) if last_row is None else last_row + 1
        return _chunk_forward(self.cfg, p, cache, ids[0], slot, start, n_valid, last_row)

    def decode(self, p, cache, tok, pos, active):
        return _decode_forward(self.cfg, p, cache, tok, pos, active)


# -------------------------------------------------------------------- model
class SolarOpen2ForCausalLM(nn.Layer):
    """The model (or one chip's share of it) with its weights as plain device
    arrays under ``weights`` (``SolarOpen2Config.weight_shapes`` names them);
    made from ``seed`` unless given."""

    def __init__(self, cfg: SolarOpen2Config, seed: int = 0, dtype: str = "bfloat16", weights: Optional[dict] = None):
        super().__init__()
        self.cfg = cfg
        self.weights = init_weights(cfg, seed, dtype) if weights is None else dict(weights)

    def decoder(self) -> SolarOpen2Decoder:
        """What the serving engine runs this model through."""
        return SolarOpen2Decoder(self)

    def forward(self, input_ids):
        """Logits ``[b, s, V]`` (float32) of whole sequences, each from an
        empty state: the chunk forward over a scratch cache."""
        from ..framework.core import unwrap
        from ..tensor._helpers import _wrap_value

        ids = jnp.asarray(unwrap(input_ids), jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        return _wrap_value(_sequence_logits(self.cfg, self.weights, ids))


def _sequence_logits(cfg: SolarOpen2Config, p: dict, ids):
    dec_dtype = p["embed"].dtype
    s = ids.shape[1]
    Lg, Ll = len(cfg.gqa_layers), len(cfg.linear_layers)
    H, d = cfg.linear_num_heads, cfg.linear_head_dim
    scratch = (jnp.zeros((Lg, 1, cfg.num_key_value_heads, s, cfg.head_dim), dec_dtype),
               jnp.zeros((Lg, 1, cfg.num_key_value_heads, s, cfg.head_dim), dec_dtype),
               *(jnp.zeros((1, H, d, d), cfg.state_dtype) for _ in range(Ll)),
               *(jnp.zeros((1, cfg.short_conv_kernel_size - 1, 3 * H * d), dec_dtype) for _ in range(Ll)))
    one = lambda row: _chunk_forward(cfg, p, scratch, row, jnp.int32(0), jnp.int32(0), jnp.int32(s), "all")[0]  # noqa: E731
    return jnp.stack([one(row) for row in ids])

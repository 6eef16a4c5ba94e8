"""GigaChat 3.5 (``model_type: gigachat3_5``): a decoder whose blocks are
normalised before *and* after each sub-layer (``layernorm_type: pre_post``),
whose mixers alternate one latent-attention (MLA) layer with three gated delta
net layers, and whose feed-forward is dense in the leading layers and a sparse
expert layer after them — served, whole or as *one chip's share* of an
expert-parallel group, through the serving engine.

Every block is ``h += N(Mixer(N(h))); h += N(FFN(N(h)))`` with four norms of
its own; ``N(x) = x / rms(x) * 2 sigmoid(w)`` (``ZeroCenteredGatedNorm``: scale
1 at ``w = 0``); no biases, an untied embedding and head, a final norm.

- **Latent attention** (``full_attention_layers``; DeepSeek-V3's equations):
  ``c_q = N(W_dq x)``, ``[q_nope | q_rope]_h = W_uq,h c_q``; ``[c_kv | k_r] =
  W_dkv x``, ``c = N(c_kv)``; ``q_rope`` and the one ``k_r`` all heads share
  are rotated (``ops/rope.py``: YaRN frequencies, interleaved pairs); the
  softmax scale is ``(dn + rope)^-1/2 mscale^2``; ``y = W_o (o * sigmoid(W_g
  x))``. A slot caches ``[c | rotated k_r]`` a token, no head axis. Prefill
  expands ``k_nope = W_uk c`` and ``v = W_uv c`` block by block of the context;
  decode is absorbed: ``q'_h = W_uk,h^T q_nope,h`` scores the cached rows
  themselves and ``o_h = W_uv,h (s_h c)`` (``ops/mla_attention.py``).
- **Gated delta net** (the other layers; arXiv:2412.06464): ``q, k, v`` through
  a depthwise causal convolution (kernel 4) and SiLU, ``q`` and ``k``
  L2-normalised per head, each key head serving ``Hv / Hk`` value heads; one
  scalar decay ``alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))`` and one
  write strength ``beta = sigmoid(W_b x)`` a value head (``ops/delta_rule.py``
  has the recurrence); ``y = W_o (RMSNorm_head(o) * 2 sigmoid(W_z x))``. A
  slot's state is one float32 matrix a value head and the last three inputs
  of the convolution.
- **Experts** (layers from ``first_k_dense_replace``): sigmoid scores over all
  ``router_experts``, the ``num_experts_per_tok`` largest, weights normalised
  over the chosen times ``routed_scaling_factor``; the experts *held here*
  compute their part, dropless (``ops/moe_dropless.py``), and one shared expert
  is added; every gated product clamps its gate from above and its
  up-projection on both sides at ``swiglu_limit``. The leading layers have one
  dense gated FFN of ``intermediate_size`` instead.

One chip's share holds some of the experts and rows ``[0, vocab_size)`` of the
vocabulary; the mixers, norms, routers, shared experts and dense FFNs are whole
(the deployment runs them data-parallel). What the absent experts would have
added is left out and the partial result goes on to the next layer (the
model-configs guide, section 4). Nothing here stands in for absent chips. The
source's two multi-token-prediction layers are not built: the serving forward
does not run them.

Serving: :class:`GigaChat35Decoder` is the model's face to ``DecodeEngine``
(``models/decoder.py``): per slot, one latent cache ``[B, S, rank + rope]`` a
latent layer, its rows padded to whole lanes (no reset at admission) and, a delta-net layer, the matrix state
and the convolution's tail (zeroed inside the slot's first prefill program).
Weights, every matmul's operands and the cached rows take the model's dtype;
the residual stream (each sub-layer's output goes into its post-norm and the
sum unrounded: ten unit-size terms are added), the norms, the delta net's
state and decay and the gates stay float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import mla_attention as _mla
from ..ops import rope as _rope
from ..ops.delta_rule import delta_rule_chunked, delta_rule_step
from ..ops.moe_dropless import dropless_experts, gated_ffn, route_topk
from .decoder import BufferSpec, Decoder

__all__ = ["GigaChat35Config", "GigaChat35ForCausalLM", "GigaChat35Decoder"]

_INNER_CHUNK = 64     # the chunkwise delta rule's inner chunk


class GigaChat35Config:
    """Sizes of the model, or of the share of it held here: ``vocab_size`` and
    ``held_experts`` are what *this* holder has; ``router_experts`` is the
    router's full width."""

    def __init__(self, *, hidden_size: int, num_hidden_layers: int, full_attention_layers: Sequence[int],
                 first_k_dense_replace: int, num_attention_heads: int, q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int, linear_num_key_heads: int,
                 linear_num_value_heads: int, linear_key_head_dim: int, linear_value_head_dim: int,
                 linear_conv_kernel_dim: int = 4, vocab_size: int, intermediate_size: int, moe_intermediate_size: int,
                 router_experts: int, held_experts: Optional[Tuple[int, int]] = None, n_shared_experts: int = 1,
                 num_experts_per_tok: int = 8, norm_topk_prob: bool = True, routed_scaling_factor: float = 1.0,
                 swiglu_limit: Optional[float] = None, rms_norm_eps: float = 1e-6, linear_attn_o_norm_eps: float = 1e-6,
                 layernorm_gating_weight: float = 2.0, linear_sigmoid_gate_scale: float = 2.0,
                 rope_theta: float = 10000.0, rope_scaling: Optional[dict] = None, use_mla_scaling_factor: bool = True,
                 max_position_embeddings: int = 1 << 18):
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.full_attention_layers = tuple(int(i) for i in full_attention_layers)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.num_attention_heads = int(num_attention_heads)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), int(kv_lora_rank)
        self.qk_nope_head_dim, self.qk_rope_head_dim = int(qk_nope_head_dim), int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.linear_num_key_heads, self.linear_num_value_heads = int(linear_num_key_heads), int(linear_num_value_heads)
        self.linear_key_head_dim, self.linear_value_head_dim = int(linear_key_head_dim), int(linear_value_head_dim)
        self.linear_conv_kernel_dim = int(linear_conv_kernel_dim)
        self.vocab_size = int(vocab_size)
        self.intermediate_size, self.moe_intermediate_size = int(intermediate_size), int(moe_intermediate_size)
        self.router_experts = int(router_experts)
        self.held_experts = (0, self.router_experts) if held_experts is None else (int(held_experts[0]), int(held_experts[1]))
        self.n_shared_experts = int(n_shared_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.swiglu_limit = None if swiglu_limit is None else float(swiglu_limit)
        self.rms_norm_eps, self.linear_attn_o_norm_eps = float(rms_norm_eps), float(linear_attn_o_norm_eps)
        self.layernorm_gating_weight = float(layernorm_gating_weight)
        self.linear_sigmoid_gate_scale = float(linear_sigmoid_gate_scale)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {})
        self.use_mla_scaling_factor = bool(use_mla_scaling_factor)
        self.max_position_embeddings = int(max_position_embeddings)
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("delta-net value heads are no multiple of the key heads")
        if any(not 0 <= i < self.num_hidden_layers for i in self.full_attention_layers):
            raise ValueError(f"full_attention_layers {self.full_attention_layers} outside the {self.num_hidden_layers} layers")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"first_k_dense_replace {self.first_k_dense_replace} outside the {self.num_hidden_layers} layers")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.router_experts):
            raise ValueError(f"held_experts {self.held_experts} outside the router's {self.router_experts}")

    @classmethod
    def from_config_file(cls, cfg: dict) -> "GigaChat35Config":
        """From a configuration file of the benchmark: the source's keys at
        the top level; ``n_routed_experts`` is what is held here, the router's
        width is the published count."""
        return cls(
            hidden_size=cfg["hidden_size"], num_hidden_layers=cfg["num_hidden_layers"],
            full_attention_layers=cfg["full_attention_layers"], first_k_dense_replace=cfg["first_k_dense_replace"],
            num_attention_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
            linear_num_key_heads=cfg["linear_num_key_heads"], linear_num_value_heads=cfg["linear_num_value_heads"],
            linear_key_head_dim=cfg["linear_key_head_dim"], linear_value_head_dim=cfg["linear_value_head_dim"],
            linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"], vocab_size=cfg["vocab_size"],
            intermediate_size=cfg["intermediate_size"], moe_intermediate_size=cfg["moe_intermediate_size"],
            router_experts=cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]),
            held_experts=cfg.get("held_experts"), n_shared_experts=cfg["n_shared_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"], norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling_factor=cfg["routed_scaling_factor"], swiglu_limit=cfg.get("swiglu_limit"),
            rms_norm_eps=cfg["rms_norm_eps"], linear_attn_o_norm_eps=cfg["linear_attn_o_norm_eps"],
            layernorm_gating_weight=cfg["layernorm_gating_weight"], linear_sigmoid_gate_scale=cfg["linear_sigmoid_gate_scale"],
            rope_theta=cfg["rope_theta"], rope_scaling=cfg.get("rope_scaling"),
            use_mla_scaling_factor=cfg.get("use_mla_scaling_factor", True),
            max_position_embeddings=cfg["max_position_embeddings"])

    # ------------------------------------------------------------- layout
    @property
    def linear_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers) if i not in self.full_attention_layers)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace, self.num_hidden_layers))

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """A cached row as the device stores it: ``latent_width`` padded with
        zeros to whole lanes (576 -> 640). The TPU's tiled layout pads the
        last axis so in any case; made explicit, a kernel can copy whole rows."""
        return -(-self.latent_width // 128) * 128

    @property
    def conv_channels(self) -> int:
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim + self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def yarn(self) -> Optional[dict]:
        """``rope_scaling`` where it is YaRN's, else None."""
        s = self.rope_scaling
        return s if s.get("type", s.get("rope_type")) == "yarn" else None

    def inv_freq(self):
        """The rotation's frequencies, YaRN's where ``rope_scaling`` says so: a
        host constant of ``qk_rope_head_dim / 2`` float32."""
        s = self.yarn
        if s is None:
            return _rope.yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta)
        return _rope.yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta, s["factor"], s["beta_fast"], s["beta_slow"],
                                   s["original_max_position_embeddings"])

    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.use_mla_scaling_factor and self.yarn is not None:
            scale *= _rope.yarn_mscale(self.yarn["factor"], self.yarn.get("mscale_all_dim", 0)) ** 2
        return scale

    def fingerprint(self) -> tuple:
        return ("gigachat3_5",) + tuple(
            tuple(sorted(v.items())) if isinstance(v, dict) else v for _, v in sorted(vars(self).items()))

    def weight_shapes(self) -> Dict[str, tuple]:
        """Every weight by name. Per-layer weights are stacked: ``[L, ...]``
        over all layers for the four block norms, over the latent layers for
        ``mla_*``, over the delta-net layers for ``gdn_*``, over the leading
        dense layers for ``dense_*`` and over the expert layers for the
        router, the experts and the shared expert."""
        D, L = self.hidden_size, self.num_hidden_layers
        Lm, Lg, Le, Ld = len(self.full_attention_layers), len(self.linear_layers), len(self.expert_layers), self.first_k_dense_replace
        H, dn, dr, dv = self.num_attention_heads, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        Hv, K = self.linear_num_value_heads, self.linear_conv_kernel_dim
        z = Hv * self.linear_value_head_dim
        F, Fd, held = self.moe_intermediate_size, self.intermediate_size, self.held_experts[1]
        return {
            "embed": (self.vocab_size, D), "head": (self.vocab_size, D), "final_norm": (D,),
            "norm_pre1": (L, D), "norm_post1": (L, D), "norm_pre2": (L, D), "norm_post2": (L, D),
            "mla_q_down": (Lm, D, self.q_lora_rank), "mla_q_norm": (Lm, self.q_lora_rank),
            "mla_q_up": (Lm, self.q_lora_rank, H * (dn + dr)), "mla_kv_down": (Lm, D, self.latent_width),
            "mla_kv_norm": (Lm, self.kv_lora_rank), "mla_k_up": (Lm, self.kv_lora_rank, H * dn),
            "mla_v_up": (Lm, self.kv_lora_rank, H * dv), "mla_gate": (Lm, D, H * dv), "mla_out": (Lm, H * dv, D),
            "gdn_in": (Lg, D, self.conv_channels + z), "gdn_ab": (Lg, D, 2 * Hv), "gdn_conv": (Lg, K, self.conv_channels),
            "gdn_dt_bias": (Lg, Hv), "gdn_a_log": (Lg, Hv), "gdn_out_norm": (Lg, self.linear_value_head_dim),
            "gdn_out": (Lg, z, D),
            "dense_gate_up": (Ld, D, 2 * Fd), "dense_down": (Ld, Fd, D),
            "router": (Le, D, self.router_experts),
            "experts_gate_up": (Le, held, D, 2 * F), "experts_down": (Le, held, F, D),
            "shared_gate_up": (Le, D, 2 * F * self.n_shared_experts), "shared_down": (Le, F * self.n_shared_experts, D),
        }


# kept float32 whatever the model's dtype: the decay's parameters
F32_WEIGHTS = ("gdn_dt_bias", "gdn_a_log")
# held as one array a layer (a tuple over the expert layers, indexed like a stack): the grouped matmul takes a layer's
# experts whole, and a slice of a stack would be copied out for it
PER_LAYER_WEIGHTS = ("experts_gate_up", "experts_down")
# scales that are 1 at a weight of 0 (``2 sigmoid(w)``, ``1 + w``): drawn around 0
ZERO_CENTRED = ("final_norm", "norm_pre1", "norm_post1", "norm_pre2", "norm_post2", "mla_q_norm", "mla_kv_norm", "gdn_out_norm")


def init_weights(cfg: GigaChat35Config, seed: int, dtype: str = "bfloat16"):
    """Every weight from ``seed``, on the device, in ``dtype``, in one jitted
    call. Matrices and the zero-centred norm weights N(0, 0.02) (so that a
    dropped scale shows); ``A_log = log U(1, 16)`` and ``dt_bias =
    softplus^-1(U(1e-3, 0.1))``: the decay starts near 1 and the state carries
    history."""
    make = _weight_maker(tuple(sorted(cfg.weight_shapes().items())), str(dtype))
    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


@functools.lru_cache(maxsize=None)
def _weight_maker(shapes: tuple, dtype: str):
    dt = jnp.dtype(dtype)

    def one(name, shape, k):
        if name == "gdn_a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))  # noqa: PTA304 (jax.random, a key folded from the seed)
        if name == "gdn_dt_bias":
            dt0 = jax.random.uniform(k, shape, jnp.float32, 1e-3, 0.1)  # noqa: PTA304 (jax.random, a key folded from the seed)
            return dt0 + jnp.log(-jnp.expm1(-dt0))                      # softplus^-1
        if name in PER_LAYER_WEIGHTS:
            return tuple(one("", shape[1:], jax.random.fold_in(k, layer)) for layer in range(shape[0]))
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    def make(key):
        return {shapes[i][0]: one(shapes[i][0], shapes[i][1], jax.random.fold_in(key, i)) for i in range(len(shapes))}

    return jax.jit(make)


# ------------------------------------------------------------------ pieces
def _norm(cfg, x, w):
    """``x / rms(x) * g sigmoid(w)`` (``g = layernorm_gating_weight``)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    return (y * (cfg.layernorm_gating_weight * jax.nn.sigmoid(w.astype(jnp.float32)))).astype(x.dtype)


def _layer(p: dict, prefix: str, i: int) -> dict:
    """Entry ``i`` of every stacked weight whose name starts with ``prefix``."""
    return {k: v[i] for k, v in p.items() if k.startswith(prefix)}


def _moe(cfg: GigaChat35Config, p: dict, ei: int, x, routed=None):
    """``(routed part of the held experts + shared expert [T, D] float32,
    stats)`` for rows ``x [T, D]`` of expert layer ``ei``. ``routed``, a list, is handed the
    experts each row chose (``[T, k]``): what :func:`chunk_routing` and
    :func:`decode_probe` report."""
    w, idx = route_topk(x, p["router"][ei], top_k=cfg.num_experts_per_tok,
                        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
    y, stats = dropless_experts(x, w, idx, p["experts_gate_up"][ei], p["experts_down"][ei],
                                held=cfg.held_experts, n_experts=cfg.router_experts, limit=cfg.swiglu_limit)
    with jax.named_scope("moe_shared"):
        y = y + gated_ffn(x, p["shared_gate_up"][ei], p["shared_down"][ei], cfg.swiglu_limit)
    if routed is not None:
        routed.append(idx)  # noqa: PTA104 (a host list filled while tracing)
    return y, stats


def _dense(cfg, p, di: int, x):
    with jax.named_scope("mlp"):
        return gated_ffn(x, p["dense_gate_up"][di], p["dense_down"][di], cfg.swiglu_limit)


def _mla_project(cfg, lp, x, positions):
    """The latent layer's projections of rows ``x [T, D]`` at ``positions
    [T]``: ``q_nope [T, H, dn]`` and the rotated ``q_rope [T, H, rope]``, both
    scaled by the softmax scale, and the rows' cache entries ``[c | rotated
    k_r | 0] [T, cache_width]``, all in ``x``'s dtype."""
    T = x.shape[0]
    H, dn, dr, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla_q"):
        c_q = _norm(cfg, jnp.matmul(x, lp["mla_q_down"]), lp["mla_q_norm"])
        q = jnp.matmul(c_q, lp["mla_q_up"], preferred_element_type=jnp.float32).reshape(T, H, dn + dr) * cfg.softmax_scale()
    with jax.named_scope("mla_kv"):
        ckv = jnp.matmul(x, lp["mla_kv_down"], preferred_element_type=jnp.float32)
        c = _norm(cfg, ckv[:, :rank], lp["mla_kv_norm"])
    with jax.named_scope("rope"):
        cos, sin = _rope.rope_angles(positions, cfg.inv_freq())
        q_rope = _rope.rotate(q[..., dn:], cos[:, None], sin[:, None])
        k_r = _rope.rotate(ckv[:, rank:], cos, sin)
    row = _lanes(cfg, jnp.concatenate([c, k_r], axis=-1).astype(x.dtype))
    return q[..., :dn].astype(x.dtype), q_rope.astype(x.dtype), row


def _lanes(cfg, a):
    """``a [..., latent_width]`` padded with zeros to the cache's width."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, cfg.cache_width - cfg.latent_width),))


def _mla_out(cfg, lp, x, att):
    """``W_o (att * sigmoid(W_g x))`` for ``att [T, H, dv]``, float32."""
    with jax.named_scope("mla_out"):
        gate = jax.nn.sigmoid(jnp.matmul(x, lp["mla_gate"], preferred_element_type=jnp.float32))
        return jnp.matmul((att.reshape(att.shape[0], -1).astype(jnp.float32) * gate).astype(x.dtype), lp["mla_out"],
                          preferred_element_type=jnp.float32)


def _mla_chunk(cfg, lp, x, latent, slot, start):
    """The latent mixer over ``C`` tokens ``x [C, D]`` of one slot at ``start``
    against the layer's cache ``[B, S, cache_width]``: the chunk's rows are
    written in place, then every row attends the slot's rows up to its own.
    Returns ``(y [C, D], latent)``."""
    C, H, rank = x.shape[0], cfg.num_attention_heads, cfg.kv_lora_rank
    q_nope, q_rope, rows = _mla_project(cfg, lp, x, start + jnp.arange(C, dtype=jnp.int32))
    with jax.named_scope("cache_write"):
        latent = jax.lax.dynamic_update_slice(latent, rows[None], (slot, start, 0))
    att = _mla.prefill(q_nope, q_rope, latent, lp["mla_k_up"].reshape(rank, H, -1), lp["mla_v_up"].reshape(rank, H, -1),
                       slot, start, rank=rank)
    return _mla_out(cfg, lp, x, att), latent


def _mla_decode(cfg, lp, x, latent, pos, active):
    """The latent mixer for one token of every slot, ``x [B, D]``, absorbed:
    each head's key up-projection folded into its query, the value
    up-projection applied to what the heads read of the latents."""
    H, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    q_nope, q_rope, row = _mla_project(cfg, lp, x, pos)
    with jax.named_scope("mla_q"):
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope, lp["mla_k_up"].reshape(rank, H, -1))
    q = _lanes(cfg, jnp.concatenate([q_lat, q_rope], axis=-1))
    o_lat, latent = _mla.decode(q, row, latent, pos, active, rank=rank)
    with jax.named_scope("mla_out"):
        att = jnp.einsum("bhc,chd->bhd", o_lat, lp["mla_v_up"].reshape(rank, H, -1))
    return _mla_out(cfg, lp, x, att), latent


def _gdn_project(cfg, lp, x):
    """The delta-net layer's projections of rows ``x [T, D]``: the
    convolution's input ``qkv [T, conv_channels]`` (``x``'s dtype), and in
    float32 the output gate's argument ``z [T, Hv * dv]``, ``log_alpha [T, Hv,
    1]`` and ``beta [T, Hv]``."""
    Hv = cfg.linear_num_value_heads
    with jax.named_scope("linear_proj"):
        h = jnp.matmul(x, lp["gdn_in"], preferred_element_type=jnp.float32)
        qkv, z = h[:, :cfg.conv_channels].astype(x.dtype), h[:, cfg.conv_channels:]
        ab = jnp.matmul(x, lp["gdn_ab"], preferred_element_type=jnp.float32)
        dt = jax.nn.softplus(ab[:, :Hv] + lp["gdn_dt_bias"])
        log_alpha = (-jnp.exp(lp["gdn_a_log"]) * dt)[..., None]
        beta = jax.nn.sigmoid(ab[:, Hv:])
    return qkv, z, log_alpha, beta


def _conv_heads(cfg, lp, window):
    """Depthwise causal convolution + SiLU over ``window [..., K - 1 + T,
    conv_channels]`` (the ``K - 1`` inputs before the run, then the run), and
    the per-head normalisation: ``q, k [..., T, Hk, dk]`` L2-normalised (``q``
    also scaled by ``dk^-1/2``), ``v [..., T, Hv, dv]``; float32."""
    K, Hk, dk = cfg.linear_conv_kernel_dim, cfg.linear_num_key_heads, cfg.linear_key_head_dim
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    T = window.shape[-2] - (K - 1)
    w = lp["gdn_conv"].astype(jnp.float32)
    x = window.astype(jnp.float32)
    y = jax.nn.silu(sum(x[..., j:j + T, :] * w[j] for j in range(K)))      # tap K-1 is the current token
    lead = y.shape[:-1]
    q = y[..., :Hk * dk].reshape(lead + (Hk, dk))
    k = y[..., Hk * dk:2 * Hk * dk].reshape(lead + (Hk, dk))
    v = y[..., 2 * Hk * dk:].reshape(lead + (Hv, dv))
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    return l2(q) * (dk ** -0.5), l2(k), v


def _gdn_out(cfg, lp, o, z, dtype):
    """``W_o (RMSNorm_head(o) * g sigmoid(z))`` for ``o [T, Hv, dv]`` float32
    (``g = linear_sigmoid_gate_scale``; the norm's scale is ``1 + w``); the
    product's operands in ``dtype``, the result float32."""
    with jax.named_scope("linear_out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.linear_attn_o_norm_eps)
        o = o * (1.0 + lp["gdn_out_norm"].astype(jnp.float32))
        gate = cfg.linear_sigmoid_gate_scale * jax.nn.sigmoid(z)
        return jnp.matmul((o.reshape(o.shape[0], -1) * gate).astype(dtype), lp["gdn_out"], preferred_element_type=jnp.float32)


def _gdn_chunk(cfg, lp, x, state, tail, n_valid):
    """The delta-net mixer over ``C`` tokens ``x [C, D]`` of one sequence, from
    ``state [Hv, dk, dv]`` (float32) and the convolution's ``tail [K - 1,
    conv_channels]``. Rows at ``n_valid`` and after are padding: they decay
    nothing, write nothing and leave the tail alone. Returns ``(y [C, D],
    state, tail)``."""
    C = x.shape[0]
    K = cfg.linear_conv_kernel_dim
    qkv, z, log_alpha, beta = _gdn_project(cfg, lp, x)
    with jax.named_scope("linear_core"):
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=0)              # [K-1+C, channels]
        q, k, v = _conv_heads(cfg, lp, window)
        valid = jnp.arange(C) < n_valid
        log_alpha = jnp.where(valid[:, None, None], log_alpha, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
        inner = min(_INNER_CHUNK, C)
        pad = (-C) % inner                  # padding rows: alpha 1, beta 0
        heads_first = lambda a: jnp.pad(jnp.moveaxis(a, 0, 1), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        o, state = delta_rule_chunked(heads_first(q), heads_first(k), heads_first(v), heads_first(log_alpha),
                                      heads_first(beta), state, chunk=inner)
        o = jnp.moveaxis(o[:, :C], 0, 1)                                             # [C, Hv, dv]
        tail = jax.lax.dynamic_slice_in_dim(window, n_valid, K - 1, axis=0).astype(tail.dtype)
    return _gdn_out(cfg, lp, o, z, x.dtype), state, tail


def _gdn_decode(cfg, lp, x, state, tail, active):
    """The delta-net mixer for one token of every slot: ``x [B, D]``, ``state
    [B, Hv, dk, dv]``, ``tail [B, K - 1, conv_channels]``. A slot that is not
    active keeps its state and tail bitwise."""
    qkv, z, log_alpha, beta = _gdn_project(cfg, lp, x)
    with jax.named_scope("linear_core"):
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv[:, None]], axis=1)      # [B, K, channels]
        q, k, v = _conv_heads(cfg, lp, window)
        log_alpha = jnp.where(active[:, None, None], log_alpha, 0.0)
        beta = jnp.where(active[:, None], beta, 0.0)
        o, state = delta_rule_step(q[:, 0], k[:, 0], v[:, 0], log_alpha, beta, state)
        tail = jnp.where(active[:, None, None], window[:, 1:].astype(tail.dtype), tail)
    return _gdn_out(cfg, lp, o, z, x.dtype), state, tail


def _head(cfg, p, h):
    with jax.named_scope("norm"):
        h = _norm(cfg, h, p["final_norm"]).astype(p["head"].dtype)
    with jax.named_scope("head_loss"):
        return jnp.matmul(h, p["head"].T, preferred_element_type=jnp.float32)


def _admitting(start):
    """A slot's first prefill program: the one that runs at position 0."""
    return start == 0


def _layers(cfg: GigaChat35Config, p: dict, cache, ids, latent, linear, routed=None):
    """Both forwards' walk from token ids ``[T]`` to hidden rows ``[T, D]``
    (float32): the
    embedding, then ``norm -> mixer -> norm -> residual -> norm -> FFN -> norm
    -> residual`` a layer, the mixer and the FFN each of the layer's own kind.
    ``cache`` is the engine's flat tuple of buffers: a latent cache a latent
    layer, then a state a delta-net layer, then a conv tail a delta-net layer.
    The forward's two mixers, ``latent(mi, x, rows) -> (y, rows)`` and
    ``linear(gi, x, state, tail) -> (y, state, tail)``, are told which latent
    or delta-net layer this is and handed its buffers whole. Returns ``(h,
    cache, stats int32[2])`` with the routed experts' load summed over the
    layers."""
    n_lat, n_lin = len(cfg.full_attention_layers), len(cfg.linear_layers)
    latents, states = list(cache[:n_lat]), list(cache[n_lat:n_lat + n_lin])
    tails = list(cache[n_lat + n_lin:n_lat + 2 * n_lin])
    with jax.named_scope("embed"):
        h = jnp.take(p["embed"], ids, axis=0)
    dtype, h = h.dtype, h.astype(jnp.float32)     # the residual stream is float32; a sub-layer's input the model's dtype
    mi = gi = 0
    stats = jnp.zeros((2,), jnp.int32)

    def normed(name, layer, x):
        with jax.named_scope("norm"):
            return _norm(cfg, x, p[name][layer])

    for layer in range(cfg.num_hidden_layers):  # noqa: PTA104 (static unroll, host loop bound)
        x = normed("norm_pre1", layer, h).astype(dtype)
        if layer in cfg.full_attention_layers:
            y, latents[mi] = latent(mi, x, latents[mi])  # noqa: PTA104 (static unroll, host loop bound)
            mi += 1
        else:
            y, states[gi], tails[gi] = linear(gi, x, states[gi], tails[gi])  # noqa: PTA104 (static unroll, host loop bound)
            gi += 1
        h = h + normed("norm_post1", layer, y.astype(jnp.float32))
        x = normed("norm_pre2", layer, h).astype(dtype)
        if layer < cfg.first_k_dense_replace:
            y = _dense(cfg, p, layer, x)
        else:
            y, s = _moe(cfg, p, layer - cfg.first_k_dense_replace, x, routed)
            stats = stats + s
        h = h + normed("norm_post2", layer, y.astype(jnp.float32))
    return h, (*latents, *states, *tails), stats


def _chunk_forward(cfg: GigaChat35Config, p: dict, cache, ids, slot, start, n_valid, want_rows, routed=None):
    """``C`` tokens ``ids [C]`` of slot ``slot`` at ``start`` through every
    layer. At ``start == 0`` the slot is being admitted: its state and tail
    start from zero, whatever an earlier request left there. ``want_rows``:
    ``None`` (no logits), a traced row index (that row's logits ``[1, V]``) or
    ``"all"`` (``[C, V]``). Returns ``(logits | None, cache)``."""
    fresh = _admitting(start)

    def latent(mi, x, rows):
        return _mla_chunk(cfg, _layer(p, "mla_", mi), x, rows, slot, start)

    def linear(gi, x, states, tails):
        st = jax.lax.dynamic_slice_in_dim(states, slot, 1, axis=0)[0].astype(jnp.float32)
        tl = jax.lax.dynamic_slice_in_dim(tails, slot, 1, axis=0)[0]
        st, tl = jnp.where(fresh, 0.0, st), jnp.where(fresh, jnp.zeros_like(tl), tl)
        y, st, tl = _gdn_chunk(cfg, _layer(p, "gdn_", gi), x, st, tl, n_valid)
        return (y, jax.lax.dynamic_update_slice(states, st[None].astype(states.dtype), (slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(tails, tl[None], (slot, 0, 0)))

    h, cache, _ = _layers(cfg, p, cache, ids, latent, linear, routed)
    if want_rows is None:
        return None, cache
    if not isinstance(want_rows, str):
        h = jax.lax.dynamic_slice_in_dim(h, want_rows, 1, axis=0)
    return _head(cfg, p, h), cache


def _decode_forward(cfg: GigaChat35Config, p: dict, cache, tok, pos, active, routed=None):
    """One token of every slot: ``tok``, ``pos`` ``[B]``; writes gated by
    ``active``. Returns ``(logits [B, V], cache, stats int32[2])`` with the
    routed experts' load summed over the layers."""
    def latent(mi, x, rows):
        return _mla_decode(cfg, _layer(p, "mla_", mi), x, rows, pos, active)

    def linear(gi, x, state, tail):
        return _gdn_decode(cfg, _layer(p, "gdn_", gi), x, state, tail, active)

    h, cache, stats = _layers(cfg, p, cache, tok, latent, linear, routed)
    return _head(cfg, p, h), cache, stats


def chunk_routing(cfg: GigaChat35Config, p: dict, cache, ids, slot, start, n_valid):
    """The chunk forward of the engine's prefill programs, also saying which
    experts every row chose in every expert layer: ``(cache, experts [Le, C,
    k])``. For a comparison that has to follow the program's routing where two
    scores tie within rounding (``benchmark/families/gigachat3_5.py``)."""
    routed = []
    _, cache = _chunk_forward(cfg, p, cache, ids, slot, start, n_valid, None, routed)
    return cache, jnp.stack(routed)


def decode_probe(cfg: GigaChat35Config, p: dict, cache, tok, pos, active):
    """The decode forward of the engine's decode program with its routing:
    ``(logits [B, V], experts [Le, B, k], cache)``."""
    routed = []
    logits, cache, _ = _decode_forward(cfg, p, cache, tok, pos, active, routed)
    return logits, jnp.stack(routed), cache


# ------------------------------------------------------------------ decoder
class GigaChat35Decoder(Decoder):
    """The model through the serving engine's interface."""

    recurrent = True
    n_stats = 2
    stat_counters = ("infer.moe.assignments_local", "infer.moe.experts_hit")

    def __init__(self, model: "GigaChat35ForCausalLM"):
        self.cfg = model.cfg
        self._weights = model.weights
        self.vocab_size = model.cfg.vocab_size
        self.max_positions = model.cfg.max_position_embeddings
        self.dtype = model.weights["embed"].dtype

    def params(self, int8: bool = False):
        if int8:
            raise NotImplementedError("GigaChat35 has no int8 weights")
        return dict(self._weights)

    def fingerprint(self) -> tuple:
        return self.cfg.fingerprint()

    def buffer_specs(self, slots: int, rows: int, kv_dtype=None):
        c = self.cfg
        B, n_lat, n_lin = int(slots), len(c.full_attention_layers), len(c.linear_layers)
        dt = str(self.dtype)
        state = (B, c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim)
        # each one buffer a layer: a decode step rewrites the state and the tail whole, and a stack of them would be
        # copied to be rebuilt; the latent cache is aliased through the decode kernel
        return (*(BufferSpec(f"latent{i}", (B, int(rows), c.cache_width), dt, 0, False) for i in range(n_lat)),
                *(BufferSpec(f"state{i}", state, "float32", 0, True) for i in range(n_lin)),
                *(BufferSpec(f"conv{i}", (B, c.linear_conv_kernel_dim - 1, c.conv_channels), dt, 0, True)
                  for i in range(n_lin)))

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
class GigaChat35ForCausalLM(nn.Layer):
    """The model (or one chip's share of it) with its weights as plain device
    arrays under ``weights`` (``GigaChat35Config.weight_shapes`` names them);
    made from ``seed`` unless given."""

    def __init__(self, cfg: GigaChat35Config, seed: int = 0, dtype: str = "bfloat16", weights: Optional[dict] = None):
        super().__init__()
        self.cfg = cfg
        self.weights = init_weights(cfg, seed, dtype) if weights is None else dict(weights)

    def decoder(self) -> GigaChat35Decoder:
        """What the serving engine runs this model through."""
        return GigaChat35Decoder(self)

    def forward(self, input_ids):
        """Logits ``[b, s, V]`` (float32) of whole sequences, each from an
        empty state: the chunk forward over a scratch cache."""
        from ..framework.core import unwrap
        from ..tensor._helpers import _wrap_value

        ids = jnp.asarray(unwrap(input_ids), jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        scratch = self.decoder().alloc(1, ids.shape[1])
        one = lambda row: _chunk_forward(self.cfg, self.weights, scratch, row, jnp.int32(0), jnp.int32(0),  # noqa: E731
                                         jnp.int32(ids.shape[1]), "all")[0]
        return _wrap_value(jnp.stack([one(row) for row in ids]))

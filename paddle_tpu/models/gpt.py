"""GPT decoder-only language model — the flagship pretraining model.

Parity: the reference ships GPT as its auto-parallel/fleet workhorse
(python/paddle/fluid/tests/unittests/auto_parallel_gpt_model.py;
ppfleetx-style GPT built from paddle.nn.TransformerDecoder + the TP layers in
fleet/meta_parallel/parallel_layers/mp_layers.py:30,95,171,251).

TPU-first: every parallelism is a sharding annotation, not a wrapper —
  * vocab over 'mp' (VocabParallelEmbedding),
  * attention heads + ffn hidden over 'mp' (Column/RowParallelLinear),
  * batch over 'dp'×'sdp' (fleet.distributed_step input sharding),
  * sequence over 'sep' for long context (ring/Ulysses attention in
    distributed/ring_attention.py can replace the core here),
  * layers stackable over 'pp' via distributed/pipeline.spmd_pipeline.
The attention core dispatches to the Pallas flash kernel on TPU
(ops/flash_attention.py), replacing fused_attention_op.cu /
fused_multi_transformer_op.cu.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import nn
from ..distributed.mp_layers import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..framework import random as _random
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import registry as _registry
from ..tensor import manipulation as M


class GPTConfig:
    """Hyperparameters. ``gpt3_1p3b()`` is the BASELINE.json config #4 model.

    ``stacked=True`` (default) builds the trunk as :class:`GPTBlockStack` —
    all L blocks as [L, ...]-stacked parameters, run as a statically unrolled
    loop over the layers (:func:`_stack_forward` says why not ``lax.scan``)
    or, under a fleet mesh with pp_degree>1, via the spmd_pipeline over the
    'pp' axis. ``recompute=True`` turns on per-layer rematerialization inside
    the loop/pipeline (activation memory ~O(L·input) instead of
    O(L·all-intermediates)).
    """

    def __init__(
        self,
        vocab_size=50304,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        ffn_hidden_size=None,
        max_seq_len=1024,
        dropout=0.0,
        attn_dropout=0.0,
        initializer_range=0.02,
        use_flash=True,
        stacked=True,
        recompute=False,
        recompute_granularity="full",
        moe=0,
        moe_num_experts=0,
        moe_every=2,
        moe_top_k=2,
        moe_capacity_factor=1.25,
    ):
        # ``moe=E`` is the one-knob spelling: swap every moe_every-th
        # block's dense FFN for an E-expert MoELayer and pick the per-layer
        # trunk it needs (a stacked trunk assumes homogeneous layers)
        if moe:
            moe_num_experts = moe_num_experts or int(moe)
            stacked = False
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.attn_dropout = attn_dropout
        self.initializer_range = initializer_range
        self.use_flash = use_flash
        self.stacked = stacked
        self.recompute = recompute
        # 'full' recomputes the whole block in backward (max memory saving);
        # 'selective' saves matmul outputs and recomputes the rest (parity:
        # paddle recompute_granularity full vs full_attn/core_attn)
        self.recompute_granularity = recompute_granularity
        # GPT-MoE (GShard / ERNIE-3.0-style sparse FFN): every moe_every-th
        # block swaps its dense FFN for a MoELayer. Requires stacked=False
        # (the [L,...]-stacked trunk assumes homogeneous layers).
        self.moe_num_experts = moe_num_experts
        self.moe_every = moe_every
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        if moe_num_experts and stacked:
            raise ValueError("GPT-MoE needs stacked=False (heterogeneous layers)")
        if moe_num_experts and moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got {moe_every}")

    def to_dict(self):
        """JSON-able constructor kwargs — the cross-process spelling of a
        config (e.g. a speculative-decoding draft model shipped to
        ProcServingFleet replicas over the subprocess spec)."""
        return dict(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            ffn_hidden_size=self.ffn_hidden_size,
            max_seq_len=self.max_seq_len,
            dropout=self.dropout,
            attn_dropout=self.attn_dropout,
            initializer_range=self.initializer_range,
            use_flash=self.use_flash,
            stacked=self.stacked,
            recompute=self.recompute,
        )

    @staticmethod
    def gpt3_1p3b(**kw):
        cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16, max_seq_len=2048)
        cfg.update(kw)
        return GPTConfig(**cfg)

    @staticmethod
    def tiny(**kw):
        cfg = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=128)
        cfg.update(kw)
        return GPTConfig(**cfg)


class GPTAttention(nn.Layer):
    """Causal self-attention, heads sharded over 'mp' via column/row linears."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        assert self.head_dim * cfg.num_heads == cfg.hidden_size
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv_proj = ColumnParallelLinear(cfg.hidden_size, 3 * cfg.hidden_size, weight_attr=init, gather_output=False)
        self.out_proj = RowParallelLinear(cfg.hidden_size, cfg.hidden_size, weight_attr=init, input_is_parallel=True)
        self.attn_dropout = cfg.attn_dropout

    def gen_cache(self, x, static=False, max_seq=None, kv_dtype=None):
        from ..nn.layer.transformer import MultiHeadAttention
        from ..tensor.creation import zeros

        if static:
            # fixed-shape serving cache: preallocated [b, max_seq, h, d],
            # written in place at the carried position — decode keeps one
            # set of shapes (and one compiled program) for the whole run.
            # kv_dtype="int8" preallocates the quantized representation
            # (int8 payload + f32 scale planes) instead of compute-dtype K/V.
            if max_seq is None:
                raise ValueError("gen_cache(static=True) needs max_seq=")
            if kv_dtype is not None:
                if str(kv_dtype) != "int8":
                    raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
                qz = lambda: zeros([x.shape[0], int(max_seq), self.num_heads, self.head_dim], dtype="int8")  # noqa: E731
                sz = lambda: zeros([x.shape[0], int(max_seq), self.num_heads], dtype="float32")  # noqa: E731
                return MultiHeadAttention.QuantizedFixedCache(qz(), sz(), qz(), sz(), zeros([], dtype="int32"))
            empty = lambda: zeros([x.shape[0], int(max_seq), self.num_heads, self.head_dim], dtype=x.dtype)  # noqa: E731
            return MultiHeadAttention.FixedCache(empty(), empty(), zeros([], dtype="int32"))
        empty = lambda: zeros([x.shape[0], 0, self.num_heads, self.head_dim], dtype=x.dtype)
        return MultiHeadAttention.Cache(empty(), empty())

    def forward(self, x, cache=None):
        from ..nn.layer.transformer import MultiHeadAttention

        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("attn_qkv"):
            qkv = self.qkv_proj(x)
            qkv = M.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = (M.squeeze(t, 2) for t in M.split(qkv, 3, axis=2))
        if isinstance(cache, MultiHeadAttention.FixedCache):
            from ..nn.layer.transformer import _fixed_cache_mask, _fixed_cache_write

            kf, vf = _fixed_cache_write(cache, k, v)
            mask = _fixed_cache_mask(cache.pos, s, kf.shape[1])
            out = F.scaled_dot_product_attention(q, kf, vf, attn_mask=mask, dropout_p=self.attn_dropout, training=self.training)
            out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.out_proj(out), MultiHeadAttention.FixedCache(kf, vf, cache.pos + s)
        if isinstance(cache, MultiHeadAttention.QuantizedFixedCache):
            from ..nn.layer.transformer import (
                _fixed_cache_mask,
                _quant_cache_read,
                _quant_cache_write,
            )

            qk, sk = _quant_cache_write(cache.qk, cache.sk, k, cache.pos)
            qv, sv = _quant_cache_write(cache.qv, cache.sv, v, cache.pos)
            kf = _quant_cache_read(qk, sk, q.dtype)
            vf = _quant_cache_read(qv, sv, q.dtype)
            mask = _fixed_cache_mask(cache.pos, s, kf.shape[1])
            out = F.scaled_dot_product_attention(q, kf, vf, attn_mask=mask, dropout_p=self.attn_dropout, training=self.training)
            out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.out_proj(out), MultiHeadAttention.QuantizedFixedCache(qk, sk, qv, sv, cache.pos + s)
        if cache is not None:
            if cache.k.shape[1] > 0:
                k = M.concat([cache.k, k], axis=1)
                v = M.concat([cache.v, v], axis=1)
            cache = MultiHeadAttention.Cache(k, v)
            # new queries attend to all cached keys + causally within the block
            import jax.numpy as jnp

            from ..framework.core import _wrap_value

            past = k.shape[1] - s
            mask = jnp.tril(jnp.ones((s, k.shape[1]), bool), k=past)
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=_wrap_value(mask), dropout_p=self.attn_dropout, training=self.training)
        else:
            with jax.named_scope("attn_core"):
                out = F.scaled_dot_product_attention(q, k, v, is_causal=True, dropout_p=self.attn_dropout, training=self.training)
        with jax.named_scope("attn_out"):
            out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
            out = self.out_proj(out)
        if cache is not None:
            return out, cache
        return out


class GPTBlock(nn.Layer):
    """Pre-LN decoder block (attn + gelu MLP), mp-sharded."""

    def __init__(self, cfg: GPTConfig, use_moe=False):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.norm1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.norm2 = nn.LayerNorm(cfg.hidden_size)
        self.moe = None
        if use_moe:
            from ..distributed.moe import MoELayer

            self.moe = MoELayer(cfg.hidden_size, cfg.ffn_hidden_size,  # noqa: PTA104 (host-side, never traced)
                                num_experts=cfg.moe_num_experts, top_k=cfg.moe_top_k,
                                capacity_factor=cfg.moe_capacity_factor)
        else:
            self.ffn1 = ColumnParallelLinear(cfg.hidden_size, cfg.ffn_hidden_size, weight_attr=init, gather_output=False)  # noqa: PTA104 (host-side, never traced)
            self.ffn2 = RowParallelLinear(cfg.ffn_hidden_size, cfg.hidden_size, weight_attr=init, input_is_parallel=True)  # noqa: PTA104 (host-side, never traced)
        self.dropout = nn.Dropout(cfg.dropout)

    def gen_cache(self, x, static=False, max_seq=None, kv_dtype=None):
        return self.attn.gen_cache(x, static=static, max_seq=max_seq, kv_dtype=kv_dtype)

    def forward(self, x, cache=None):
        with jax.named_scope("norm"):
            h = self.norm1(x)
        if cache is not None:
            att, cache = self.attn(h, cache=cache)
        else:
            att = self.attn(h)
        with jax.named_scope("attn_out"):
            x = x + self.dropout(att)
        with jax.named_scope("norm"):
            h = self.norm2(x)
        with jax.named_scope("mlp"):
            if self.moe is not None:
                x = x + self.dropout(self.moe(h))
            else:
                x = x + self.dropout(self.ffn2(F.gelu(self.ffn1(h), approximate=True)))
        if cache is not None:
            return x, cache
        return x


def _block_apply(lp, h, key, *, num_heads, dropout=0.0, attn_dropout=0.0, epsilon=1e-5):
    """One pre-LN decoder block on raw arrays, the train step's. ``lp`` = (12
    stacked-param slices, layer index); ``key`` = dropout PRNG key or None.

    Not :func:`_serve_block` with a training mixer: besides its norm (the
    closed-form vjp), its attention entry (packed qkv) and dropout, it adds the
    bias before the residual (``h + drop(att @ ow + ob)``) where the serving
    block adds the residual first (``h + att @ ow + ob``). Merging the two
    changes the rounding, and so the compiled program, of one of them."""
    # closed-form vjp: the autodiff of mean/var compiles to extra backward
    # reduce fusions a layer on the TPU (ops/layer_norm.py)
    from ..ops.layer_norm import layer_norm_fused

    (n1w, n1b, qkvw, qkvb, ow, ob, n2w, n2b, f1w, f1b, f2w, f2b), idx = lp

    def drop(v, p, k):
        if p == 0.0 or k is None:
            return v
        keep = jax.random.bernoulli(k, 1.0 - p, v.shape)
        return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)

    k_attn = k_res1 = k_res2 = None
    if key is not None:
        base = jax.random.fold_in(key, idx)
        k_attn, k_res1, k_res2 = (jax.random.fold_in(base, i) for i in range(3))

    b, s, d = h.shape
    hd = d // num_heads
    with jax.named_scope("norm"):
        x1 = layer_norm_fused(h, n1w, n1b, epsilon)
    with jax.named_scope("attn_qkv"):
        qkv = (x1 @ qkvw + qkvb).reshape(b, s, 3, num_heads, hd)
    with jax.named_scope("attn_core"):
        # the packed [b, s, 3, H, dh] projection goes to the ``attention_core``
        # registry entry whole: the flat-lane kernels read q/k/v through index
        # maps and return the packed d(qkv) in backward, with the classic pair
        # and the jnp reference as ordered fallbacks
        att = _registry.dispatch("attention_core", qkv, attn_dropout, k_attn).reshape(b, s, d)
    with jax.named_scope("attn_out"):
        h = h + drop(att @ ow + ob, dropout, k_res1)
    with jax.named_scope("norm"):
        x2 = layer_norm_fused(h, n2w, n2b, epsilon)
    with jax.named_scope("mlp"):
        y = jax.nn.gelu(x2 @ f1w + f1b, approximate=True)
        h = h + drop(y @ f2w + f2b, dropout, k_res2)
    return h


# The part of the block each of the 12 stacked parameters belongs to, in
# ``GPTBlockStack._order``.
_PARAM_SCOPES = ("norm", "norm", "attn_qkv", "attn_qkv", "attn_out", "attn_out",
                 "norm", "norm", "mlp", "mlp", "mlp", "mlp")


def _layer_params(params, idx, i):
    """``lp`` of layer ``i``: its slices of the stacked parameters and its
    index. Each slice is cut under the scope of the part that uses it, so
    the slice — and, in backward, the write of its gradient into the
    stacked gradient — is named like the part."""
    sliced = []
    for j in range(len(params)):
        with jax.named_scope(_PARAM_SCOPES[j]):
            sliced.append(params[j][i])  # noqa: PTA104 (static unroll, host loop bound)
    return tuple(sliced), idx[i]


def _stack_forward(x, *rest, num_layers, num_heads, dropout, attn_dropout, recompute, has_key, mesh, n_micro):
    """Whole-trunk forward on raw arrays, the train step's: an unrolled loop
    over the layers (pp==1) or spmd_pipeline over the 'pp' mesh axis (pp>1)."""
    from ..distributed.pipeline import active_pipeline_schedule, microbatch, spmd_pipeline, unmicrobatch

    if has_key:
        params, key = rest[:-1], rest[-1]
    else:
        params, key = rest, None
    idx = jnp.arange(num_layers, dtype=jnp.int32)
    stacked = (tuple(params), idx)
    block = functools.partial(_block_apply, num_heads=num_heads, dropout=dropout, attn_dropout=attn_dropout)

    def constrain(h):
        """Pin the hidden state's sharding between layers (batch over
        dp×sdp, seq over 'sep', hidden replicated). Without this GSPMD
        flip-flops it between batch- and mp-sharded layouts from one layer to
        the next: 'Involuntary full rematerialization' warnings."""
        if mesh is None:
            return h
        spec = P(("dp", "sdp"), "sep" if mesh.shape.get("sep", 1) > 1 else None, None)
        try:
            return jax.lax.with_sharding_constraint(h, NamedSharding(mesh, spec))
        except (ValueError, TypeError):  # eager run outside jit
            return h

    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        if has_key:
            # fold by microbatch index so the n_micro passes draw distinct
            # dropout masks (the layer index is folded inside _block_apply)
            stage_fn = lambda lp, h, mb, k: block(lp, h, jax.random.fold_in(k, mb))
            extras = (key,)
        else:
            stage_fn = lambda lp, h, mb: block(lp, h, None)
            extras = ()
        xm = microbatch(x, n_micro, mesh)
        out = spmd_pipeline(stage_fn, stacked, xm, mesh, axis="pp", remat=bool(recompute), extras=extras, mb_index=True, schedule=active_pipeline_schedule())
        return unmicrobatch(out, mesh)

    # statically-unrolled layer loop: XLA schedules/fuses across layers and
    # chooses per-layer buffer lifetimes — measured ~20% faster than
    # lax.scan over the stacked axis on TPU (scan also pins all per-layer
    # residual stacks as single live buffers, which OOMs first)
    body = lambda lp, h: block(lp, h, key)
    if recompute == "full":
        body = jax.checkpoint(body)
    elif recompute == "selective":
        # keep matmul outputs (qkv/proj/ffn), recompute cheap elementwise +
        # attention internals — near-baseline speed, most of the memory win
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_saveable)
    h = constrain(x)
    for i in range(num_layers):
        h = constrain(body(_layer_params(params, idx, i), h))
    return h


class GPTBlockStack(nn.Layer):
    """All decoder blocks as [L, ...]-stacked parameters: the leading axis
    shards over 'pp', per-tensor dims over 'mp'.
    pp==1 runs the layers as a statically unrolled loop; pp>1 runs the
    GPipe-schedule spmd_pipeline. Parity: the trunk of
    pp_layers.py:162 PipelineLayer + mp_layers.py TP layers, as shardings.
    """

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        L, D, Ff = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size
        init = I.Normal(0.0, cfg.initializer_range)

        def mk(shape, initializer, mp_dim=None):
            p = self.create_parameter(shape, default_initializer=initializer)
            spec = [None] * len(shape)
            spec[0] = "pp"
            if mp_dim is not None:
                spec[mp_dim] = "mp"  # noqa: PTA104 (host-side, never traced)
            p.dist_spec = P(*spec)
            p.is_distributed = True
            return p

        self.norm1_w = mk([L, D], I.Constant(1.0))
        self.norm1_b = mk([L, D], I.Constant(0.0))
        self.qkv_w = mk([L, D, 3 * D], init, mp_dim=2)
        self.qkv_b = mk([L, 3 * D], I.Constant(0.0), mp_dim=1)
        self.out_w = mk([L, D, D], init, mp_dim=1)
        self.out_b = mk([L, D], I.Constant(0.0))
        self.norm2_w = mk([L, D], I.Constant(1.0))
        self.norm2_b = mk([L, D], I.Constant(0.0))
        self.ffn1_w = mk([L, D, Ff], init, mp_dim=2)
        self.ffn1_b = mk([L, Ff], I.Constant(0.0), mp_dim=1)
        self.ffn2_w = mk([L, Ff, D], init, mp_dim=1)
        self.ffn2_b = mk([L, D], I.Constant(0.0))
        self._order = list(GPTModel._PER_LAYER_TO_STACKED.values())

    def load_blocks(self, blocks):
        """Copy weights from a list of eager :class:`GPTBlock` (parity/test
        helper: LayerList trunk -> stacked trunk)."""
        import numpy as np

        for path, name in GPTModel._PER_LAYER_TO_STACKED.items():  # noqa: PTA102 (host-side, never traced)
            per_layer = [functools.reduce(getattr, path.split("."), b).numpy() for b in blocks]
            getattr(self, name).set_value(jnp.asarray(np.stack(per_layer)))

    def forward(self, x):
        from ..distributed.pipeline import active_pipeline_plan
        from ..tensor._helpers import ensure_tensor, op

        from ..distributed.fleet import fleet

        cfg = self.cfg
        mesh, n_micro = active_pipeline_plan()
        if mesh is None and fleet._hcg is not None:
            mesh = fleet._hcg.mesh  # no pipeline, but constrain activations
        dropping = self.training and (cfg.dropout > 0.0 or cfg.attn_dropout > 0.0)
        params = [getattr(self, n) for n in self._order]
        aux = [_random.key_tensor()] if dropping else []
        return op(
            _stack_forward,
            ensure_tensor(x),
            *params,
            *aux,
            _name="gpt_stack",
            num_layers=cfg.num_layers,
            num_heads=cfg.num_heads,
            dropout=cfg.dropout if dropping else 0.0,
            attn_dropout=cfg.attn_dropout if dropping else 0.0,
            recompute=cfg.recompute_granularity if cfg.recompute else False,
            has_key=dropping,
            mesh=mesh,
            n_micro=n_micro,
        )


# ------------------------------------------------------------- KV-cache packs
# An engine KV cache is either a plain array (compute dtype) or an int8
# pack ``{"q": int8 [..., S, dh], "s": f32 [..., S]}`` with one abs_max
# scale per (layer, slot, head, position) vector — per-head, per-position
# ("per-chunk along S" at chunk=1) granularity, so a row's round-trip error
# is bounded by its own abs_max/127 and never bleeds across positions. The
# helpers below keep every cache-touching forward representation-agnostic:
# writes quantize, attends read a dequantized view whose scale multiply XLA
# folds into the consuming matmul (the QuantizedLinear idiom on the cache).

def _kv_quantize(u):
    """``u [..., dh]`` → ``(q int8 [..., dh], s f32 [...])`` abs_max scales."""
    f = u.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(f), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(f / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def _kv_dequant(pack, dt):
    """Dequantized view of an int8 pack (folds into the consuming matmul)."""
    return (pack["q"].astype(jnp.float32) * pack["s"][..., None]).astype(dt)


def _kvc_read(c, dt):
    """Attend view of a cache: dequantizes a pack, passes arrays through."""
    return _kv_dequant(c, dt) if isinstance(c, dict) else c


def _kvc_update(c, u, idx, gate=None):
    """In-place cache write of a compute-dtype update ``u`` at index tuple
    ``idx`` (scale plane takes ``idx[:-1]``); quantizes iff ``c`` is a pack.
    Where a traced bool ``gate`` is False the cache keeps what it holds."""
    def put(plane, rows, at):
        if gate is not None:
            rows = jnp.where(gate, rows, jax.lax.dynamic_slice(plane, at, rows.shape))
        return jax.lax.dynamic_update_slice(plane, rows, at)

    if isinstance(c, dict):
        q, s = _kv_quantize(u)
        return {"q": put(c["q"], q, idx), "s": put(c["s"], s, idx[:-1])}
    return put(c, u, idx)


def _kvc_copy(c, seg, idx):
    """Copy an already-stored segment (same representation as ``c``) into the
    cache at ``idx`` — the prefix-cache insert: a pack segment moves int8
    payload + scale planes verbatim, never round-tripping through f32."""
    if isinstance(c, dict):
        return {"q": jax.lax.dynamic_update_slice(c["q"], seg["q"], idx),
                "s": jax.lax.dynamic_update_slice(c["s"], seg["s"], idx[:-1])}
    return jax.lax.dynamic_update_slice(c, seg, idx)


def _kvc_slice(c, idx, size):
    """Slice a segment out of the cache in its STORED representation (the
    prefix-cache extract; pair with :func:`_kvc_copy` to re-insert)."""
    if isinstance(c, dict):
        return {"q": jax.lax.dynamic_slice(c["q"], idx, size),
                "s": jax.lax.dynamic_slice(c["s"], idx[:-1], size[:-1])}
    return jax.lax.dynamic_slice(c, idx, size)


def _kv_layer(c, i):
    """Layer ``i`` of a stacked [L, ...] cache (array or pack)."""
    with jax.named_scope("cache_read"):
        if isinstance(c, dict):
            return {"q": c["q"][i], "s": c["s"][i]}
        return c[i]


def _kv_stack(xs):
    """Re-stack per-layer caches (inverse of :func:`_kv_layer`)."""
    with jax.named_scope("cache_write"):
        if isinstance(xs[0], dict):
            return {"q": jnp.stack([x["q"] for x in xs]),
                    "s": jnp.stack([x["s"] for x in xs])}
        return jnp.stack(xs)


def _kv_zeros(shape, dt, kv_dtype=None):
    """A fresh cache buffer: ``shape`` is the payload shape ``[..., S, dh]``;
    ``kv_dtype="int8"`` allocates the quantized pack instead of ``dt``."""
    if kv_dtype == "int8":
        return {"q": jnp.zeros(shape, jnp.int8),
                "s": jnp.zeros(shape[:-1], jnp.float32)}
    return jnp.zeros(shape, dt)


def _layer_norm(x, w, b, epsilon=1e-5):
    """LayerNorm by mean and variance, as the serving forwards compute it (the
    train step's is ``layer_norm_fused``, for its backward)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + epsilon) * w + b


def _serve_block(lp, h, mix, *, num_heads):
    """One pre-LN decoder block of a serving forward: ``h`` [b, s, d] is a
    window of ``s`` tokens a batch row. The forwards differ only in where the
    window's keys and values go and which cached rows it attends, which is
    ``mix``: ``q, k, v`` [b, H, s, dh] -> ``(att [b, s, d], *cache)``. Returns
    ``(h, *cache)``. The per-row math does not depend on the mixer, which is
    what keeps bucketed prefill, chunked prefill, decode and the speculative
    window bitwise equal on the same rows. Parity: the per-layer decode of
    fused_multi_transformer_op.cu, as lax ops on a static-shape cache."""
    (n1w, n1b, qkvw, qkvb, ow, ob, n2w, n2b, f1w, f1b, f2w, f2b), _ = lp
    b, s, d = h.shape
    with jax.named_scope("norm"):
        x1 = _layer_norm(h, n1w, n1b)
    with jax.named_scope("attn_qkv"):
        # the product is formed whole before it is cut into q, k, v and heads: XLA otherwise folds that split and the
        # transpose into the matmul's output layout and re-lays the weight to match, so each layer's slice of the
        # stacked qkv weight is copied out into VMEM every step (24 x 25 MB a decode step at width 2,048)
        qkv = jax.lax.optimization_barrier(x1 @ qkvw + qkvb).reshape(b, s, 3, num_heads, d // num_heads)
        q = jnp.swapaxes(qkv[:, :, 0], 1, 2)  # [b, H, s, dh]
        k = jnp.swapaxes(qkv[:, :, 1], 1, 2)
        v = jnp.swapaxes(qkv[:, :, 2], 1, 2)
    att, *cache = mix(q, k, v)
    with jax.named_scope("attn_out"):
        h = h + att @ ow + ob
    with jax.named_scope("norm"):
        x2 = _layer_norm(h, n2w, n2b)
    with jax.named_scope("mlp"):
        y = jax.nn.gelu(x2 @ f1w + f1b, approximate=True)
        h = h + y @ f2w + f2b
    return (h, *cache)


def _merge_heads(att):
    """``att`` [b, H, s, dh] -> [b, s, H * dh]."""
    b, H, s, hd = att.shape
    return jnp.swapaxes(att, 1, 2).reshape(b, s, H * hd)


def _attend_rows(q, rk, rv, first_pos):
    """Causal attention of a window ``q`` [b, H, s, dh] whose row j stands at
    absolute position ``first_pos + j`` (one scalar for the batch) over cached
    rows ``rk``/``rv`` [b, H, S, dh]: row j sees positions up to its own.
    Scores run as bf16 x bf16 -> f32 MXU dots (``preferred_element_type``), so
    no f32 copy of the cache is made; masked lanes contribute exact zeros.
    Returns [b, s, H * dh] in q's dtype."""
    s, hd, S = q.shape[2], q.shape[3], rk.shape[2]
    with jax.named_scope("attn_core"):
        scale = jnp.asarray(1.0 / (hd ** 0.5), q.dtype)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q * scale, rk,
                            preferred_element_type=jnp.float32)
        q_pos = first_pos + jax.lax.broadcasted_iota(jnp.int32, (s, S), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (s, S), 1)
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(rv.dtype)
        att = jnp.einsum("bhqk,bhkd->bhqd", p, rv, preferred_element_type=jnp.float32)
        return _merge_heads(att.astype(q.dtype))


# The cache accessors: what a serving forward's mixer does with one layer of
# the cache. Each writes the window's K/V before it attends, so a stale row
# (a speculative window's rejected tail, an earlier request's) is overwritten
# before it can become visible. They stay apart because they lower to
# different update-slices.

def _batch_write_attend(q, k, v, ck, cv, start_pos):
    """Every batch row's window at the one ``start_pos`` (``generate``, the
    bucketed prefill): ``ck``/``cv`` [b, H, S, dh] or int8 packs."""
    with jax.named_scope("cache_write"):
        ck = _kvc_update(ck, k, (0, 0, start_pos, 0))
        cv = _kvc_update(cv, v, (0, 0, start_pos, 0))
    with jax.named_scope("cache_read"):
        rk = _kvc_read(ck, q.dtype)
        rv = _kvc_read(cv, q.dtype)
    return _attend_rows(q, rk, rv, start_pos), ck, cv


def _chunk_write_attend(q, k, v, ck, cv, slot, start):
    """One slot's chunk (``q`` [1, H, C, dh]) at ``(slot, start)`` of the
    engine's cache ``ck``/``cv`` [B, H, S, dh]: the chunk attends the slot's
    whole row, so everything earlier chunks or a prefix-cache insert wrote."""
    _, H, _, hd = q.shape
    S = (ck["q"] if isinstance(ck, dict) else ck).shape[2]
    with jax.named_scope("cache_write"):
        ck = _kvc_update(ck, k, (slot, 0, start, 0))
        cv = _kvc_update(cv, v, (slot, 0, start, 0))
    with jax.named_scope("cache_read"):
        rk = _kvc_read(_kvc_slice(ck, (slot, 0, 0, 0), (1, H, S, hd)), q.dtype)
        rv = _kvc_read(_kvc_slice(cv, (slot, 0, 0, 0), (1, H, S, hd)), q.dtype)
    return _attend_rows(q, rk, rv, start), ck, cv


def _slot_write_attend(q, k, v, ck, cv, pos, active, layer=None):
    """Per-slot windows (continuous-batching decode, the speculative window):
    the ``decode_attention`` registry entry's lax fallback. ``q``/``k``/``v``
    [b, H, W, dh]; ``ck``/``cv`` [b, H, S, dh] (or int8 packs) are ONE layer,
    cut from the stack by the caller (``layer`` is the kernel's way to find it
    in the stack, and not looked at). The window's K/V are written at ``pos[b]``,
    gated per slot by ``active`` (None: every slot): an inactive slot's cache
    stays bitwise untouched, so a decode dispatch interleaved with that slot's
    chunked prefill cannot clobber its fresh rows at a stale ``pos``. Row j then
    attends keys up to ``pos[b] + j``: slots at different depths share one
    program. Returns (att [b, H, W, dh] in q's dtype, ck, cv)."""
    b, _, s, hd = q.shape
    S = (ck["q"] if isinstance(ck, dict) else ck).shape[2]
    write = jax.vmap(lambda c, u, p, a: _kvc_update(c, u, (0, p, 0), a))
    with jax.named_scope("cache_write"):
        ck, cv = write(ck, k, pos, active), write(cv, v, pos, active)
    with jax.named_scope("cache_read"):
        rk = _kvc_read(ck, q.dtype)
        rv = _kvc_read(cv, q.dtype)
    with jax.named_scope("attn_core"):
        scale = jnp.asarray(1.0 / (hd ** 0.5), q.dtype)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q * scale, rk,
                            preferred_element_type=jnp.float32)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (b, s, S), 2)
        q_pos = pos[:, None, None] + jax.lax.broadcasted_iota(jnp.int32, (b, s, S), 1)
        visible = k_pos <= q_pos  # [b, W, S]: row j sees its slot's prefix + itself
        scores = jnp.where(visible[:, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(rv.dtype)
        att = jnp.einsum("bhqk,bhkd->bhqd", p, rv, preferred_element_type=jnp.float32)
    return att.astype(q.dtype), ck, cv


def _decode_attention_impl(cache, window):
    """The ``decode_attention`` registry entry's choice for a stacked cache
    (array or int8 pack) and a window of ``window`` tokens: the aliased
    Pallas kernel of ``ops/decode_attention.py`` on the TPU for a plain-array
    cache with no mesh, the lax formulation everywhere else. One selection
    per compiled specialization (``kernels.decode_attention.picked`` /
    ``.fallback``)."""
    packed = isinstance(cache, dict)
    return _registry.select("decode_attention", cache["q"] if packed else cache,
                            packed=packed, window=int(window))


_registry.register(
    "decode_attention", "xla", _slot_write_attend, fallback=True,
    doc="lax write-and-attend on one layer cut from the stack (any cache, any device)")


def _embed(wte, wpe, ids, positions):
    """Token plus learned position embedding: ``ids`` [b, s]; ``positions``
    [s] (the same for every batch row) or [b, s]."""
    with jax.named_scope("embed"):
        te, pe = jnp.take(wte, ids, axis=0), jnp.take(wpe, positions, axis=0)
        return (te + pe).astype(wte.dtype)


def _serve_layers(stacked, h, cache_k, cache_v, mix, *, num_heads, in_place=False):
    """The layer walk of every serving forward. ``mix(q, k, v, ck, cv, layer)``
    -> ``(att [b, s, d], ck, cv)`` is a cache accessor. A lax accessor is given
    layer i cut from the stacked [L, ...] caches and ``layer=None``; the layers'
    updated caches come back as two lists for the caller to :func:`_kv_stack`
    (before or after its head: the order is part of a program's text).
    ``in_place`` (the aliased kernel) threads the stacked caches through the
    layers whole with ``layer=i``: the kernel writes and reads layer i where it
    is stored. Statically unrolled, like :func:`_stack_forward`."""
    params, idx = stacked
    ks, vs = [], []
    for i in range(params[0].shape[0]):
        lp = _layer_params(params, idx, i)
        if in_place:
            h, cache_k, cache_v = _serve_block(
                lp, h, functools.partial(mix, ck=cache_k, cv=cache_v, layer=i), num_heads=num_heads)
        else:
            h, ck, cv = _serve_block(
                lp, h, functools.partial(mix, ck=_kv_layer(cache_k, i), cv=_kv_layer(cache_v, i), layer=None),
                num_heads=num_heads)
            ks.append(ck)  # noqa: PTA104 (static unroll, host loop bound)
            vs.append(cv)  # noqa: PTA104 (static unroll, host loop bound)
    return (h, cache_k, cache_v) if in_place else (h, ks, vs)


def _head(h, fnw, fnb, wte):
    """Final LayerNorm and the tied head: ``h`` [b, s, d] -> logits [b, s, V]."""
    with jax.named_scope("norm"):
        h = _layer_norm(h, fnw, fnb)
    with jax.named_scope("head_loss"):
        return jnp.einsum("bsd,vd->bsv", h, wte)


def _cache_forward(stacked, wte, wpe, fnw, fnb, ids, cache_k, cache_v, start_pos, *, num_heads, mesh=None):
    """Trunk forward over a fixed cache; returns (logits, cache_k, cache_v).

    cache_k/v: [L, b, H, S, dh] hold keys/values for positions < start_pos and
    are updated at [start_pos, start_pos + s). ids [b, s] (s = prompt length at
    prefill, 1 at decode). With ``mesh``, caches/activations carry mp (heads /
    vocab) sharding constraints so decode runs tensor-parallel (reference: the
    mp-sharded fused_multi_transformer decode path).
    """
    def mpc(x, *spec):
        # int8 packs skip the mp constraint (the serving engine never meshes)
        if mesh is None or mesh.shape.get("mp", 1) <= 1 or isinstance(x, dict):
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))

    def mix(q, k, v, ck, cv, layer):
        return _batch_write_attend(q, k, v, ck, cv, start_pos)

    with jax.named_scope("embed"):
        pos = start_pos + jnp.arange(ids.shape[1], dtype=jnp.int32)
    h = _embed(wte, wpe, ids, pos)
    h, ks, vs = _serve_layers(stacked, h, cache_k, cache_v, mix, num_heads=num_heads)
    ks, vs = [mpc(c, None, "mp") for c in ks], [mpc(c, None, "mp") for c in vs]
    logits = mpc(_head(h, fnw, fnb, wte), None, None, "mp")
    return logits, _kv_stack(ks), _kv_stack(vs)


def _slot_window_forward(stacked, wte, wpe, fnw, fnb, toks, cache_k, cache_v, pos, *, num_heads, active=None):
    """W-token trunk forward with per-slot start positions: row j of
    ``toks`` [b, W] runs at absolute position ``pos[b] + j`` against the
    engine's cache ``[L, b, H, S, dh]`` (or int8 packs): the decode step at
    W=1, the speculative verification of a drafted window in ONE forward at
    W=K+1. ``active`` [b] bool gates the cache write per slot. Returns (logits
    [b, W, V], cache_k, cache_v); per-row math is the W=1 step's, so greedy
    accepted tokens stay bitwise equal to sequential decode."""
    W = toks.shape[1]
    rows = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
    # a speculative window near the sequence limit can index past the
    # positional table; clamp (those rows are never emitted — an unclamped
    # jnp.take fills NaN, which the window's own KV writes would spread to
    # later rows). No-op at W=1, where pos < max_seq_len always holds.
    rows = jnp.minimum(rows, jnp.int32(wpe.shape[0] - 1))
    impl = _decode_attention_impl(cache_k, W)

    def mix(q, k, v, ck, cv, layer):
        att, ck, cv = impl.fn(q, k, v, ck, cv, pos, active, layer)
        return _merge_heads(att), ck, cv

    h = _embed(wte, wpe, toks, rows)
    h, cache_k, cache_v = _serve_layers(stacked, h, cache_k, cache_v, mix, num_heads=num_heads,
                                        in_place=not impl.fallback)
    if impl.fallback:
        cache_k, cache_v = _kv_stack(cache_k), _kv_stack(cache_v)
    return _head(h, fnw, fnb, wte), cache_k, cache_v


def _slot_decode_forward(stacked, wte, wpe, fnw, fnb, tok, cache_k, cache_v, pos, *, num_heads, active=None):
    """The serving engine's decode step, the W=1 case of
    :func:`_slot_window_forward`: ``tok`` [b] int32 (last token per slot),
    ``pos`` [b] int32. Returns (logits [b, V], cache_k, cache_v): one compiled
    program serves every step of every request whatever each slot's depth."""
    logits, cache_k, cache_v = _slot_window_forward(
        stacked, wte, wpe, fnw, fnb, tok[:, None], cache_k, cache_v, pos,
        num_heads=num_heads, active=active)
    return logits[:, 0], cache_k, cache_v


def _chunk_prefill_forward(stacked, wte, wpe, fnw, fnb, ids, cache_k, cache_v,
                           slot, start, *, num_heads, last_row=None):
    """Trunk forward over one prompt chunk of one slot, directly against the
    engine's [L, B, H, S, dh] cache. ``ids`` [1, C] (C fixed: a long prompt is
    a sequence of these dispatches, interleaved with decode, and one program
    serves every chunk at every depth); ``start`` is the chunk's first absolute
    position. With ``last_row`` a traced row index, also returns the logits of
    that row (the sampling row of the prompt's last chunk); intermediate chunks
    skip the head. Returns (logits|None, cache_k, cache_v)."""
    def mix(q, k, v, ck, cv, layer):
        return _chunk_write_attend(q, k, v, ck, cv, slot, start)

    with jax.named_scope("embed"):
        pos = start + jnp.arange(ids.shape[1], dtype=jnp.int32)
    h = _embed(wte, wpe, ids, pos)
    h, ks, vs = _serve_layers(stacked, h, cache_k, cache_v, mix, num_heads=num_heads)
    cache_k, cache_v = _kv_stack(ks), _kv_stack(vs)
    if last_row is None:
        return None, cache_k, cache_v
    with jax.named_scope("norm"):
        hl = jax.lax.dynamic_slice(h, (0, last_row, 0), (1, 1, h.shape[2]))
    logits = _head(hl, fnw, fnb, wte)
    with jax.named_scope("head_loss"):
        return logits[:, 0], cache_k, cache_v  # [1, V]


# the token samplers are the serving engine's as much as ``generate``'s: they
# live with the decoder interface
from .decoder import BufferSpec, Decoder  # noqa: E402
from .decoder import filtered_logits as _filtered_logits  # noqa: E402,F401
from .decoder import select_token as _select_token  # noqa: E402
from .decoder import select_token_rows as _select_token_rows  # noqa: E402,F401


@functools.partial(jax.jit, static_argnames=("num_heads", "num_layers", "head_dim", "max_new", "do_sample", "temperature", "top_k", "top_p", "eos", "mesh"))
def _generate_jit(params, ids, key, *, num_heads, num_layers, head_dim, max_new, do_sample, temperature, top_k, top_p, eos, mesh=None):
    """Prefill + lax.scan single-token decode loop, one XLA computation."""
    stacked_tree, wte, wpe, fnw, fnb = params
    b, s0 = ids.shape
    S = s0 + max_new
    dt = wte.dtype
    cache_k = jnp.zeros((num_layers, b, num_heads, S, head_dim), dt)
    cache_v = jnp.zeros((num_layers, b, num_heads, S, head_dim), dt)
    if mesh is not None and mesh.shape.get("mp", 1) > 1:
        csh = NamedSharding(mesh, P(None, None, "mp"))
        cache_k = jax.lax.with_sharding_constraint(cache_k, csh)
        cache_v = jax.lax.with_sharding_constraint(cache_v, csh)
    logits, cache_k, cache_v = _cache_forward(
        stacked_tree, wte, wpe, fnw, fnb, ids, cache_k, cache_v, jnp.int32(0), num_heads=num_heads, mesh=mesh)
    first = _select_token(logits[:, -1].astype(jnp.float32), key, do_sample, temperature, top_k, top_p)
    done0 = jnp.zeros((b,), bool) if eos is None else (first == eos)

    def step(carry, i):
        tok, ck, cv, done, key = carry
        key, sub = jax.random.split(key)
        lg, ck, cv = _cache_forward(
            stacked_tree, wte, wpe, fnw, fnb, tok[:, None], ck, cv, s0 + i, num_heads=num_heads, mesh=mesh)
        nxt = _select_token(lg[:, -1].astype(jnp.float32), sub, do_sample, temperature, top_k, top_p)
        if eos is not None:
            nxt = jnp.where(done, jnp.int32(eos), nxt)
            done = done | (nxt == eos)
        return (nxt, ck, cv, done, key), nxt

    (_, _, _, _, _), rest = jax.lax.scan(step, (first, cache_k, cache_v, done0, key), jnp.arange(max_new - 1, dtype=jnp.int32))
    return jnp.concatenate([ids, first[:, None], rest.T.astype(jnp.int32)], axis=1)


def _dequant(entry, dt):
    """A params-pack entry is either a plain array or an int8 payload
    ``{"q", "s"}``; dequantize the latter to ``dt`` (XLA folds the multiply
    into the consuming matmul — the QuantizedLinear idiom on raw stacked
    weights)."""
    if isinstance(entry, dict):
        return (entry["q"].astype(jnp.float32) * entry["s"]).astype(dt)
    return entry


class GPTDecoder(Decoder):
    """GPT through the serving engine's decoder interface
    (:mod:`paddle_tpu.models.decoder`): the stacked trunk's parameter pack, a
    key and a value cache ``[L, B, H, S, dh]`` over query heads (plain arrays
    or int8 packs; rows need no reset at admission), and the cache forwards
    above: bucketed prefill (:func:`_cache_forward`), chunked prefill
    (:func:`_chunk_prefill_forward`), decode and the speculative window
    (:func:`_slot_window_forward`)."""

    has_window = True

    def __init__(self, model):
        if not isinstance(model.gpt.layers, GPTBlockStack):
            raise NotImplementedError("DecodeEngine requires the stacked trunk (GPTConfig(stacked=True))")
        cfg = model.gpt.cfg
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_seq_len
        (stack, self.idx), self._wte, self._wpe, self._fnw, self._fnb = model._decode_params()
        self._stack = stack
        self._order = model.gpt.layers._order
        self.stack_dtypes = tuple(w.dtype for w in stack)  # dequant targets
        self.dtype = self._wte.dtype

    # ------------------------------------------------------------ parameters
    def params(self, int8: bool = False):
        stack = self._stack
        if int8:
            # per-layer x per-output-channel abs_max scales on the
            # [L, in, out]-stacked trunk weight (channel_wise_abs_max over the
            # stack) — int8 constants land in the compiled programs, dequant
            # folds into the matmul
            import numpy as np

            from .. import quantization as Q

            def pack(i):
                if self._order[i] not in {"qkv_w", "out_w", "ffn1_w", "ffn2_w"}:
                    return stack[i]
                q, s = Q.quant_abs_max(np.asarray(stack[i]), channel_axis=(0, 2))
                return {"q": jnp.asarray(q), "s": jnp.asarray(s)}

            return {"stack": tuple(pack(i) for i in range(len(stack))), "wte": self._wte, "wpe": self._wpe,
                    "fnw": self._fnw, "fnb": self._fnb}
        return {"stack": stack, "wte": self._wte, "wpe": self._wpe, "fnw": self._fnw, "fnb": self._fnb}

    def fingerprint(self) -> tuple:
        cfg = self.cfg
        return (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
                cfg.ffn_hidden_size, cfg.max_seq_len)

    def _unpack(self, p):
        return ((tuple(_dequant(e, dt) for e, dt in zip(p["stack"], self.stack_dtypes)), self.idx),
                p["wte"], p["wpe"], p["fnw"], p["fnb"])

    # --------------------------------------------------------------- buffers
    def cache_shape(self, slots: int, rows: int) -> tuple:
        cfg = self.cfg
        return (cfg.num_layers, int(slots), cfg.num_heads, int(rows), cfg.hidden_size // cfg.num_heads)

    def buffer_specs(self, slots: int, rows: int, kv_dtype=None):
        shape = self.cache_shape(slots, rows)
        dt = "int8" if kv_dtype == "int8" else str(self.dtype)
        return (BufferSpec("k", shape, dt, 1, False), BufferSpec("v", shape, dt, 1, False))

    def alloc(self, slots: int, rows: int, kv_dtype=None):
        shape = self.cache_shape(slots, rows)
        return (_kv_zeros(shape, self.dtype, kv_dtype), _kv_zeros(shape, self.dtype, kv_dtype))

    # -------------------------------------------------------------- forwards
    def prefill(self, p, cache, ids, length, slot):
        stacked, wte, wpe, fnw, fnb = self._unpack(p)
        ck, cv = cache
        L, _, H, _, dh = self.cache_shape(1, 1)
        kvdt = "int8" if isinstance(ck, dict) else None
        P = ids.shape[1]
        # the bucketed scratch carries the SAME representation as the big
        # cache (int8 pack under kv_dtype), so bucketed prefill attends
        # exactly the rows a chunked prefill would — the bitwise basis
        # of the bucketed-vs-chunked parity pin survives quantization
        sk = _kv_zeros((L, 1, H, P, dh), wte.dtype, kvdt)
        sv = _kv_zeros((L, 1, H, P, dh), wte.dtype, kvdt)
        logits, sk, sv = _cache_forward(stacked, wte, wpe, fnw, fnb, ids, sk, sv,
                                        jnp.int32(0), num_heads=self.cfg.num_heads)
        ck = _kvc_copy(ck, sk, (0, slot, 0, 0, 0))
        cv = _kvc_copy(cv, sv, (0, slot, 0, 0, 0))
        last = jax.lax.dynamic_slice(logits, (0, length - 1, 0), (1, 1, logits.shape[2]))[:, 0]
        return last, (ck, cv)

    def chunk(self, p, cache, ids, slot, start, last_row=None):
        stacked, wte, wpe, fnw, fnb = self._unpack(p)
        ck, cv = cache
        logits, ck, cv = _chunk_prefill_forward(stacked, wte, wpe, fnw, fnb, ids, ck, cv, slot, start,
                                                num_heads=self.cfg.num_heads, last_row=last_row)
        return logits, (ck, cv)

    def decode(self, p, cache, tok, pos, active):
        stacked, wte, wpe, fnw, fnb = self._unpack(p)
        logits, ck, cv = _slot_decode_forward(stacked, wte, wpe, fnw, fnb, tok, cache[0], cache[1],
                                              pos, num_heads=self.cfg.num_heads, active=active)
        return logits, (ck, cv), None

    def window(self, p, cache, toks, pos, active):
        stacked, wte, wpe, fnw, fnb = self._unpack(p)
        logits, ck, cv = _slot_window_forward(stacked, wte, wpe, fnw, fnb, toks, cache[0], cache[1], pos,
                                              num_heads=self.cfg.num_heads, active=active)
        return logits, (ck, cv)

    # -------------------------------------------- prefix-cache segments (KV)
    def segment_extract(self, cache, slot, start, size: int):
        L, _, H, _, dh = self.cache_shape(1, 1)
        shape = (L, 1, H, int(size), dh)
        return tuple(_kvc_slice(c, (0, slot, 0, start, 0), shape) for c in cache)

    def segment_insert(self, cache, segment, slot, start):
        # under kv_dtype the segment is the stored int8 pack and both planes
        # copy verbatim: a cache hit never round-trips through f32 in HBM
        return tuple(_kvc_copy(c, seg, (0, slot, 0, start, 0)) for c, seg in zip(cache, segment))

    def segment_bytes(self, size: int, kv_dtype=None) -> int:
        L, _, H, _, dh = self.cache_shape(1, 1)
        if kv_dtype == "int8":
            return 2 * L * H * int(size) * (dh + 4)      # int8 payload + one f32 scale per (layer, head, row)
        return 2 * L * H * int(size) * dh * jnp.dtype(self.dtype).itemsize


class GPTEmbeddings(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_seq_len, cfg.hidden_size, weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            from ..tensor.creation import arange

            position_ids = arange(0, input_ids.shape[1], dtype="int32")
        with jax.named_scope("embed"):
            return self.dropout(self.word_embeddings(input_ids) + self.position_embeddings(position_ids))


class GPTModel(nn.Layer):
    """Embedding + N decoder blocks + final LN → hidden states."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        if cfg.stacked:
            self.layers = GPTBlockStack(cfg)  # noqa: PTA104 (host-side, never traced)
        else:
            self.layers = nn.LayerList([  # noqa: PTA104 (host-side, never traced)
                GPTBlock(cfg, use_moe=bool(cfg.moe_num_experts)
                         and (i + 1) % cfg.moe_every == 0)
                for i in range(cfg.num_layers)])
        self.final_norm = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids=None):
        h = self.embeddings(input_ids, position_ids)
        if isinstance(self.layers, GPTBlockStack):
            h = self.layers(h)
        else:
            for blk in self.layers:
                h = self._block_maybe_remat(blk, h)
        with jax.named_scope("norm"):
            return self.final_norm(h)

    def _block_maybe_remat(self, blk, h):
        # honor cfg.recompute on the per-layer trunk too (the stacked path
        # remats inside GPTBlockStack); granularity maps as in _stack_forward
        if not self.cfg.recompute:
            return blk(h)
        from ..distributed.recompute import recompute as _rc

        policy = ("dots_saveable" if self.cfg.recompute_granularity == "selective"
                  else "nothing_saveable")
        return _rc(blk, h, policy=policy)

    @property
    def moe_aux_loss(self):
        """Sum of the MoE gates' load-balancing losses from the last
        forward (GPT-MoE blocks only); add `model.moe_aux_loss * coef` to
        the training loss (GShard aux objective)."""
        total = None
        if not isinstance(self.layers, GPTBlockStack):
            for blk in self.layers:
                if getattr(blk, "moe", None) is not None:
                    total = blk.moe.aux_loss if total is None else total + blk.moe.aux_loss
        return total

    # per-layer GPTBlock param path <-> stacked GPTBlockStack param name
    _PER_LAYER_TO_STACKED = {
        "norm1.weight": "norm1_w", "norm1.bias": "norm1_b",
        "attn.qkv_proj.weight": "qkv_w", "attn.qkv_proj.bias": "qkv_b",
        "attn.out_proj.weight": "out_w", "attn.out_proj.bias": "out_b",
        "norm2.weight": "norm2_w", "norm2.bias": "norm2_b",
        "ffn1.weight": "ffn1_w", "ffn1.bias": "ffn1_b",
        "ffn2.weight": "ffn2_w", "ffn2.bias": "ffn2_b",
    }

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Accepts both trunk layouts: ``layers.N.attn.qkv_proj.weight``
        (per-layer GPTBlock checkpoints, incl. ones converted from the
        reference's auto_parallel_gpt_model naming) and ``layers.qkv_w``
        ([L, ...]-stacked). Mismatched layouts are converted by
        stacking/unstacking along the layer axis."""
        import re

        import numpy as np

        from ..framework.core import Tensor as _T

        def val(v):
            return np.asarray(v._value) if isinstance(v, _T) else np.asarray(v)

        L = self.cfg.num_layers
        if isinstance(self.layers, GPTBlockStack):
            groups, rest = {}, {}
            for k, v in state_dict.items():  # noqa: PTA102 (host-side, never traced)
                m = re.match(r"layers\.(\d+)\.(.+)$", k)
                if m and m.group(2) in self._PER_LAYER_TO_STACKED:
                    groups.setdefault(self._PER_LAYER_TO_STACKED[m.group(2)], {})[int(m.group(1))] = v  # noqa: PTA104 (host-side, never traced)
                else:
                    rest[k] = v  # noqa: PTA104 (host-side, never traced)
            if groups:
                state_dict = rest
                inv = {v: k for k, v in self._PER_LAYER_TO_STACKED.items()}
                for stacked_name, per in groups.items():  # noqa: PTA102 (host-side, never traced)
                    if len(per) == L and sorted(per) == list(range(L)):
                        state_dict[f"layers.{stacked_name}"] = np.stack([val(per[i]) for i in range(L)])  # noqa: PTA104 (host-side, never traced)
                    else:
                        # incomplete group: restore the original keys so the
                        # base class reports them as unexpected (no silent drop)
                        for i, v in per.items():  # noqa: PTA102 (host-side, never traced)
                            state_dict[f"layers.{i}.{inv[stacked_name]}"] = v  # noqa: PTA104 (host-side, never traced)
        else:
            inv = {v: k for k, v in self._PER_LAYER_TO_STACKED.items()}
            converted = {}
            for k, v in state_dict.items():  # noqa: PTA102 (host-side, never traced)
                m = re.match(r"layers\.([a-z0-9_]+)$", k)
                if m and m.group(1) in inv:
                    arr = val(v)
                    if arr.shape[0] != L:
                        converted[k] = v  # wrong layer count: surface as unexpected  # noqa: PTA104 (host-side, never traced)
                        continue
                    for i in range(L):
                        converted[f"layers.{i}.{inv[m.group(1)]}"] = arr[i]  # noqa: PTA104 (host-side, never traced)
                else:
                    converted[k] = v  # noqa: PTA104 (host-side, never traced)
            state_dict = converted
        return super().set_state_dict(state_dict, use_structured_name)


class GPTForPretraining(nn.Layer):
    """LM head tied to the (vocab-sharded) word embedding — logits over 'mp'."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, position_ids=None):
        h = self.gpt(input_ids, position_ids)
        from ..tensor.linalg import matmul

        # tied head: h @ wte^T; vocab axis stays mp-sharded for the
        # vocab-parallel loss (c_softmax_with_cross_entropy parity)
        with jax.named_scope("head_loss"):
            logits = matmul(h, self.gpt.embeddings.word_embeddings.weight, transpose_y=True)
        if self.gpt.cfg.moe_num_experts:
            # GPT-MoE: the GShard balancing loss rides the outputs so the
            # criterion (and any compiled step) sees it — no side channel
            return logits, self.gpt.moe_aux_loss
        return logits

    def generate(self, input_ids, max_new_tokens=32, do_sample=False, temperature=1.0, top_k=0, top_p=1.0, seed=0, eos_token_id=None):
        """Autoregressive decoding over a fixed-size KV cache, compiled as
        one XLA computation (prefill + lax.scan token loop).

        Parity: the reference decodes through gen_cache/Cache plumbing
        (python/paddle/nn/layer/transformer.py:284) or the fused decoder
        (fused_multi_transformer_op.cu); here the cache has a static
        [L, b, H, s0+max_new, dh] shape so the whole loop jits once.
        Greedy by default; ``do_sample`` enables temperature / top-k /
        top-p sampling. Returns [b, s0 + max_new_tokens] token ids.
        """
        from ..framework.core import _wrap_value, unwrap
        from ..tensor._helpers import ensure_tensor

        cfg = self.gpt.cfg
        if not isinstance(self.gpt.layers, GPTBlockStack):
            raise NotImplementedError("generate() requires the stacked trunk (GPTConfig(stacked=True))")
        ids = unwrap(ensure_tensor(input_ids)).astype(jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if ids.shape[1] + max_new_tokens > cfg.max_seq_len:
            raise ValueError(f"prompt {ids.shape[1]} + max_new_tokens {max_new_tokens} exceeds max_seq_len {cfg.max_seq_len}")
        params = self._decode_params()
        # tensor-parallel decode: when the fleet mesh has mp>1 (and no pp),
        # place the trunk stack per its dist_spec annotations and thread the
        # mesh so caches/logits stay mp-sharded through the token loop
        from ..distributed.fleet import fleet as _fleet

        mesh = None
        if _fleet._hcg is not None:
            fm = _fleet.mesh
            if fm is not None and fm.shape.get("mp", 1) > 1 and fm.shape.get("pp", 1) == 1:
                mesh = fm
                stack = self.gpt.layers
                specs = [getattr(getattr(stack, n), "dist_spec", None) for n in stack._order]
                placed = tuple(
                    jax.device_put(arr, NamedSharding(mesh, sp if sp is not None else P()))
                    for arr, sp in zip(params[0][0], specs))
                wte_spec = getattr(self.gpt.embeddings.word_embeddings.weight, "dist_spec", None)
                params = (
                    (placed, params[0][1]),
                    jax.device_put(params[1], NamedSharding(mesh, wte_spec if wte_spec is not None else P())),
                    jax.device_put(params[2], NamedSharding(mesh, P())),
                    jax.device_put(params[3], NamedSharding(mesh, P())),
                    jax.device_put(params[4], NamedSharding(mesh, P())),
                )
        out = _generate_jit(
            params, ids, jax.random.key(seed),
            num_heads=cfg.num_heads, num_layers=cfg.num_layers,
            head_dim=cfg.hidden_size // cfg.num_heads,
            max_new=int(max_new_tokens), do_sample=bool(do_sample),
            temperature=float(temperature), top_k=int(top_k), top_p=float(top_p),
            eos=None if eos_token_id is None else int(eos_token_id), mesh=mesh)
        return _wrap_value(out)

    def decoder(self) -> "GPTDecoder":
        """What the serving engine runs this model through."""
        return GPTDecoder(self)

    def _decode_params(self):
        """The decode-loop parameter pack (single definition shared by
        generate() and export_decoder — layout matches GPTBlockStack._order)."""
        from ..framework.core import unwrap

        cfg = self.gpt.cfg
        stack = self.gpt.layers
        stacked = (tuple(unwrap(getattr(stack, n)) for n in stack._order),
                   jnp.arange(cfg.num_layers, dtype=jnp.int32))
        return (
            stacked,
            unwrap(self.gpt.embeddings.word_embeddings.weight),
            unwrap(self.gpt.embeddings.position_embeddings.weight),
            unwrap(self.gpt.final_norm.weight),
            unwrap(self.gpt.final_norm.bias),
        )

    def export_decoder(self, path, prompt_len, max_new_tokens=32, do_sample=False,
                       temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None):
        """Export the whole decode loop (prefill + KV-cache token scan +
        sampling) as a deployable StableHLO artifact servable by
        ``paddle.inference.create_predictor``.

        Parity: the reference deploys decoding through the fused decoder op
        inside an inference program (fused_multi_transformer_op.cu consumed
        by AnalysisPredictor); here the artifact IS the compiled loop. The
        batch dimension is symbolic; ``prompt_len`` is fixed at export (the
        KV cache is static-shape). Feeds: ids [b, prompt_len] int32, seed []
        int32. Fetch: tokens [b, prompt_len + max_new_tokens] int32.
        """
        import pickle
        from pathlib import Path

        cfg = self.gpt.cfg
        if not isinstance(self.gpt.layers, GPTBlockStack):
            raise NotImplementedError("export_decoder requires the stacked trunk")
        if prompt_len + max_new_tokens > cfg.max_seq_len:
            raise ValueError("prompt_len + max_new_tokens exceeds max_seq_len")
        Path(str(path)).parent.mkdir(parents=True, exist_ok=True)
        params = self._decode_params()

        def decode(ids, seed):
            return _generate_jit(
                params, ids, jax.random.key(seed),
                num_heads=cfg.num_heads, num_layers=cfg.num_layers,
                head_dim=cfg.hidden_size // cfg.num_heads,
                max_new=int(max_new_tokens), do_sample=bool(do_sample),
                temperature=float(temperature), top_k=int(top_k),
                top_p=float(top_p),
                eos=None if eos_token_id is None else int(eos_token_id))

        scope = jax.export.SymbolicScope()
        b = jax.export.symbolic_shape("b", scope=scope)[0]
        exported = jax.export.export(jax.jit(decode))(
            jax.ShapeDtypeStruct((b, int(prompt_len)), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
        Path(str(path) + ".pdmodel").write_bytes(exported.serialize())
        meta = {
            "feed_names": ["ids", "seed"],
            "fetch_names": ["tokens"],
            "feed_shapes": [[-1, int(prompt_len)], []],
            "feed_dtypes": ["int32", "int32"],
            "decoder": {"prompt_len": int(prompt_len), "max_new_tokens": int(max_new_tokens)},
            "format": "stablehlo",
            "producer": f"paddle_tpu/jax {jax.__version__}",
        }
        Path(str(path) + ".pdiparams").write_bytes(pickle.dumps(meta))
        return str(path)


class GPTPretrainingCriterion(nn.Layer):
    """Next-token cross entropy with optional loss mask, mean over tokens.
    For GPT-MoE outputs ``(logits, aux)`` the GShard balancing loss is added
    with ``moe_aux_coef`` (reference MoE training objective)."""

    def __init__(self, moe_aux_coef=0.01):
        super().__init__()
        self.parallel_ce = ParallelCrossEntropy()
        self.moe_aux_coef = moe_aux_coef

    def forward(self, logits, labels, loss_mask=None):
        with jax.named_scope("head_loss"):
            return self._loss(logits, labels, loss_mask)

    def _loss(self, logits, labels, loss_mask):
        from ..tensor.math import mean, multiply, sum as t_sum
        from ..tensor.manipulation import reshape

        aux = None
        if isinstance(logits, (tuple, list)):
            logits, aux = logits
        per_tok = self.parallel_ce(logits, labels)
        if aux is not None:
            if loss_mask is not None:
                m = reshape(loss_mask, per_tok.shape)
                return t_sum(multiply(per_tok, m)) / t_sum(m) + aux * self.moe_aux_coef
            return mean(per_tok) + aux * self.moe_aux_coef
        if loss_mask is not None:
            m = reshape(loss_mask, per_tok.shape)
            return t_sum(multiply(per_tok, m)) / t_sum(m)
        return mean(per_tok)

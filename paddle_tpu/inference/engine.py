"""Static KV-cache decode engine: the serving hot path.

Replaces the growing-concat ``MultiHeadAttention.Cache`` decode (a new
shape — and under jit a new compiled program — every token) with a
preallocated device-resident cache updated in place at traced position
indices. A small fixed family of compiled programs serves an entire
request stream:

- **prefill** — bucketed (one compile per prompt-length bucket, the PR-6
  path) or **chunked** (``prefill_chunk=C``): the prompt runs as a sequence
  of fixed-``C``-token dispatches directly against the big cache, so the
  whole per-bucket compile family collapses into ONE chunk program plus one
  final-chunk program (sampling fused), and a long admission can interleave
  with decode instead of stalling it;
- **decode step** — advances every occupied slot one token with per-slot
  position indices; ``fuse=D`` runs D decode iterations inside ONE donated
  ``lax.scan`` dispatch (the ``TrainStep.run_steps`` idiom via
  ``jit.scan_steps``), with the eos/max-token stop flags carried in the
  scan state so finished slots self-deactivate without a host round-trip —
  one dispatch and one host sync per D tokens. At depth 1 a caller that
  asks for it (``decode_step(ahead=True)``, the scheduler) **runs one step
  ahead**: each call launches the next step before it pulls the last one's
  tokens, so the device always has a program queued while the host works;
- **prefix reuse** — ``prefix_cache_mb=M`` keeps an LRU cache of
  chunk-aligned prompt-prefix KV segments (:mod:`.prefix_cache`); a request
  whose prefix matches copies the cached chunks into its slot with one
  compiled ``dynamic_update_slice`` program per chunk — no prefill compute
  or compile for the shared portion.

What depends on the architecture the engine takes from the model's *decoder*
(``model.decoder()``, the interface of :mod:`paddle_tpu.models.decoder`): the
parameter pack, the per-slot buffers — rows of keys and values, which
admission leaves as they are, and for a model with linear-attention or
state-space layers a recurrent state (a delta-rule matrix, a Mamba-2
``ssm_state``, and the ``conv_tail`` either keeps beside it), which the slot's
first prefill program zeroes — and the prefill, chunk, decode and window
forwards over them. GPT (``models/gpt.py:GPTDecoder``), Solar Open 2
(``models/solar_open2.py:SolarOpen2Decoder``), GigaChat 3.5
(``models/gigachat3_5.py:GigaChat35Decoder``, whose cached rows are latents
``[B, S, rank + rope]`` with no head axis) and Granite 4.0-H
(``models/granite_moe_hybrid.py:GraniteMoeHybridDecoder``) and EvaByte
(``models/evabyte.py:EvaByteDecoder``, whose slots hold a window ring of exact
rows and a table of chunk summaries) are its clients; for a model with
recurrent state, or rows that cannot be rebuilt, the engine refuses a prefix
cache, a draft and an int8 cache.

The slot buffers (and the slot state) are donated, so what the engine
*holds* stays flat for its life. Whether a program also updates them in
place is the program's: the decode step on the TPU does (the
``decode_attention`` registry entry's aliased kernel writes a slot's new rows
into the stacked cache where it is stored, and the compiled step holds
temporaries of a fraction of the cache); the lax programs — prefill, chunked
prefill, and decode on other devices, for an int8 cache or under a mesh — cut
each layer out of the stack, re-lay it out and stack it again, with
temporaries larger than the cache (PERF.md §5, §6 "Memory").
Compiles run through the observability AOT ``lower().compile()``
path, so ``explain()`` answers cost/memory questions, the
``infer.compiles`` counter pins the program-family size in tests, and — with
``FLAGS_compile_cache_dir`` set — every executable is serialized to disk
(:mod:`.aot_cache`) so a RESTARTED engine skips the compile family
entirely.

Parity: the reference serves GPT decode through
``fused_multi_transformer_op.cu`` driven by AnalysisPredictor; here the
fused decoder is the compiled step program and the "predictor" is the
:class:`~paddle_tpu.inference.scheduler.ContinuousBatchingScheduler` on top.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import sanitizer as _sanitizer

__all__ = ["DecodeEngine", "default_buckets"]


def default_buckets(max_seq: int, start: int = 16) -> Tuple[int, ...]:
    """Power-of-two prompt-padding buckets up to ``max_seq``: prompts pad to
    the smallest bucket that fits, so prefill compiles once per bucket
    instead of once per prompt length."""
    out: List[int] = []
    b = start
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(sorted(set(out)))


def _placed(method):
    """Run an engine method with the engine's device as JAX's default, so
    the host-side scalars and index arrays it builds land beside the cache
    they are dispatched with (no-op for an engine on the default device)."""
    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with self._device_scope():
            return method(self, *args, **kwargs)
    return wrapped


class _PrefillJob:
    """Host-side progress of one in-flight prompt admission: which slot it
    owns, how far the cache is written (``next_pos``), how many tokens the
    prefix cache supplied, and — once the final chunk ran — the sampled
    first token (``pending``: the final program ran and its first token is
    still on the device, for the next :meth:`DecodeEngine.prefill_step`) and
    the engine's count of prefill programs with that one (``programs``: what
    is dispatched after it runs after the first token exists)."""

    __slots__ = ("slot", "prompt", "n", "eos", "limit", "seed",
                 "next_pos", "reused_tokens", "done", "first", "more", "pending", "programs")

    def __init__(self, slot, prompt, n, eos, limit, seed):
        self.slot = slot
        self.prompt = prompt
        self.n = n
        self.eos = eos
        self.limit = limit
        self.seed = seed
        self.next_pos = 0          # cache rows [0, next_pos) are written
        self.reused_tokens = 0     # rows supplied by the prefix cache
        self.done = False
        self.first: Optional[int] = None
        self.more: Optional[bool] = None
        self.pending = None        # (first, more) on the device, not yet pulled
        self.programs = 0          # DecodeEngine.prefill_programs as the program that samples the first token was dispatched

    def chunks_left(self, chunk: Optional[int]) -> int:
        """Model dispatches still needed to finish this prefill."""
        if self.done or self.pending is not None:
            return 0
        if chunk is None:
            return 1
        return max(1, -(-(self.n - self.next_pos) // chunk))


class _DecodeInFlight:
    """One launched decode step whose report the host has not pulled yet:
    the program's int32 ``report`` (tokens, which slots emitted them, which
    stay active, the decoder's counters), the slots the host has freed or
    admitted into since the launch (``touched``: the step's word on them is
    another request's), and the engine's running count of prefill programs
    as it stood at the launch (``queued``: what the device had ahead of this
    step)."""

    __slots__ = ("report", "touched", "queued")

    def __init__(self, report, touched, queued):
        self.report = report
        self.touched = touched
        self.queued = queued


class DecodeEngine:
    """Slot-based autoregressive decode over a static KV cache.

    ``model`` is any model with a ``decoder()`` (:mod:`paddle_tpu.models.decoder`):
    :class:`~paddle_tpu.models.gpt.GPTForPretraining` with the stacked trunk,
    :class:`~paddle_tpu.models.solar_open2.SolarOpen2ForCausalLM`.
    ``max_batch_slots`` fixes the decode batch width B: each
    slot holds one in-flight request, and requests are admitted into free
    slots mid-stream (continuous batching) — admission never recompiles.

    ``int8=True`` quantizes the trunk matmul weights (qkv/out/ffn1/ffn2)
    to int8 with per-layer × per-output-channel abs_max scales through
    :mod:`paddle_tpu.quantization`; the compiled programs carry int8
    constants and dequantize into the matmuls.

    Serving-throughput knobs (each defaults to the PR-6 behaviour):

    - ``fuse=D`` — default decode fusion depth: :meth:`decode_step` runs D
      iterations per dispatch (helps whenever per-dispatch host overhead is
      visible, i.e. small models / fast devices; a slot that finishes
      mid-scan idles until the dispatch drains, so very large D wastes
      compute on short completions);
    - ``prefill_chunk=C`` — chunked prefill: prompts prefill in fixed
      C-token dispatches against the big cache (compile family becomes 2
      programs for ALL prompt lengths; long prompts interleave with decode);
    - ``prefix_cache_mb=M`` — prefix KV reuse over chunk-aligned prompt
      prefixes (requires ``prefill_chunk``), LRU-evicted under an M-MiB
      device-byte budget;
    - ``draft=<GPTConfig | dict | model>`` — speculative decoding: a small
      draft model proposes ``spec_k`` tokens per step and ONE wide target
      forward verifies them (accept-longest-prefix + bonus token in-graph),
      so one dispatch emits up to ``spec_k+1`` tokens. Greedy accepted
      tokens are bitwise-identical to the non-speculative path; a
      config/dict draft is built from ``draft_seed`` so every replica holds
      the same weights. Requires ``fuse=1``;
    - ``kv_dtype="int8"`` — the K/V cache stores int8 payloads with
      per-head per-row abs_max f32 scale planes (~``4*dh/(dh+4)``x smaller
      than f32); dequant folds into the attention matmuls and prefix-cache
      segments stay quantized end-to-end. Decode tokens can differ from the
      f32 cache within quantization tolerance (the engine family itself
      stays bitwise-reproducible run to run).

    ``device`` places the engine — its copy of the weights, the KV cache,
    the slot state and every compiled program — on one ``jax.Device``
    (default: JAX's default device). ``ServingFleet`` gives each replica a
    local device of its own, which is what makes four replicas on a
    four-chip host use four chips.

    Sampling config (``do_sample``/``temperature``/``top_k``/``top_p``) is
    compiled into the programs; per-request randomness comes from each
    request's own ``seed`` folded with its absolute position, so a request's
    tokens never depend on which slot it runs in or on its batch neighbours.
    """

    def __init__(self, model, max_batch_slots: int = 4, max_seq_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 int8: bool = False, donate: bool = True, fuse: int = 1,
                 prefill_chunk: Optional[int] = None, prefix_cache_mb: float = 0.0,
                 draft=None, spec_k: int = 4, draft_seed: int = 0,
                 kv_dtype: Optional[str] = None, device=None):
        self._device = device

        dec = self._decoder_of(model)
        self._dec = dec
        self.cfg = getattr(dec, "cfg", None)
        S = int(max_seq_len) if max_seq_len is not None else int(dec.max_positions)
        if S > dec.max_positions:
            raise ValueError(f"max_seq_len {S} exceeds the model's positional table {dec.max_positions}")
        self.max_seq_len = S
        self.max_batch_slots = B = int(max_batch_slots)
        self.buckets = tuple(sorted(int(b) for b in prefill_buckets)) if prefill_buckets else default_buckets(S)
        if any(b > S for b in self.buckets):
            raise ValueError(f"prefill bucket larger than max_seq_len {S}: {self.buckets}")
        self._sample = (bool(do_sample), float(temperature), int(top_k), float(top_p))
        self.int8 = bool(int8)
        self._donate = bool(donate)
        self.fuse = int(fuse)
        if self.fuse < 1:
            raise ValueError(f"fuse depth must be >= 1, got {fuse}")
        self._chunk = int(prefill_chunk) if prefill_chunk else None
        if self._chunk is not None and not (1 <= self._chunk <= S):
            raise ValueError(f"prefill_chunk {prefill_chunk} must be in [1, max_seq_len={S}]")
        self._kv_dtype = None if kv_dtype is None else str(kv_dtype)
        if self._kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        if dec.recurrent:
            # a slot of this model holds state no cached row can rebuild, and
            # snapshots of it are not built: refuse, loudly, what rests on rows
            what = type(model).__name__
            if prefix_cache_mb and float(prefix_cache_mb) > 0:
                raise NotImplementedError(f"prefix_cache_mb: {what} keeps recurrent state beside its KV rows; the "
                                          "prefix cache holds chunk-aligned KV segments and no state snapshots")
            if draft is not None:
                raise NotImplementedError(f"draft=: {what} keeps recurrent state; a rejected speculative tail "
                                          "cannot be rolled back out of it")
            if self._kv_dtype == "int8":
                raise NotImplementedError(f"kv_dtype='int8': {what}'s decoder has no quantized cache")
            if self._chunk is not None and S % self._chunk:
                raise ValueError(f"prefill_chunk {self._chunk} must divide max_seq_len {S} for {what}: a final "
                                 "chunk cannot be shifted back over tokens its state has already taken in")

        # --- draft model for speculative decoding ------------------------
        ddec = None
        if draft is not None:
            from ..models.gpt import GPTConfig

            if int(spec_k) < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if self.fuse != 1:
                raise ValueError("draft= requires fuse=1 (a speculative dispatch "
                                 "already emits up to spec_k+1 tokens)")
            if not dec.has_window:
                raise NotImplementedError(f"draft=: {type(model).__name__}'s decoder has no window forward")
            if isinstance(draft, dict):
                draft = GPTConfig(**draft)
            if isinstance(draft, GPTConfig):
                # build the draft's random init under a pinned RNG stream so
                # every engine (and every fleet replica) with the same
                # draft_seed holds bitwise-identical draft weights — fleet
                # requeue after a replica kill must re-accept the same runs
                from ..framework import random as _fwrng
                from ..models.gpt import GPTForPretraining

                state = _fwrng.get_rng_state()
                _fwrng.seed(int(draft_seed))
                try:
                    draft_model = GPTForPretraining(draft)
                finally:
                    _fwrng.set_rng_state(state)
            else:
                draft_model = draft
            try:
                ddec = self._decoder_of(draft_model)
            except NotImplementedError as e:
                raise NotImplementedError("draft model requires the stacked trunk") from e
            if ddec.vocab_size != dec.vocab_size:
                raise ValueError(f"draft vocab {ddec.vocab_size} != target vocab {dec.vocab_size}")
            if ddec.max_positions < S:
                raise ValueError(f"draft positional table {ddec.max_positions} < max_seq_len {S}")
        self._ddec = ddec
        self.draft_cfg = getattr(ddec, "cfg", None)
        self.spec_k = int(spec_k) if draft is not None else 0
        self.draft_seed = int(draft_seed)

        self._params = dec.params(int8=self.int8)
        self._idx = getattr(dec, "idx", None)
        self._dparams = None if ddec is None else ddec.params(int8=self.int8)
        if device is not None:
            # this engine's own copy of the weights, beside its cache
            self._params = jax.device_put(self._params, device)
            if self._dparams is not None:
                self._dparams = jax.device_put(self._dparams, device)  # noqa: PTA104 (host-side serving state)

        # the cache carries spec_k slack rows past max_seq_len so the
        # (spec_k+1)-wide speculative window write near the sequence limit
        # never clamps back over committed rows; slack rows are never
        # attendable by an emitted token (q_pos < max_seq_len always)
        cache_S = S + self.spec_k
        self._specs = dec.buffer_specs(B, cache_S, self._kv_dtype)
        self._shape = tuple(self._specs[0].shape)
        with self._device_scope():
            self._cache = dec.alloc(B, cache_S, self._kv_dtype)
            # the draft cache is small — keep it in the compute dtype
            self._dcache = None if ddec is None else ddec.alloc(B, cache_S, None)  # noqa: PTA104 (host-side serving state)
            self._pos = jnp.zeros((B,), jnp.int32)
            self._tok = jnp.zeros((B,), jnp.int32)
            self._active = jnp.zeros((B,), bool)
        # host mirrors / per-slot request metadata. eos, limit and seed also
        # live on the device (``_slot_consts``) and are copied there where
        # they change — admission, reset — not with every dispatch
        self._active_np = np.zeros((B,), bool)
        # each slot's position as the host knows it: set where its first token's program is dispatched, advanced by
        # each decode launch for the slots the host takes as active (what ``Decoder.step_notes`` counts from)
        self._pos_np = np.zeros((B,), np.int64)
        self._occupied = np.zeros((B,), bool)
        self._eos = np.full((B,), -1, np.int32)
        self._limit = np.zeros((B,), np.int32)
        self._seed = np.zeros((B,), np.int32)
        with self._device_scope():
            self._put_slot_consts()
        # run-ahead: the decode step launched and not yet pulled
        self._inflight: Optional[_DecodeInFlight] = None
        # What the device ran between two decode steps, counted where it is queued: prefill programs dispatched so
        # far (``infer.prefill``, ``infer.prefill_chunk``, final chunks too; a call that only pulls a deferred first
        # token is none); the count as it stood at the launch of the last step pulled (``pulled_at``: the device runs
        # one stream in launch order, so the difference of two is what ran between the two steps' ends); and when
        # that step's tokens reached the host (the end of its ``infer.decode_sync``, on ``time.perf_counter_ns()``;
        # 0 with FLAGS_monitor off). The scheduler reads them where it stamps the tokens.
        self.prefill_programs = 0
        self.pulled_at = 0
        self.arrived_ns = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self.last_stats = None    # the decoder's counters of the last decode dispatch (``n_stats`` int32)

        self.prefix_cache = None
        if prefix_cache_mb and float(prefix_cache_mb) > 0:
            if self._chunk is None:
                raise ValueError("prefix_cache_mb requires prefill_chunk= (prefix "
                                 "entries are chunk-aligned KV segments)")
            from .prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(self._chunk,
                                            int(float(prefix_cache_mb) * (1 << 20)),
                                            dec.segment_bytes(self._chunk, self._kv_dtype))

        # host scalars baked into the traced programs — part of the disk
        # cache key so a restarted engine only reuses executables compiled
        # for the exact same specialization (kv dtype and the draft config
        # change every traced program, so both fold in)
        dfp = None if ddec is None else tuple(ddec.fingerprint()) + (self.spec_k,)
        self._fingerprint = repr((
            tuple(dec.fingerprint()),
            self._sample, self.int8, self._donate, S, B, self._chunk,
            tuple(str(d) for d in getattr(dec, "stack_dtypes", ())), str(dec.dtype),
            self._kv_dtype, dfp))

        self._build()
        self._fused_jits: Dict[int, Any] = {}
        self._compiled: Dict[tuple, Any] = {}
        self._specializations: List[dict] = []
        from ..observability.metrics import gauge_set
        gauge_set("infer.kv_bytes_per_slot", self.kv_bytes_per_slot())
        gauge_set("infer.state_bytes_per_slot", self.state_bytes_per_slot())
        gauge_set("infer.latent_bytes_per_slot", self.latent_bytes_per_slot())

    # ------------------------------------------------------------ programs
    @property
    def device(self):
        """The device this engine's KV cache lives on."""
        return next(iter(jax.tree_util.tree_leaves(self._cache)[0].devices()))

    def _device_scope(self):
        if self._device is None:
            return contextlib.nullcontext()
        return jax.default_device(self._device)

    @staticmethod
    def _decoder_of(model):
        """The model's decoder (:mod:`paddle_tpu.models.decoder`)."""
        if not hasattr(model, "decoder"):
            raise NotImplementedError(f"{type(model).__name__} has no decoder(): DecodeEngine serves a model "
                                      "through the interface of paddle_tpu.models.decoder")
        return model.decoder()

    # the buffers by their old names: the key and the value cache of a model
    # whose decoder holds just those (what the benchmark's GPT family and the
    # tests read)
    @property
    def _ck(self):
        return self._cache[0]

    @property
    def _cv(self):
        return self._cache[1]

    def _build(self):
        from ..models.decoder import filtered_logits as _filtered_logits
        from ..models.decoder import select_token as _select_token
        from ..models.decoder import select_token_rows as _select_token_rows

        dec, ddec = self._dec, self._ddec
        do_sample, temperature, top_k, top_p = self._sample
        spec_k = self.spec_k
        has_draft = ddec is not None
        n_stats = int(dec.n_stats)

        def admit_state(pos, tok, active, first, length, slot, eos, limit):
            """Shared tail of every first-token program: the in-graph
            eos/limit check and the per-slot state writes."""
            done = (eos >= 0) & (first == eos)
            more = (~done) & (length + 1 < limit)
            dus = jax.lax.dynamic_update_slice
            pos = dus(pos, length[None], (slot,))
            tok = dus(tok, first[None], (slot,))
            active = dus(active, more[None], (slot,))
            return pos, tok, active, more

        def prefill_core(p, cache, pos, tok, active, ids, length, slot, eos, limit, seed):
            last, cache = dec.prefill(p, cache, ids, length, slot)
            key = jax.random.fold_in(jax.random.key(seed), length - 1)
            first = _select_token(last.astype(jnp.float32), key, do_sample, temperature, top_k, top_p)[0]
            pos, tok, active, more = admit_state(pos, tok, active, first, length, slot, eos, limit)
            return cache, pos, tok, active, first, more

        if has_draft:
            # draft prefill rides the SAME dispatch as the target prefill
            # (one donated program, two trunks; XLA dead-code-eliminates the
            # draft logits) so admission cost stays one dispatch per bucket
            def prefill_fn(p, dp, cache, dcache, pos, tok, active, ids, length,
                           slot, eos, limit, seed):
                cache, pos, tok, active, first, more = prefill_core(
                    p, cache, pos, tok, active, ids, length, slot, eos, limit, seed)
                _, dcache = ddec.prefill(dp, dcache, ids, length, slot)
                return cache, dcache, pos, tok, active, first, more
        else:
            prefill_fn = prefill_core

        def chunk_core(p, cache, ids, slot, start):
            _, cache = dec.chunk(p, cache, ids, slot, start)
            return cache

        def draft_chunk(dp, dcache, ids, slot, start):
            _, dcache = ddec.chunk(dp, dcache, ids, slot, start)
            return dcache

        if has_draft:
            def chunk_fn(p, dp, cache, dcache, ids, slot, start):
                cache = chunk_core(p, cache, ids, slot, start)
                dcache = draft_chunk(dp, dcache, ids, slot, start)
                return cache, dcache
        else:
            chunk_fn = chunk_core

        def chunk_final_core(p, cache, pos, tok, active, ids, slot, start, last_row,
                             length, eos, limit, seed):
            logits, cache = dec.chunk(p, cache, ids, slot, start, last_row=last_row)
            key = jax.random.fold_in(jax.random.key(seed), length - 1)
            first = _select_token(logits.astype(jnp.float32), key, do_sample, temperature, top_k, top_p)[0]
            pos, tok, active, more = admit_state(pos, tok, active, first, length, slot, eos, limit)
            return cache, pos, tok, active, first, more

        if has_draft:
            def chunk_final_fn(p, dp, cache, dcache, pos, tok, active, ids, slot,
                               start, last_row, length, eos, limit, seed):
                cache, pos, tok, active, first, more = chunk_final_core(
                    p, cache, pos, tok, active, ids, slot, start, last_row,
                    length, eos, limit, seed)
                dcache = draft_chunk(dp, dcache, ids, slot, start)
                return cache, dcache, pos, tok, active, first, more
        else:
            chunk_final_fn = chunk_final_core

        def insert_fn(cache, segment, slot, start):
            # prefix-cache hit: copy a cached chunk's KV into the slot's
            # lanes — the whole "prefill" of the shared portion is this one
            # dynamic_update_slice program
            return dec.segment_insert(cache, segment, slot, start)

        chunk = self._chunk

        def extract_fn(cache, slot, start):
            return dec.segment_extract(cache, slot, start, chunk if chunk else 1)

        def spec_fn(p, dp, cache, dcache, pos, tok, active, eos_v, limit_v, seed_v):
            """ONE speculative dispatch: spec_k+1 chained draft forwards on
            the draft cache propose a window, ONE (spec_k+1)-wide target
            forward verifies it, and the accept-longest-prefix + bonus-token
            ledger runs in-graph. Rejected-tail KV is left stale past the
            rolled-back position — harmless under write-before-attend (the
            next window overwrites those rows before any emitted row can
            attend them)."""
            K = spec_k
            # --- draft scan: iteration i consumes the token at pos+i and
            # writes its draft KV there; iterations 0..K-1 yield proposals
            # d_1..d_K, iteration K only backfills the last proposal's KV so
            # the all-accepted case leaves no draft-cache hole
            props, dfilt = [], []
            dtok = tok
            for i in range(K + 1):  # noqa: PTA104 (static unroll, host loop bound)
                dpos = pos + jnp.int32(i)
                dlogits, dcache, _ = ddec.decode(dp, dcache, dtok, dpos, active)
                if i < K:
                    if do_sample:
                        fl = _filtered_logits(dlogits.astype(jnp.float32),
                                              temperature, top_k, top_p)
                        dkeys = jax.vmap(lambda s, q: jax.random.fold_in(
                            jax.random.fold_in(jax.random.key(s), q), 3))(seed_v, dpos)
                        nd = jax.vmap(jax.random.categorical)(dkeys, fl).astype(jnp.int32)
                        dfilt.append(fl)  # noqa: PTA104 (host-side serving state)
                    else:
                        nd = jnp.argmax(dlogits.astype(jnp.float32), axis=-1).astype(jnp.int32)
                    nd = jnp.where(active, nd, dtok)  # free slots hold
                    props.append(nd)  # noqa: PTA104 (host-side serving state)
                    dtok = nd
            # --- target verification: one (K+1)-wide window forward over
            # [tok, d_1..d_K] at per-slot positions pos..pos+K
            ids = jnp.stack([tok] + props, axis=1)
            vlogits, cache = dec.window(p, cache, ids, pos, active)
            # --- per-row outcome: row j scores the token at position
            # pos+j+1. Greedy: argmax + equality accept (bitwise = sequential
            # decode, since per-row width-W math equals the s=1 math).
            # Sampled: residual resampling over the SAME filtered
            # distribution _select_token samples from.
            outs, accs = [], []
            for j in range(K + 1):  # noqa: PTA104 (static unroll, host loop bound)
                lg = vlogits[:, j].astype(jnp.float32)
                if not do_sample:
                    sel = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    outs.append(sel)  # noqa: PTA104 (host-side serving state)
                    if j < K:
                        accs.append(sel == props[j])  # noqa: PTA104 (host-side serving state)
                    continue
                flp = _filtered_logits(lg, temperature, top_k, top_p)
                kj = jax.vmap(lambda s, q: jax.random.fold_in(jax.random.key(s), q))(
                    seed_v, pos + jnp.int32(j))
                if j < K:
                    P_ = jax.nn.softmax(flp, axis=-1)
                    Q_ = jax.nn.softmax(dfilt[j], axis=-1)
                    d = props[j]
                    pd = jnp.take_along_axis(P_, d[:, None], axis=-1)[:, 0]
                    qd = jnp.take_along_axis(Q_, d[:, None], axis=-1)[:, 0]
                    u = jax.vmap(lambda k: jax.random.uniform(jax.random.fold_in(k, 1)))(kj)
                    acc = u * qd <= pd
                    res = jnp.maximum(P_ - Q_, 0.0)
                    has = jnp.sum(res, axis=-1, keepdims=True) > 0
                    rlog = jnp.where(res > 0, jnp.log(jnp.where(res > 0, res, 1.0)), -jnp.inf)
                    rlog = jnp.where(has, rlog, flp)  # P==Q residual: fall back to target
                    corr = jax.vmap(lambda k, lg2: jax.random.categorical(
                        jax.random.fold_in(k, 2), lg2))(kj, rlog).astype(jnp.int32)
                    outs.append(jnp.where(acc, d, corr))  # noqa: PTA104 (host-side serving state)
                    accs.append(acc)  # noqa: PTA104 (host-side serving state)
                else:
                    # bonus row: a direct draw from the target distribution
                    # with the position's own key — the all-accepted case
                    # samples exactly what sequential decode would
                    bonus = jax.vmap(jax.random.categorical)(kj, flp).astype(jnp.int32)
                    outs.append(bonus)  # noqa: PTA104 (host-side serving state)
            # --- emission ledger: accept-longest-prefix, eos/limit stops
            # mid-window, rejected tail rolls the slot position back simply
            # by not advancing it
            win = jnp.ones_like(active)
            act_s, pos_s, tok_s = active, pos, tok
            toks_rows, emit_rows = [], []
            for j in range(K + 1):  # noqa: PTA104 (static unroll, host loop bound)
                emit = act_s & win
                row = jnp.where(emit, outs[j], tok_s)
                tok_s = row
                pos_s = pos_s + emit.astype(jnp.int32)
                hit_eos = (eos_v >= 0) & (row == eos_v)
                live = ~hit_eos & (pos_s + 1 < limit_v)
                act_s = jnp.where(emit, act_s & live, act_s)
                toks_rows.append(row)  # noqa: PTA104 (host-side serving state)
                emit_rows.append(emit)  # noqa: PTA104 (host-side serving state)
                if j < K:
                    win = win & accs[j]
            return (cache, dcache, pos_s, tok_s, act_s,
                    jnp.stack(toks_rows), jnp.stack(emit_rows))

        def decode_body(consts, carry, _x):
            # ONE decode iteration — the scan body of the fused program and
            # (at D=1) the whole single-step program, so every fuse depth is
            # bitwise the same math
            p, eos_v, limit_v, seed_v = consts
            cache, pos, tok, active = carry
            logits, cache, stats = dec.decode(p, cache, tok, pos, active)
            keys = jax.vmap(lambda s, q: jax.random.fold_in(jax.random.key(s), q))(seed_v, pos)
            nxt = _select_token_rows(logits.astype(jnp.float32), keys, do_sample,
                                     temperature, top_k, top_p)
            nxt = jnp.where(active, nxt, tok)  # slot-masked: free slots hold
            hit_eos = (eos_v >= 0) & (nxt == eos_v)
            new_pos = pos + active.astype(jnp.int32)
            new_active = active & ~hit_eos & (new_pos + 1 < limit_v)
            # ys: the step's token per slot + which slots really emitted
            # (+ the decoder's counters, where it counts)
            ys = (nxt, active) if not n_stats else (nxt, active, stats)
            return (cache, new_pos, nxt, new_active), ys

        self._decode_body = decode_body

        def decode_fn(p, cache, pos, tok, active, eos_v, limit_v, seed_v):
            carry, ys = decode_body((p, eos_v, limit_v, seed_v), (cache, pos, tok, active), None)
            # what the host needs of the step leaves in one int32 array that no later launch donates (``tok`` and
            # ``active`` are the next step's): the tokens, which slots emitted them, which stay active, and the
            # decoder's counters where it counts — one pull, a step later for a caller that runs ahead
            report = (carry[2], ys[1], carry[3]) + tuple(ys[2:])
            return carry + (jnp.concatenate([r.astype(jnp.int32) for r in report]),)

        if has_draft:
            # state args shift by one (draft params at arg 1) and both caches
            # donate; the draft weights thread through like the target's
            donate = (2, 3, 4, 5, 6) if self._donate else ()
            donate_cache = (2, 3) if self._donate else ()
            self._spec_jit = jax.jit(spec_fn, donate_argnums=donate)  # noqa: PTA104 (host-side serving state)
            self._draft_chunk_jit = jax.jit(  # noqa: PTA104 (host-side serving state)
                draft_chunk, donate_argnums=(1,) if self._donate else ())
        else:
            donate = (1, 2, 3, 4) if self._donate else ()
            donate_cache = (1,) if self._donate else ()
            self._spec_jit = self._draft_chunk_jit = None  # noqa: PTA104 (host-side serving state)
        self._prefill_jit = jax.jit(prefill_fn, donate_argnums=donate)
        self._decode_jit = jax.jit(decode_fn, donate_argnums=(1, 2, 3, 4) if self._donate else ())
        self._chunk_jit = jax.jit(chunk_fn, donate_argnums=donate_cache)
        self._chunk_final_jit = jax.jit(chunk_final_fn, donate_argnums=donate)
        self._insert_jit = jax.jit(insert_fn, donate_argnums=(0,) if self._donate else ())
        self._extract_jit = jax.jit(extract_fn)  # pure read: nothing donated

    def _fused(self, depth: int):
        """The fused-decode program for ``depth`` scan iterations (compiled
        once per distinct depth; carry donated, params threaded as consts)."""
        jitfn = self._fused_jits.get(depth)
        if jitfn is None:
            from ..jit import scan_steps

            jitfn = scan_steps(self._decode_body, length=depth, with_consts=True,
                               donate_argnums=(1,) if self._donate else ())
            self._fused_jits[depth] = jitfn
        return jitfn

    def _dispatch(self, which: str, jitfn, args, label: Optional[str] = None):
        """Run one dispatch, AOT-compiling on a new (kind, shape) signature
        so the XLA Compiled handle is retained for ``explain()`` and the
        compile is counted/logged — the TrainStep._dispatch idiom. With
        ``FLAGS_compile_cache_dir`` set, executables round-trip through the
        on-disk AOT cache: a restarted engine loads instead of compiling."""
        if _sanitizer.enabled():
            # pre-flight: the decode/prefill programs donate the KV cache
            # and slot-state buffers — holding one across a dispatch is the
            # PR-10 aliasing bug; a deleted leaf raises a structured
            # StaleStateError naming its path instead of crashing in XLA
            _sanitizer.check_state("decode_engine", args, label=which)
        sig = (which,) + tuple(
            (tuple(l.shape), str(l.dtype)) for l in jax.tree_util.tree_leaves(args))
        entry = self._compiled.get(sig)
        if entry is None:
            _sanitizer.note_compile("decode_engine", which, sig[1:])
            from ..observability import introspect as _introspect
            from ..observability import runlog as _runlog
            from ..observability import span as _span
            from ..profiler import counter_inc
            from . import aot_cache

            label = label or which
            key = aot_cache.make_key(which, sig[1:], self._fingerprint, self._device)
            entry = aot_cache.load(key, device=self._device)
            if entry is not None:
                self._compiled[sig] = entry
                counter_inc("infer.aot_cache_hits")
                self._specializations.append({"label": label, "kind": which,
                                              "from_disk_cache": True})
                _runlog.emit("compile", component="infer", label=label, cached=True)
            else:
                with _span("infer.compile"):
                    compiled, info = _introspect.aot_compile(jitfn, args)
                entry = compiled if compiled is not None else jitfn
                if compiled is not None:
                    from ..framework.flags import flag as _flag

                    if _flag("FLAGS_shard_check"):
                        # serving pre-flight (PTA2xx) before the executable
                        # is cached: PTA203 flags any collective compiled
                        # into a decode program — the hot loop pays it per
                        # generated token — and PTA204 budget overruns
                        # abort before the request stream starts
                        from ..analysis import spmd as _spmd

                        report = _spmd.shard_check(
                            compiled, component="infer", label=label,
                            kind=which, options=_spmd.ShardCheckOptions(
                                decode=which.startswith("decode")))
                        info["spmd"] = report.summary()
                self._compiled[sig] = entry
                counter_inc("infer.compiles")
                if compiled is not None and aot_cache.store(key, compiled):
                    counter_inc("infer.aot_cache_stores")
                info["label"] = label
                info["kind"] = which
                self._specializations.append(info)
                _runlog.emit("compile", component="infer", label=label,
                             seconds=info.get("compile_seconds"),
                             flops=info.get("flops"),
                             bytes_accessed=info.get("bytes_accessed"),
                             peak_bytes=info.get("peak_bytes"))
            _introspect.note_program(f"infer/{which}", entry)
        try:
            try:
                with _sanitizer.transfer_scope(f"infer.{which}"):
                    return entry(*args)
            except (TypeError, ValueError):
                if entry is jitfn:
                    raise
                self._compiled[sig] = jitfn  # AOT aval drift: jit path forever
                with _sanitizer.transfer_scope(f"infer.{which}"):
                    return jitfn(*args)
        except Exception as exc:
            # unhandled dispatch fault (aval drift already fell back above):
            # leave a flight-recorder dump, then let the fault propagate
            from ..observability import flightrec as _flightrec

            _flightrec.dump("dispatch_exception", exc, component="infer",
                            which=which, label=label or which)
            raise

    # ------------------------------------------------------------ slot API
    def bucket_for(self, prompt_len: int) -> int:
        """The padded prefill length for a prompt: its bucket, or — in
        chunked mode — the chunk-rounded length (capped at max_seq_len)."""
        if self._chunk is not None:
            if prompt_len > self.max_seq_len:
                raise ValueError(f"prompt of {prompt_len} tokens exceeds "
                                 f"max_seq_len {self.max_seq_len}")
            return min(self.max_seq_len, -(-prompt_len // self._chunk) * self._chunk)
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(f"prompt of {prompt_len} tokens exceeds the largest "
                         f"prefill bucket {self.buckets[-1]}")

    def free_slots(self) -> List[int]:
        return [i for i in range(self.max_batch_slots) if not self._occupied[i]]

    # ----------------------------------------------------------- prefill
    @_placed
    def begin_prefill(self, prompt, slot: int, max_new_tokens: int,
                      eos_token_id: Optional[int] = None, seed: int = 0) -> _PrefillJob:
        """Claim ``slot`` for one prompt and apply any prefix-cache hits
        (insert dispatches only — no model compute). Drive the returned job
        with :meth:`prefill_step`; the scheduler interleaves those chunk
        dispatches with decode so long admissions stop stalling the stream.
        """
        from ..observability.metrics import counter_inc, gauge_set

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n < 1:
            raise ValueError("empty prompt")
        if self._occupied[slot]:
            raise ValueError(f"slot {slot} is occupied; free it first")
        if n + int(max_new_tokens) > self.max_seq_len:
            raise ValueError(f"prompt {n} + max_new_tokens {max_new_tokens} "
                             f"exceeds max_seq_len {self.max_seq_len}")
        eos = -1 if eos_token_id is None else int(eos_token_id)
        limit = n + int(max_new_tokens)
        job = _PrefillJob(slot, prompt, n, eos, limit, int(seed))
        self._occupied[slot] = True
        self._eos[slot] = eos
        self._limit[slot] = limit
        self._seed[slot] = int(seed)
        self._put_slot_consts()
        if self.prefix_cache is not None:
            # reuse at most n-1 tokens: the prompt's last token must run
            # through the model (its logits pick the first generated token)
            matched = self.prefix_cache.match(prompt, max_tokens=n - 1)
            for i, segment in enumerate(matched):
                self._cache = self._dispatch(  # noqa: PTA104 (host-side serving state)
                    "prefix_insert", self._insert_jit,
                    (self._cache, tuple(segment), jnp.int32(slot), jnp.int32(i * self._chunk)))
                counter_inc("infer.prefix_insert_dispatches")
            if matched and self._dparams is not None:
                # the prefix cache holds TARGET KV only; backfill the draft
                # cache for the matched prefix with cheap draft-only chunk
                # forwards (ascending — each chunk attends the earlier ones)
                for i in range(len(matched)):
                    ids = prompt[i * self._chunk:(i + 1) * self._chunk][None]
                    self._dcache = self._dispatch(  # noqa: PTA104 (host-side serving state)
                        "draft_chunk", self._draft_chunk_jit,
                        (self._dparams, self._dcache, jnp.asarray(ids),
                         jnp.int32(slot), jnp.int32(i * self._chunk)),
                        label=f"draft_chunk/C{self._chunk}")
            job.next_pos = job.reused_tokens = len(matched) * self._chunk
            counter_inc("serving.prefix_hits" if matched else "serving.prefix_misses")
            counter_inc("serving.prefix_tokens_reused", job.reused_tokens)
            gauge_set("serving.prefix_cache_bytes", self.prefix_cache.bytes_used())
        return job

    @_placed
    def prefill_step(self, job: _PrefillJob) -> bool:
        """Run ONE prefill dispatch for ``job``: the whole bucket program in
        bucketed mode, or one C-token chunk in chunked mode. Returns True
        when the prompt is fully prefilled (``job.first``/``job.more`` are
        then set and the slot starts decoding on the next decode dispatch).

        The program that samples the first token also activates the slot,
        in-graph, so the next decode dispatch serves it whether or not the
        host has seen that token. With a decode step in flight (a caller
        that runs ahead) pulling it here would wait for that step and for
        every chunk queued behind it, and every running request's tokens
        with it: the token is left on the device, the call returns False,
        and the next call — the scheduler's next tick, before its decode
        step — pulls it and returns True."""
        from ..observability import span as _span
        from ..profiler import counter_inc

        if job.done:
            return True
        if job.pending is not None:
            return self._take_first_token(job, *job.pending)
        n, slot = job.n, job.slot
        spec = self._dparams is not None
        if self._chunk is None:
            P = self.bucket_for(n)
            ids = np.zeros((1, P), np.int32)
            ids[0, :n] = job.prompt
            state = self._program_state()
            with _span("infer.prefill"):
                out = self._dispatch(
                    "prefill", self._prefill_jit,
                    state + (self._pos, self._tok, self._active,
                             jnp.asarray(ids), jnp.int32(n), jnp.int32(slot), jnp.int32(job.eos),
                             jnp.int32(job.limit), jnp.int32(job.seed)),
                    label=f"prefill/P{P}")
            self.prefill_programs += 1  # noqa: PTA104 (host-side serving state)
            out = self._take_caches(out, spec)
            self._pos, self._tok, self._active, first, more = out  # noqa: PTA104 (host-side serving state)
            job.next_pos = n
        else:
            C = self._chunk
            if job.next_pos + C < n:
                # intermediate chunk: KV writes only, no logits work
                ids = job.prompt[job.next_pos:job.next_pos + C][None]
                state = self._program_state()
                with _span("infer.prefill_chunk") as chunk_span:
                    self._note(chunk_span, self._dec.chunk_notes(job.next_pos, C))
                    out = self._dispatch(
                        "prefill_chunk", self._chunk_jit,
                        state + (jnp.asarray(ids), jnp.int32(slot), jnp.int32(job.next_pos)),
                        label=f"prefill_chunk/C{C}")
                self.prefill_programs += 1  # noqa: PTA104 (host-side serving state)
                self._take_caches(out if spec else (out,), spec)
                job.next_pos += C
                counter_inc("infer.prefill_chunk_dispatches")
                return False
            # final chunk: cover the remaining rows [next_pos, n) inside one
            # C-token window. The window start stays chunk-aligned unless the
            # padded write would spill past the cache end, in which case it
            # shifts back to [n-C, n) and re-writes a few rows bitwise.
            w = job.next_pos if job.next_pos + C <= self.max_seq_len else n - C
            ids = np.zeros((1, C), np.int32)
            ids[0, :n - w] = job.prompt[w:n]
            state = self._program_state()
            with _span("infer.prefill_chunk") as chunk_span:
                self._note(chunk_span, self._dec.chunk_notes(job.next_pos, n - job.next_pos))
                out = self._dispatch(
                    "prefill_final", self._chunk_final_jit,
                    state + (self._pos, self._tok, self._active,
                             jnp.asarray(ids), jnp.int32(slot), jnp.int32(w),
                             jnp.int32(n - 1 - w), jnp.int32(n), jnp.int32(job.eos),
                             jnp.int32(job.limit), jnp.int32(job.seed)),
                    label=f"prefill_final/C{C}")
            self.prefill_programs += 1  # noqa: PTA104 (host-side serving state)
            out = self._take_caches(out, spec)
            self._pos, self._tok, self._active, first, more = out  # noqa: PTA104 (host-side serving state)
            job.next_pos = n
            counter_inc("infer.prefill_chunk_dispatches")
        job.programs = self.prefill_programs
        self._pos_np[slot] = n
        self._touch(slot)
        if self._inflight is not None:
            # until ``more`` is pulled the host takes the slot for active, as the device may
            self._active_np[slot] = True
            job.pending = (first, more)
            counter_inc("infer.prefill_first_deferred")
            return False
        return self._take_first_token(job, first, more)

    def _take_first_token(self, job: _PrefillJob, first, more) -> bool:
        """Pull the first token and whether the request goes on (the wait
        for the program that sampled them), and close the job."""
        from ..profiler import counter_inc

        job.first = int(first)
        job.more = bool(more)
        job.done, job.pending = True, None
        self._active_np[job.slot] = job.more
        counter_inc("infer.prefill_dispatches")
        counter_inc("infer.tokens")
        if self.prefix_cache is not None:
            self._store_prefix_chunks(job)
        return True

    def _put_slot_consts(self) -> None:
        """Copy the per-slot eos, limit and seed to the device. They change
        at admission (a free slot's, which no step in flight reads a live
        value of) and at reset; every decode dispatch takes the copies."""
        # jnp.array, not asarray: the host arrays are written in place later
        self._slot_consts = (jnp.array(self._eos), jnp.array(self._limit), jnp.array(self._seed))

    def _program_state(self):
        """What every prefill program takes first: the weights and the slot
        buffers (and the draft's, where there is one)."""
        if self._dparams is not None:
            return (self._params, self._dparams, self._cache, self._dcache)
        return (self._params, self._cache)

    def _take_caches(self, out, spec: bool):
        """Keep the cache (and the draft's) a program returned first; the
        rest of its outputs."""
        if spec:
            self._cache, self._dcache = out[:2]  # noqa: PTA104 (host-side serving state)
            return out[2:]
        self._cache = out[0]  # noqa: PTA104 (host-side serving state)
        return out[1:]

    def _store_prefix_chunks(self, job: _PrefillJob) -> None:
        """After a completed prefill, extract and cache every chunk-aligned
        prefix segment of the prompt that isn't cached yet (the slot's rows
        below n are final: decode writes only at positions >= n)."""
        from ..observability.metrics import counter_inc, gauge_set

        cache = self.prefix_cache
        for i in range(job.n // self._chunk):
            key = cache.key(job.prompt, i)
            if cache.has(key):
                continue
            seg_k, seg_v = self._dispatch(
                "prefix_extract", self._extract_jit,
                (self._cache, jnp.int32(job.slot), jnp.int32(i * self._chunk)))
            counter_inc("infer.prefix_extract_dispatches")
            cache.put(key, seg_k, seg_v)
        gauge_set("serving.prefix_cache_bytes", cache.bytes_used())

    def prefill(self, prompt, slot: int, max_new_tokens: int, eos_token_id: Optional[int] = None,
                seed: int = 0) -> Tuple[int, bool]:
        """Admit one prompt into ``slot`` synchronously (every chunk back to
        back): prefix-cache inserts, prefill dispatches, first-token sample.
        Returns ``(first_token, more)`` — ``more`` False means the request
        finished at its first token (eos or max_new_tokens == 1)."""
        job = self.begin_prefill(prompt, slot, max_new_tokens,
                                 eos_token_id=eos_token_id, seed=seed)
        while not self.prefill_step(job):
            pass
        return job.first, job.more

    # ------------------------------------------------------------- decode
    @_placed
    def decode_step(self, fuse: Optional[int] = None, ahead: bool = False):
        """Advance every active slot in ONE dispatch. At fuse depth 1
        returns ``(tokens[B], emitted[B], active[B])``; at depth D > 1 the
        dispatch runs D decode iterations inside one donated ``lax.scan``
        and returns ``(tokens[D, B], emitted[D, B], active[B])`` — the
        eos/limit stop flags ride the scan carry, so a slot that finishes at
        iteration j self-deactivates in-graph (``emitted[j+1:, slot]`` is
        False) with no host round-trip until the stack is drained.

        **When a token reaches the host.** By default the call is
        synchronous: it returns the tokens of the step it launched. With
        ``ahead=True`` (the scheduler's form) it *runs one step ahead*: it
        launches step *k* and then pulls step *k - 1*, the one the previous
        such call launched, so a token reaches the host one call after the
        call that launched its step, and the device has step *k* queued
        while the host pulls, drains and admits. Same shapes and meaning of
        the result; ``emitted`` is all False when nothing was in flight (the
        first call after an idle spell). Everything step *k* needs is on the
        device — eos and limit are applied in-graph, so a slot that finished
        in step *k - 1* sits step *k* out without the host — and no token is
        computed that the synchronous order would not compute. A slot freed
        or admitted into between a step's launch and its pull belongs to
        another request by then: its ``emitted`` reads False and the token is
        dropped, so a late token never lands in a slot's next request. A step
        left in flight when no slot is active any more has nothing to
        deliver and is forgotten unpulled; :meth:`reset` drops one too. The
        engine declines, and the synchronous step runs, where one dispatch
        already returns a stack: with a draft model and at depth > 1. A
        synchronous call while a step is in flight would lose that step's
        tokens, and raises."""
        from ..observability import span as _span
        from ..profiler import counter_inc

        depth = self.fuse if fuse is None else int(fuse)
        if depth < 1:
            raise ValueError(f"fuse depth must be >= 1, got {depth}")
        spec = self._dparams is not None
        n_stats = int(self._dec.n_stats)
        if spec and depth != 1:
            raise ValueError("speculative decode runs at fuse depth 1 (one "
                             "dispatch already emits up to spec_k+1 tokens)")
        ahead = bool(ahead) and depth == 1 and not spec
        if self._inflight is not None and not ahead:
            raise RuntimeError("a decode step launched ahead is still in flight: pull it with "
                               "decode_step(ahead=True) or drop it with reset() before a synchronous step")
        stats = None
        # infer.decode_step runs from entry to the tokens on the host. Its
        # children: infer.decode_launch (the dispatch; eos/limit/seed are on
        # the device already) and infer.decode_sync (the pull, which waits
        # for the device) — of this call's step, or running ahead of the
        # step before it: the end of infer.decode_sync is when the tokens
        # this call returns reached the host. ``queued``: the prefill count at
        # the launch of the step this call pulls (None if it pulls none).
        with _span("infer.decode_step") as step_span:
            queued = self.prefill_programs
            self._note(step_span, self._dec.step_notes(self._pos_np, self._active_np))
            self._pos_np += depth * self._active_np
            if spec:
                from ..observability.metrics import gauge_set

                with _span("infer.decode_launch"):
                    out = self._dispatch(
                        "spec_decode", self._spec_jit,
                        (self._params, self._dparams, self._cache, self._dcache,
                         self._pos, self._tok, self._active) + self._slot_consts,
                        label=f"spec_decode/K{self.spec_k}")
                (self._cache, self._dcache,  # noqa: PTA104 (host-side serving state)
                 self._pos, self._tok, self._active, toks, emitted) = out  # noqa: PTA104 (host-side serving state)
                with _span("infer.decode_sync") as sync:
                    toks = np.asarray(toks)
                    emitted = np.asarray(emitted)
                    self._active_np = np.array(self._active)  # noqa: PTA104 (host-side serving state)
                n_active = int(emitted[0].sum())   # row 0 always emits per live slot
                n_emitted = int(emitted.sum())
                self._spec_drafted += self.spec_k * n_active  # noqa: PTA104 (host-side serving state)
                self._spec_accepted += n_emitted - n_active  # noqa: PTA104 (host-side serving state)
                counter_inc("infer.spec_draft_tokens", self.spec_k * n_active)
                counter_inc("infer.spec_accepted_tokens", n_emitted - n_active)
                if self._spec_drafted:
                    gauge_set("serving.spec_acceptance_rate",
                              self._spec_accepted / self._spec_drafted)
            elif depth == 1:
                with _span("infer.decode_launch"):
                    step = self._launch_decode()
                if ahead:
                    # the launch half was this call's step, the collect half is the last call's
                    if self._inflight is not None:
                        counter_inc("infer.decode_ahead")
                    step, self._inflight = self._inflight, step  # noqa: PTA104 (host-side serving state)
                with _span("infer.decode_sync") as sync:
                    toks, emitted, stats = self._collect_decode(step)
                queued = None if step is None else step.queued
                self._forget_idle_flight()
            else:
                with _span("infer.decode_launch"):
                    consts = (self._params,) + self._slot_consts
                    carry = (self._cache, self._pos, self._tok, self._active)
                    out = self._dispatch(f"decode_x{depth}", self._fused(depth), (consts, carry))
                (self._cache, self._pos, self._tok, self._active), ys = out  # noqa: PTA104 (host-side serving state)
                with _span("infer.decode_sync") as sync:
                    toks = np.asarray(ys[0])
                    emitted = np.asarray(ys[1])
                    if n_stats:
                        stats = np.asarray(ys[2]).sum(axis=0)
                    self._active_np = np.array(self._active)  # noqa: PTA104 (host-side serving state)
            counter_inc("infer.decode_dispatches")
            counter_inc("infer.tokens", int(emitted.sum()))
            if queued is not None:
                # for whoever stamps the pulled tokens: what was queued ahead of their step, and the instant they
                # were on the host: the end of the pull, which the device trace has too
                self.pulled_at = queued  # noqa: PTA104 (host-side serving state)
                self.arrived_ns = sync.end_ns  # noqa: PTA104 (host-side serving state)
            if stats is not None:
                self.last_stats = stats  # noqa: PTA104 (host-side serving state)
                for name, value in zip(self._dec.stat_counters, stats):
                    counter_inc(name, int(value))
                # on the span record of the call that pulled them: what a reader of the traced ticks takes
                step_span.note(**{n.rsplit(".", 1)[-1]: int(v) for n, v in zip(self._dec.stat_counters, stats)})
        return toks, emitted, self._active_np.copy()

    @staticmethod
    def _note(span, attrs: dict) -> None:
        """What the decoder counted, on a span record (nothing to note: no call)."""
        if attrs:
            span.note(**attrs)

    def _launch_decode(self) -> _DecodeInFlight:
        """The launch half of a depth-1 step: dispatch the decode program on
        the carry and keep its report for whoever pulls it."""
        out = self._dispatch("decode", self._decode_jit,
                             (self._params, self._cache, self._pos, self._tok, self._active) + self._slot_consts)
        self._cache, self._pos, self._tok, self._active = out[:4]  # noqa: PTA104 (host-side serving state)
        return _DecodeInFlight(out[4], np.zeros((self.max_batch_slots,), bool), self.prefill_programs)

    def _collect_decode(self, step: Optional[_DecodeInFlight]):
        """The collect half: pull a launched step's report (the one wait for
        the device) and take its word on the slots the host has not touched
        since its launch. ``(tokens, emitted, the decoder's counters or
        None)``; of no step, nothing emitted."""
        B = self.max_batch_slots
        if step is None:
            return np.zeros((B,), np.int32), np.zeros((B,), bool), None
        report = np.asarray(step.report)
        toks, emitted, active = report[:B], report[B:2 * B] != 0, report[2 * B:3 * B] != 0
        self._active_np = np.where(step.touched, self._active_np, active)  # writable host mirror  # noqa: PTA104 (host-side serving state)
        return toks, emitted & ~step.touched, (report[3 * B:] if len(report) > 3 * B else None)

    def _touch(self, slot: int) -> None:
        """The host freed ``slot`` or admitted a request into it: what a
        step in flight reports of it is no longer its owner's."""
        if self._inflight is not None:
            self._inflight.touched[slot] = True

    def _forget_idle_flight(self) -> None:
        """A step in flight with no slot still active on the host was
        launched on free slots, or every request it served has been freed
        since: it has no token to deliver, so it is forgotten unpulled."""
        if self._inflight is not None and not self._active_np.any():
            self._inflight = None  # noqa: PTA104 (host-side serving state)

    def free_slot(self, slot: int) -> None:
        """Release a slot for the next admission (cancels it if still live;
        a token a step in flight computed for it is dropped at the pull)."""
        if self._active_np[slot]:
            self._active = self._active.at[slot].set(False)  # noqa: PTA104 (host-side serving state)
            self._active_np[slot] = False  # noqa: PTA104 (host-side serving state)
        self._occupied[slot] = False
        self._touch(slot)
        self._forget_idle_flight()

    @_placed
    def reset(self) -> None:
        """Drop every in-flight request, a decode step launched ahead with
        them, and zero the slot state (the cache keeps its buffers — stale
        K/V is always overwritten before it can be attended)."""
        B = self.max_batch_slots
        self._pos = jnp.zeros((B,), jnp.int32)
        self._tok = jnp.zeros((B,), jnp.int32)
        self._active = jnp.zeros((B,), bool)
        self._active_np[:] = False
        self._pos_np[:] = 0
        self._occupied[:] = False
        self._eos[:] = -1
        self._limit[:] = 0
        self._seed[:] = 0
        self._put_slot_consts()
        self._inflight = None

    # ------------------------------------------------------------- helpers
    def generate(self, ids, max_new_tokens: int = 32, eos_token_id: Optional[int] = None,
                 seed: int = 0, fuse: Optional[int] = None) -> np.ndarray:
        """Batch generate through the slot machinery (parity helper + the
        bench decode path): each row takes one slot, prefill once per row,
        then decode steps (at ``fuse`` depth — default the engine's) until
        every row finishes. Returns ``[b, s0 + max_new_tokens]`` int32 (rows
        that hit eos pad with it) — same contract as
        ``GPTForPretraining.generate``."""
        ids = np.asarray(ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        b, s0 = ids.shape
        if b > self.max_batch_slots:
            raise ValueError(f"batch {b} exceeds max_batch_slots {self.max_batch_slots}")
        self.reset()
        rows = [[] for _ in range(b)]
        for i in range(b):
            tok, _more = self.prefill(ids[i], slot=i, max_new_tokens=max_new_tokens,
                                      eos_token_id=eos_token_id, seed=seed)
            rows[i].append(tok)
        while self._active_np.any():
            toks, emitted, _ = self.decode_step(fuse=fuse)
            toks = np.atleast_2d(toks)
            emitted = np.atleast_2d(emitted)
            for d in range(toks.shape[0]):
                for i in range(b):
                    if emitted[d, i]:
                        rows[i].append(int(toks[d, i]))
        for i in range(b):
            self.free_slot(i)
        out = np.zeros((b, s0 + int(max_new_tokens)), np.int32)
        out[:, :s0] = ids
        for i, r in enumerate(rows):
            pad = r[-1] if eos_token_id is None else int(eos_token_id)
            r = r + [pad] * (int(max_new_tokens) - len(r))
            out[i, s0:] = r[:int(max_new_tokens)]
        return out

    def explain(self, analyze: bool = False) -> List[dict]:
        """Per-specialization cost rows (prefill buckets/chunks, prefix
        insert/extract, and the decode programs) captured at AOT compile —
        render with ``observability.format_cost_table``.

        ``analyze=True`` attaches the SPMD analyzer verdict (PTA2xx) per
        retained executable under ``"spmd"`` — decode programs are checked
        with the PTA203 serving rule (any compiled-in collective fires per
        generated token)."""
        rows = [dict(r) for r in self._specializations]
        if analyze:
            from ..analysis import spmd as _spmd

            for row, entry in zip(rows, list(self._compiled.values())):
                if "spmd" in row or not hasattr(entry, "as_text"):
                    continue
                kind = str(row.get("kind", ""))
                row["spmd"] = _spmd.analyze_compiled(
                    entry, label=row.get("label", ""), kind=kind,
                    options=_spmd.ShardCheckOptions(
                        decode=kind.startswith("decode"))).summary()
        return rows

    def cache_bytes(self) -> int:
        """Device bytes held by the preallocated target K/V cache, summed
        over the ACTUAL stored leaves — under ``kv_dtype="int8"`` that is
        the int8 payload plus the f32 scale planes, not the compute dtype."""
        return self._buffer_bytes(reset=False)

    def _buffer_bytes(self, reset: bool, named: str = "") -> int:
        """Stored bytes of the slot buffers that are (``reset``) or are not
        zeroed at admission: recurrent state, or cache rows; of those, the
        ones whose spec's name starts with ``named``."""
        total = 0
        for spec, buf in zip(self._specs, self._cache):
            if spec.reset_at_admission == reset and spec.name.startswith(named):
                total += sum(l.size * jnp.dtype(l.dtype).itemsize for l in jax.tree_util.tree_leaves(buf))
        return int(total)

    def draft_cache_bytes(self) -> int:
        """Device bytes held by the draft model's K/V cache (0 without a
        draft)."""
        if self._dcache is None:
            return 0
        leaves = jax.tree_util.tree_leaves(self._dcache)
        return int(sum(l.size * jnp.dtype(l.dtype).itemsize for l in leaves))

    def kv_bytes_per_slot(self) -> int:
        """Per-request HBM cost of admission: the target cache's stored
        bytes divided by the slot count (the ``infer.kv_bytes_per_slot``
        gauge — sizing concurrent-slot capacity from this number stays
        honest under int8 KV)."""
        return self.cache_bytes() // self.max_batch_slots

    def latent_bytes_per_slot(self) -> int:
        """Of :meth:`kv_bytes_per_slot`, the bytes in latent caches (buffers
        named ``latent*``: rows of ``[c | k_r]`` with no head axis): the
        ``infer.latent_bytes_per_slot`` gauge. 0 for a model that caches keys
        and values."""
        return self._buffer_bytes(reset=False, named="latent") // self.max_batch_slots

    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state a slot holds beside its cache rows (the
        buffers admission zeroes — ``state*`` and ``conv*`` of the delta-rule
        layers, ``ssm_state*`` and ``conv_tail*`` of the Mamba-2 layers): the
        ``infer.state_bytes_per_slot`` gauge. 0 for a model whose slots hold
        keys and values only."""
        return self._buffer_bytes(reset=True) // self.max_batch_slots

    def spec_stats(self) -> dict:
        """Cumulative speculative-decoding counters: proposals drafted,
        proposals accepted, and their ratio (0.0 before any decode)."""
        drafted = self._spec_drafted
        return {"spec_k": self.spec_k, "drafted": drafted,
                "accepted": self._spec_accepted,
                "acceptance_rate": (self._spec_accepted / drafted) if drafted else 0.0}

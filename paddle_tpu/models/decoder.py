"""What the serving engine asks of a model: its *decoder*.

``DecodeEngine`` (``inference/engine.py``) owns slots, admission, the compiled
programs' bookkeeping (first token, eos and limit, sampling keys) and the
launch-and-pull of a tick. Everything that depends on the architecture it gets
from the object ``model.decoder()`` returns:

- **parameters**: ``params(int8=False)``, the pytree the programs take as
  their first argument (weights only: no host constants), and
  ``fingerprint()``, the sizes that key the on-disk executable cache;
- **per-slot buffers**: ``buffer_specs(slots, rows, kv_dtype)`` names each
  buffer a slot owns with its shape, dtype, the axis the slots lie on, and
  whether admission has to zero it. Rows of a key/value cache need no reset:
  a row is masked by the slot's position and written before it is attended.
  A recurrent state is read whole by the next token, so the slot's first
  prefill program (``start == 0``) starts from zeros *inside the program*; no
  separate dispatch resets it. ``alloc`` makes the buffers, in the same order;
- **forwards**, traced inside the engine's jitted programs: ``prefill`` (a
  whole padded prompt into a fresh slot), ``chunk`` (``C`` prompt tokens of
  one slot at ``start``; with ``last_row`` the prompt's final chunk, which
  also returns that row's logits and treats the rows past it as padding),
  ``decode`` (one token for every slot at per-slot positions, writes gated by
  ``active``) and, for a model that can verify a drafted window, ``window``;
- for a key/value cache only, the chunk-aligned **segments** the prefix
  cache keeps (``segment_extract`` / ``segment_insert`` / ``segment_bytes``).

``recurrent`` says a slot holds state that cannot be rebuilt from cached rows:
the engine then refuses a prefix cache, a draft model and an int8 cache (state
snapshots are not built), and never re-runs prompt tokens (a final chunk is
not shifted back over rows already written).

``n_stats`` int32 counters may come back from ``decode`` (the routed experts'
load); the engine pulls them in the same transfer as the step's tokens.
``step_notes(positions, active)`` and ``chunk_notes(start, rows)`` are what a
decoder counts on the host, from the slots' positions as the engine knows them
(no device pull): the engine notes them on the ``infer.decode_step`` and
``infer.prefill_chunk`` span records.

The token samplers live here too: they are the engine's, not a model's.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

__all__ = ["BufferSpec", "Decoder", "filtered_logits", "select_token", "select_token_rows"]


class BufferSpec(NamedTuple):
    """One per-slot buffer of a decoder's cache."""
    name: str
    shape: Tuple[int, ...]      # the whole buffer, every slot
    dtype: str
    slot_axis: int              # which axis of ``shape`` counts the slots
    reset_at_admission: bool    # zeroed (inside the first prefill program) when a slot is admitted


class Decoder:
    """Base of a model's decoder: the defaults of a plain key/value cache."""

    recurrent = False
    n_stats = 0
    stat_counters = ()          # the counters' names, ``n_stats`` of them (``infer.<...>``)
    dtype = "float32"           # the compute dtype of weights and activations
    has_window = False
    vocab_size = 0
    max_positions = 0

    def params(self, int8: bool = False):
        raise NotImplementedError

    def fingerprint(self) -> tuple:
        raise NotImplementedError

    def buffer_specs(self, slots: int, rows: int, kv_dtype=None) -> Tuple[BufferSpec, ...]:
        raise NotImplementedError

    def step_notes(self, positions, active) -> dict:
        """Attributes of a decode step launched with the slots at
        ``positions`` (host int array ``[B]``) where ``active``."""
        return {}

    def chunk_notes(self, start: int, rows: int) -> dict:
        """Attributes of a prefill program over ``rows`` prompt rows from
        ``start``."""
        return {}

    def alloc(self, slots: int, rows: int, kv_dtype=None):
        """The buffers of ``buffer_specs``, zeroed, as a tuple in that order."""
        return tuple(jnp.zeros(s.shape, s.dtype) for s in self.buffer_specs(slots, rows, kv_dtype))


def filtered_logits(logits, temperature, top_k, top_p):
    """Temperature/top-k/top-p filtered f32 logits over [b, V] — the exact
    transform :func:`select_token` samples from, factored out so
    speculative decoding's residual-resampling acceptance test works on the
    SAME filtered distribution the sequential sampler would draw from."""
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        k_eff = min(int(top_k), logits.shape[-1])  # top_k > vocab = keep all
        kth = jnp.sort(logits, axis=-1)[..., -k_eff][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sl = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sl, axis=-1)
        keep = jnp.cumsum(probs, axis=-1) - probs < top_p  # always keep top-1
        threshold = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return logits


def select_token(logits, key, do_sample, temperature, top_k, top_p):
    """Greedy or temperature/top-k/top-p sampling over [b, V] logits."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filtered_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def select_token_rows(logits, keys, do_sample, temperature, top_k, top_p):
    """Per-row variant of :func:`select_token` for slot-masked sampling:
    ``keys`` carries one PRNG key PER batch slot so a request's sample stream
    depends only on its own (seed, position) — never on which slot it landed
    in or what its batch neighbours are doing (no cross-request leakage)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pick = lambda lg, k: select_token(lg[None], k, True, temperature, top_k, top_p)[0]  # noqa: E731
    return jax.vmap(pick)(logits, keys)

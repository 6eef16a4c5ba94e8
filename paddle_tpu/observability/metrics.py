"""Metrics registry: counters + gauges + bounded histograms.

Generalizes the bare dispatch counters PR 3 put in ``paddle_tpu.profiler``
(reference: the per-tracer op/run accounting in platform/profiler) into the
single always-on metrics store for the runtime. Design constraints:

- **Hot path is one dict operation.** ``counter_inc``/``observe`` do a
  single dict lookup + in-place mutation under the GIL — no locks, no
  allocation on the steady state — so the Executor/TrainStep dispatch
  paths can bump them unconditionally.
- **Histograms are bounded.** A histogram is a fixed vector of bucket
  counts plus (count, sum, min, max); observing never allocates, so a
  billion-step run holds the same few hundred bytes per series.
- **Two exports.** ``snapshot()`` returns plain JSON-able dicts (the
  exporter's ``/snapshot``, tests); ``prometheus_text()`` renders the Prometheus text exposition
  format (counters, gauges, and histograms with ``_bucket``/``_sum``/
  ``_count`` series) for scraping.

This module is intentionally dependency-free (stdlib only) so the profiler
and every runtime layer can import it without cycles.
"""
from __future__ import annotations

import math
import threading
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Histogram", "counter_inc", "counter", "counters", "reset_counters", "gauge_set",
    "gauges", "observe", "histogram", "histograms", "declare_counter",
    "declare_histogram", "declare_help", "snapshot", "prometheus_text",
    "escape_help", "escape_label_value", "reset_all",
]

# Default span-duration buckets (seconds): half-decade geometric ladder from
# 1us to 100s. 17 buckets + overflow covers a TPU dispatch (~10us) and a
# multi-minute compile in the same series.
DEFAULT_BUCKETS: Tuple[float, ...] = tuple(
    10.0 ** (e / 2.0) for e in range(-12, 5)
)

_COUNTERS: Dict[str, float] = {}
_GAUGES: Dict[str, float] = {}
_HISTOGRAMS: Dict[str, "Histogram"] = {}
# creation (not observation) of histograms is the only racy structural
# mutation; guard it so two threads first-observing one name don't drop data
_CREATE_LOCK = threading.Lock()


class Histogram:
    """Bounded histogram: fixed bucket upper bounds + running aggregates.

    ``observe`` is the hot path: a linear scan over the bounds (20 by
    default; cheaper than bisect's function-call overhead at this size) and
    four scalar updates. No allocation, no lock — single-writer-per-GIL-slice safe.
    """

    __slots__ = ("bounds", "bucket_counts", "count", "sum", "min", "max",
                 "overflow_min")

    def __init__(self, bounds: Optional[Iterable[float]] = None):
        self.bounds: Tuple[float, ...] = tuple(bounds) if bounds is not None else DEFAULT_BUCKETS
        if any(nxt <= prev for prev, nxt in zip(self.bounds, self.bounds[1:])):
            raise ValueError("histogram bucket bounds must be strictly increasing")
        self.bucket_counts: List[int] = [0] * (len(self.bounds) + 1)  # +overflow
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        # smallest value that landed in the overflow bucket: the overflow
        # bucket's true lower edge for percentile interpolation (bounds[-1]
        # is a lie when the whole distribution sits above it)
        self.overflow_min = math.inf

    def observe(self, value: float, n: int = 1) -> None:
        """Take ``value``, ``n`` times over (one scan for a value that many
        observations share: the token gap of every slot of a decode step)."""
        i = 0
        for b in self.bounds:
            if value <= b:
                break
            i += 1
        self.bucket_counts[i] += n
        if i == len(self.bounds) and value < self.overflow_min:
            self.overflow_min = value
        self.count += n
        self.sum += value * n
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def percentile(self, q: float) -> Optional[float]:
        """Approximate percentile (0..100) by linear interpolation inside
        the bucket holding the q-th observation; None when empty.

        The overflow bucket anchors its low edge at ``overflow_min`` (the
        smallest value actually observed past the last bound) instead of
        ``bounds[-1]`` — with out-of-range distributions the old anchor
        skewed percentiles toward the bound. All anchors degrade to bucket
        bounds when the running min/max are not finite (delta histograms
        built from bucket-count snapshots never observe values)."""
        if self.count == 0:
            return None
        target = max(1.0, (q / 100.0) * self.count)
        seen = 0
        for i, n in enumerate(self.bucket_counts):
            if n == 0:
                continue
            if seen + n >= target:
                if i >= len(self.bounds):  # overflow bucket
                    lo = self.overflow_min
                    if not math.isfinite(lo):
                        lo = self.bounds[-1] if self.bounds else 0.0
                    hi = self.max if math.isfinite(self.max) else lo
                elif i > 0:
                    lo, hi = self.bounds[i - 1], self.bounds[i]
                else:
                    lo = (self.min if math.isfinite(self.min)
                          else min(0.0, self.bounds[0]))
                    hi = self.bounds[0]
                if math.isfinite(self.min):
                    lo = max(lo, self.min)
                if math.isfinite(self.max) and self.max >= lo:
                    hi = min(hi, self.max)
                frac = (target - seen) / n
                return lo + (hi - lo) * frac
            seen += n
        return self.max if math.isfinite(self.max) else None

    def summary(self) -> dict:
        if self.count == 0:
            return {"count": 0, "sum": 0.0}
        return {
            "count": self.count,
            "sum": self.sum,
            "mean": self.sum / self.count,
            "min": self.min,
            "max": self.max,
            "p50": self.percentile(50),
            "p90": self.percentile(90),
            "p99": self.percentile(99),
        }


# ------------------------------------------------------------------ counters
def counter_inc(name: str, n: float = 1) -> None:
    """Bump a named monotonic counter (lock-free single-dict hot path)."""
    _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def counter(name: str) -> float:
    """One counter's value (0 for a series nothing has bumped yet)."""
    return _COUNTERS.get(name, 0)


def counters(prefix: str = "") -> Dict[str, float]:
    return {k: v for k, v in _COUNTERS.items() if k.startswith(prefix)}


def reset_counters(prefix: str = "") -> None:
    """Zero counters matching ``prefix`` (all when empty). Declared names
    stay present (at 0) so exports keep a stable series set."""
    for k in [k for k in _COUNTERS if k.startswith(prefix)]:
        if k in _DECLARED_COUNTERS:
            _COUNTERS[k] = 0
        else:
            del _COUNTERS[k]


def declare_counter(name: str, help_str: str = "") -> None:
    """Pre-register ``name`` so it exports as 0 before the first increment
    (scrapes see the full series set from process start). ``help_str``
    becomes the series' ``# HELP`` line in the Prometheus exposition."""
    _DECLARED_COUNTERS.add(name)
    _COUNTERS.setdefault(name, 0)
    if help_str:
        _HELP[name] = help_str  # noqa: PTA104 (host-side, never traced)


def declare_help(name: str, help_str: str) -> None:
    """Attach ``# HELP`` text to any series (counter, gauge, histogram)."""
    _HELP[name] = help_str


_DECLARED_COUNTERS: set = set()
_HELP: Dict[str, str] = {}

# Serving-tier series (inference engine + continuous-batching scheduler):
# pre-declared here so a scrape of an idle predictor process already shows
# the full serving surface at 0. ``infer.compiles`` is the series the
# "decode of N tokens compiles exactly 2 programs" regression pins.
SERVING_COUNTERS: Tuple[str, ...] = (
    "infer.compiles", "infer.runs",
    "infer.prefill_dispatches", "infer.decode_dispatches", "infer.tokens",
    # run-ahead of one decode step (engine.decode_step(ahead=True)): launches made with the step before not yet pulled
    # (the decode dispatches less the pipe's fills), and admissions whose first token was left on the device for the
    # next tick because a step was in flight (pulling it at once would have drained the pipe)
    "infer.decode_ahead", "infer.prefill_first_deferred",
    # the routed experts' load of a decode step, pulled with its tokens: (token, expert) pairs routed to an
    # expert held here, and held experts with at least one pair, both summed over the layers
    "infer.moe.assignments_local", "infer.moe.experts_hit",
    "infer.prefill_chunk_dispatches",
    "infer.prefix_insert_dispatches", "infer.prefix_extract_dispatches",
    "infer.aot_cache_hits", "infer.aot_cache_stores",
    "serving.requests_submitted", "serving.requests_admitted",
    "serving.requests_completed", "serving.tokens_generated",
    "serving.requests_cancelled", "serving.deadline_exceeded",
    "serving.prefix_hits", "serving.prefix_misses",
    "serving.prefix_tokens_reused",
    # speculative decoding (PR 18): proposals drafted vs accepted — their
    # ratio is the serving.spec_acceptance_rate gauge and the lever behind
    # decode_dispatches_per_token dropping below 1/(spec_k acceptance)
    "infer.spec_draft_tokens", "infer.spec_accepted_tokens",
)

# Serving-fleet tier (inference/fleet.py + router.py): the failure-handling
# ledger — requeues counts in-flight requests replayed off dead replicas,
# sheds counts admissions rejected by queue-depth control, deadline_hits
# counts per-request deadline expiries, and the routed_* pair splits
# placements by discipline (prefix-chain affinity vs least-load).
FLEET_COUNTERS: Tuple[str, ...] = (
    "fleet.requests_submitted", "fleet.requests_completed",
    "fleet.requeues", "fleet.sheds", "fleet.deadline_hits",
    "fleet.replica_deaths", "fleet.scale_outs",
    "fleet.routed_affinity", "fleet.routed_load",
    # cross-process tier (inference/procfleet.py): token chunks applied to
    # the parent ledger from replica-subprocess stream messages
    "fleet.stream_chunks",
    # explicit mid-decode cancellations through the fleet front (client
    # disconnects routed down from the ingress, admin cancels)
    "fleet.cancels",
)

# Network ingress + RPC transport (PR 20: inference/ingress.py + rpc.py).
# ingress.* is the HTTP front door's admission ledger — requests accepted,
# responses served, the three structured rejection classes (429 overload,
# 503 transport backpressure, 503 draining), idempotency-key replays
# served from the ledger without re-generating, and client disconnects
# turned into mid-decode cancels. rpc.* meters the transport split: how
# much of the hot path rode the fast-path socket vs the TCPStore, socket
# connects, socket->store degradations, and partial drains returned when
# a flaky store failed mid-drain (the acknowledged-message-loss fix).
INGRESS_COUNTERS: Tuple[str, ...] = (
    "ingress.requests", "ingress.responses",
    "ingress.rejected_overload", "ingress.rejected_backpressure",
    "ingress.rejected_draining",
    "ingress.idempotent_hits", "ingress.disconnect_cancels",
    "ingress.drains",
    "rpc.socket_msgs", "rpc.store_msgs", "rpc.socket_connects",
    "rpc.socket_fallbacks", "rpc.partial_drains",
)

# Kernel-registry selection series (paddle_tpu.ops.registry): one
# ``picked`` (a real kernel won) or ``fallback`` (the XLA composite served)
# increment per distinct call signature — so ``kernels.<k>.picked`` equals
# the compile count, the invariant tests/test_kernel_registry.py pins. The registry
# also declares these at define_kernel time; listing the built-in kernels
# here keeps idle-process scrapes complete.
KERNEL_COUNTERS: Tuple[str, ...] = (
    "kernels.sdpa.picked", "kernels.sdpa.fallback",
    "kernels.attention_core.picked", "kernels.attention_core.fallback",
    "kernels.moe.picked", "kernels.moe.fallback",
    "kernels.decode_attention.picked", "kernels.decode_attention.fallback",
    "kernels.grouped_matmul.picked", "kernels.grouped_matmul.fallback",
    "kernels.moe_combine.picked", "kernels.moe_combine.fallback",
)

# SPMD sharding analyzer (paddle_tpu.analysis.spmd, FLAGS_shard_check):
# one shard_checks increment per analyzed specialization; diagnostics/
# errors count findings, collectives counts the parsed schedule length.
ANALYSIS_COUNTERS: Tuple[str, ...] = (
    "analysis.shard_checks", "analysis.diagnostics",
    "analysis.errors", "analysis.collectives",
)

# Dispatch-hygiene family (paddle_tpu.analysis.hygiene + sanitizer):
# hygiene.* counts the static CLI/self-check surface (files walked,
# PTA3xx findings emitted); sanitizer.* counts the runtime guards behind
# FLAGS_sanitize — host transfers caught by the transfer guard, distinct
# signatures seen by the recompile-churn sentinel (and sentinel trips),
# stale donated-state detections, leaves poisoned after a donating
# dispatch, and host-ledger growth-sentinel trips.
HYGIENE_COUNTERS: Tuple[str, ...] = (
    "hygiene.files_checked", "hygiene.findings",
    "sanitizer.host_transfers", "sanitizer.compiles_seen",
    "sanitizer.recompile_churn", "sanitizer.stale_state",
    "sanitizer.leaves_poisoned", "sanitizer.ledger_growth",
)

# Auto-parallel planner + checkpoint converter + AOT training-executable
# cache (distributed/planner.py, distributed/converter.py,
# introspect.aot_compile cache_scope): evaluations counts candidate
# lowerings (0 on a plan-cache hit — the zero-search restart pin),
# converter.reshards counts cross-mesh checkpoint conversions, and the
# *.aot_cache_* series pin the warm-restart path (compiles == 0 when every
# specialization loads from disk).
PLANNER_COUNTERS: Tuple[str, ...] = (
    "planner.searches", "planner.candidates", "planner.evaluations",
    "planner.pruned", "planner.cache_hits", "planner.cache_stores",
    "converter.reshards", "converter.bytes",
    "train_step.aot_cache_hits", "train_step.aot_cache_stores",
    "executor.aot_cache_hits", "executor.aot_cache_stores",
)


# Recommender workload (distributed/embedding.py + models/dlrm.py):
# embedding.lookups counts ShardedEmbedding forwards (per trace under jit
# — one per compiled program — and per call in eager); ids_exchanged /
# a2a_bytes are the static per-step exchange payloads those lookups
# declared (shape-derived, see embedding.exchange_stats); rows_touched is
# the eager-mode unique-row count (traced steps report through
# embedding_exchange run-log events); rows_checkpointed counts table rows
# published by EmbeddingCheckpointRotation. recsys.steps/examples are the
# training-driver counters the DLRM example (examples/train_dlrm.py) bumps.
RECSYS_COUNTERS: Tuple[str, ...] = (
    "recsys.steps", "recsys.examples",
    "embedding.lookups", "embedding.ids_exchanged", "embedding.a2a_bytes",
    "embedding.rows_touched", "embedding.rows_checkpointed",
)


# Observability plane itself (PR 14: trace.py / flightrec.py / runlog
# rotation / measured.py / exporter.py) — the plane meters its own cost so
# "is tracing expensive" is answerable from the same scrape.
OBS_COUNTERS: Tuple[str, ...] = (
    "trace.traces", "trace.spans",
    # span records pushed out of the full in-memory ring (spans.RING_CAPACITY): a window read back through
    # spans.recent() is whole only if this did not move while it ran
    "trace.spans_evicted",
    "flightrec.dumps",
    "runlog.rotations", "runlog.gc_removed",
    "measured.persists",
    "exporter.requests", "exporter.bind_failures",
)


# Judgment layer (PR 19: slo.py + regress.py — the detection plane over the
# collection plane). slo.* meters the monitor itself (evaluations, specs
# that violated their objective this pass); alerts.* is the burn-rate alert
# ledger (fired/cleared transitions, split by severity); regress.* is the
# perf-regression sentinel (histories checked, regressions fired/cleared,
# flight records dumped on the critical path).
SLO_COUNTERS: Tuple[str, ...] = (
    "slo.evaluations", "slo.violations",
    "alerts.fired", "alerts.cleared", "alerts.page", "alerts.warn",
    "regress.checks", "regress.regressions", "regress.cleared",
    "regress.flightrecs",
)


# Every gauge_set / observe call in paddle_tpu/ with a literal series name
# must appear in the matching tuple below — tests/test_observability.py's
# declaration drift guard greps the package and fails on a name set here
# drifting from the names used at call sites. (Dynamically-named series —
# f-strings, span names — are exempt: the guard only parses literals.)
KNOWN_GAUGES: Tuple[str, ...] = (
    "serving.prefix_cache_bytes", "serving.queue_depth",
    "serving.active_slots",
    # cumulative accepted/drafted ratio of the speculative decoder, and the
    # stored (post-quantization) HBM cost of one KV slot — concurrent-slot
    # capacity planning divides free HBM by this number
    "serving.spec_acceptance_rate", "infer.kv_bytes_per_slot",
    # what a slot holds beside its cache rows: the recurrent state admission zeroes (0 for a key/value-only model)
    "infer.state_bytes_per_slot",
    # of kv_bytes_per_slot, the part in latent caches ([c | k_r] rows with no head axis; 0 for a key/value cache)
    "infer.latent_bytes_per_slot",
    "fleet.replicas_alive", "fleet.replicas_dead", "fleet.queue_depth",
    "stability.lr", "amp.loss_scale",
    # judgment layer (PR 19): age of the stalest alive replica heartbeat
    # (the runtime.heartbeat_staleness_s SLO input) and the count of SLOs
    # currently firing an alert, split out for the page severity
    "fleet.heartbeat_staleness_seconds",
    "slo.firing", "slo.firing_page",
    # network ingress (PR 20): streams/requests currently being served by
    # the HTTP front door — the number graceful drain waits on
    "ingress.inflight",
)

KNOWN_HISTOGRAMS: Tuple[str, ...] = (
    "serving.ttft_seconds",
    "serving.queue_seconds", "serving.latency_seconds",
    # the gap between two consecutive tokens of one request, stamped where the tokens arrive (the end of the engine's
    # infer.decode_sync): one observation a gap, taken once a tick for each distinct gap with its count
    "serving.itl_seconds",
    "fleet.latency_seconds",
    # network ingress (PR 20): wall time of one HTTP request end-to-end
    # and time-to-first-streamed-chunk as the client sees them
    "ingress.request_seconds", "ingress.ttft_seconds",
    "hapi.step",
    # judgment layer (PR 19): cost of one SLOMonitor.evaluate pass — the
    # series that says what the monitor itself costs
    "slo.eval_seconds",
)


# Series that want another ladder than DEFAULT_BUCKETS. A token gap is promised in milliseconds, and half a decade
# a bucket would put every gap of a cell into one or two: eight buckets a decade (steps of a third) from 0.1 ms to 10 s.
_BOUNDS: Dict[str, Tuple[float, ...]] = {
    "serving.itl_seconds": tuple(10.0 ** (e / 8.0) for e in range(-32, 9)),
}


# -------------------------------------------------------------------- gauges
def gauge_set(name: str, value: float) -> None:
    _GAUGES[name] = value


def gauges(prefix: str = "") -> Dict[str, float]:
    return {k: v for k, v in _GAUGES.items() if k.startswith(prefix)}


# ---------------------------------------------------------------- histograms
def histogram(name: str, bounds: Optional[Iterable[float]] = None) -> Histogram:
    """The histogram registered under ``name`` (created on first use)."""
    h = _HISTOGRAMS.get(name)
    if h is None:
        with _CREATE_LOCK:
            h = _HISTOGRAMS.get(name)
            if h is None:
                h = _HISTOGRAMS[name] = Histogram(bounds if bounds is not None else _BOUNDS.get(name))
    return h


declare_histogram = histogram


def observe(name: str, value: float, n: int = 1) -> None:
    """Record ``value`` (``n`` times) into the bounded histogram ``name``
    (hot path: one dict hit + one bucket update once the series exists)."""
    h = _HISTOGRAMS.get(name)
    if h is None:
        h = histogram(name)
    h.observe(value, n)


def histograms(prefix: str = "") -> Dict[str, Histogram]:
    return {k: v for k, v in _HISTOGRAMS.items() if k.startswith(prefix)}


# ------------------------------------------------------------------- exports
def snapshot() -> dict:
    """JSON-able snapshot of every series: counters, gauges, and histogram
    summaries (count/sum/mean/min/max/p50/p90/p99)."""
    return {
        "counters": dict(_COUNTERS),
        "gauges": dict(_GAUGES),
        "histograms": {k: h.summary() for k, h in sorted(_HISTOGRAMS.items())},
    }


def _prom_name(name: str, suffix: str = "") -> str:
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    base = "".join(out)
    if not base or not (base[0].isalpha() or base[0] == "_"):
        base = "_" + base
    return f"paddle_tpu_{base}{suffix}"


def escape_help(text: str) -> str:
    """Escape ``# HELP`` text per the exposition format: backslash and
    newline (double quotes are legal raw in help text)."""
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def escape_label_value(value: str) -> str:
    """Escape a label value per the exposition format: backslash, newline,
    and double quote."""
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _help_lines(name: str, pn: str) -> List[str]:
    help_str = _HELP.get(name)
    return [f"# HELP {pn} {escape_help(help_str)}"] if help_str else []


def prometheus_text(prefix: str = "") -> str:
    """Render every series (name-prefix-filtered when ``prefix`` is given)
    in the Prometheus text exposition format. Counters get the ``_total``
    suffix, histograms the ``<name>_seconds_bucket{le=...}`` (cumulative) /
    ``_sum`` / ``_count`` triple — durations are seconds. Declared help
    text renders as ``# HELP`` with backslash/newline escaping; the ``le``
    label values go through :func:`escape_label_value` like any other."""
    lines: List[str] = []
    for name in sorted(_COUNTERS):
        if not name.startswith(prefix):
            continue
        pn = _prom_name(name, "_total")
        lines.extend(_help_lines(name, pn))  # noqa: PTA104 (host-side, never traced)
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {_COUNTERS[name]:g}")
    for name in sorted(_GAUGES):
        if not name.startswith(prefix):
            continue
        pn = _prom_name(name)
        lines.extend(_help_lines(name, pn))  # noqa: PTA104 (host-side, never traced)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {_GAUGES[name]:g}")
    for name in sorted(_HISTOGRAMS):
        if not name.startswith(prefix):
            continue
        h = _HISTOGRAMS[name]
        pn = _prom_name(name, "_seconds")
        lines.extend(_help_lines(name, pn))  # noqa: PTA104 (host-side, never traced)
        lines.append(f"# TYPE {pn} histogram")
        cum = 0
        for bound, n in zip(h.bounds, h.bucket_counts):
            cum += n
            le = escape_label_value(f"{bound:g}")
            lines.append(f'{pn}_bucket{{le="{le}"}} {cum}')  # noqa: PTA104 (host-side, never traced)
        lines.append(f'{pn}_bucket{{le="+Inf"}} {h.count}')
        lines.append(f"{pn}_sum {h.sum:g}")
        lines.append(f"{pn}_count {h.count}")
    return "\n".join(lines) + "\n"


def reset_all() -> None:
    """Test helper: clear every series (declared counters re-zero)."""
    _COUNTERS.clear()
    _GAUGES.clear()
    _HISTOGRAMS.clear()
    for name in _DECLARED_COUNTERS:
        _COUNTERS[name] = 0

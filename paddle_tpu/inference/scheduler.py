"""Continuous (in-flight) batching scheduler over a :class:`DecodeEngine`.

Requests arrive at any time and are admitted into free batch slots
mid-stream: a new request's prefill runs while other slots keep decoding,
and every decode dispatch advances ALL occupied slots (per-slot position
indices, slot-masked sampling). No request waits for a batch to drain —
the vLLM/Orca serving discipline on top of a fixed compiled-program family.

Round 2 of the serving hot path rides the engine's three throughput knobs:

- **chunked prefill** (``prefill_chunk``): an admission is a sequence of
  fixed-size chunk dispatches driven one per tick, INTERLEAVED with decode
  — a 2k-token prompt no longer holds every in-flight request's tokens back
  for its whole prefill. What it does cost them is in the *token gap*: the
  time between two consecutive tokens of one request, stamped where the
  tokens arrive (``serving.itl_seconds``; the longest a request saw is its
  ``max_gap_seconds``, carried by its ``finished`` run-log event), and each
  tick's span record keeps its gaps with the prefill programs the device ran
  inside each.
- **fused decode** (``fuse=D``): one decode dispatch returns a ``[D, B]``
  token stack; the scheduler drains it in order, appending only tokens
  whose slot really emitted (finished slots self-deactivate in-graph).
- **prefix reuse** (``prefix_cache_mb``): admissions that hit the
  prompt-prefix KV cache skip the matched chunks entirely —
  ``admitted`` events carry ``prefix_tokens`` for hit-rate reporting.

Requests carry optional **deadlines** (``submit(deadline_s=...)``) and can
be **cancelled** mid-flight (:meth:`ContinuousBatchingScheduler.cancel`):
an expired or cancelled request frees its batch slot immediately — even
mid-decode — instead of holding it to drain, and lands in ``.cancelled``
with status ``deadline_exceeded``/``cancelled``. The serving fleet builds
its graceful degradation on both.

Telemetry rides the PR-4 spine: every request emits ``request`` run-log
events (``submitted`` → ``admitted`` → ``finished``, or ``cancelled``/
``deadline_exceeded``) with queue/prefill/
decode timings, the ``serving.*`` counters/gauges/histograms feed
the metrics registry, and ``python -m paddle_tpu.observability report``
renders a serving section (request rate, queue depth, latency/TTFT
percentiles, prefix-hit rate, fused depth, token-gap percentiles) from the
event stream.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..framework.flags import flag

__all__ = ["Request", "ContinuousBatchingScheduler"]


class Request:
    """One in-flight generation request and its lifecycle timestamps.

    ``status`` walks ``queued → prefilling → running → finished``, or ends
    at ``cancelled`` / ``deadline_exceeded`` when :meth:`ContinuousBatching\
Scheduler.cancel` (or the per-tick deadline sweep) reclaims it mid-flight.
    """

    def __init__(self, rid: int, prompt: np.ndarray, max_new_tokens: int,
                 eos_token_id: Optional[int], seed: int,
                 deadline_s: Optional[float] = None,
                 trace_id: Optional[str] = None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.seed = int(seed)
        self.deadline_s = float(deadline_s) if deadline_s is not None else None
        self.trace_id = trace_id      # one id across every process/replica
        self.status = "queued"
        self.tokens: List[int] = []
        self.slot: Optional[int] = None
        self.bucket: Optional[int] = None
        self.prefix_tokens = 0        # prompt rows supplied by the prefix cache
        self.prefill_chunks = 0       # model dispatches its prefill took
        self.submitted_ts = time.perf_counter()
        self.admitted_ts: Optional[float] = None
        self.first_token_ts: Optional[float] = None
        self.finished_ts: Optional[float] = None
        # the token gap, on time.perf_counter_ns() (0 while FLAGS_monitor is off): when the newest token reached the
        # host (the first: the clock read of ``first_token_ts``; a later one: the end of the engine's pull), and the
        # longest time between two consecutive tokens so far; and the engine's count of prefill programs with the one
        # that sampled the first token: programs dispatched after it ran inside the gap to the second token
        self.last_token_ns = 0
        self.max_gap_ns = 0
        self.prefilled_at = 0

    # -- derived timings (None until the request reaches that phase) -------
    @property
    def queue_seconds(self):
        return None if self.admitted_ts is None else self.admitted_ts - self.submitted_ts

    @property
    def ttft_seconds(self):
        return None if self.first_token_ts is None else self.first_token_ts - self.submitted_ts

    @property
    def prefill_seconds(self):
        if self.admitted_ts is None or self.first_token_ts is None:
            return None
        return self.first_token_ts - self.admitted_ts

    @property
    def decode_seconds(self):
        if self.finished_ts is None or self.first_token_ts is None:
            return None
        return self.finished_ts - self.first_token_ts

    @property
    def total_seconds(self):
        return None if self.finished_ts is None else self.finished_ts - self.submitted_ts

    @property
    def max_gap_seconds(self):
        """The longest gap between two consecutive tokens the request saw
        (None before its second token, or where nothing stamped them)."""
        return self.max_gap_ns / 1e9 if self.max_gap_ns else None

    def deadline_expired(self, now: Optional[float] = None) -> bool:
        """True when the request carries a deadline and it has passed."""
        if self.deadline_s is None:
            return False
        now = time.perf_counter() if now is None else now
        return now - self.submitted_ts > self.deadline_s

    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens, the served completion."""
        return np.concatenate([self.prompt, np.asarray(self.tokens, np.int32)])


class ContinuousBatchingScheduler:
    """Admit-into-free-slots scheduler: FIFO queue in front of the engine's
    batch slots. Drive it with :meth:`step` (one admission sweep + at most
    one prefill dispatch per in-flight admission + one decode dispatch) or
    :meth:`run` (until drained)."""

    def __init__(self, engine, keep_finished: int = 256):
        if keep_finished < 1:
            raise ValueError(f"keep_finished must be >= 1, got {keep_finished}")
        self.engine = engine
        self.keep_finished = int(keep_finished)
        self.queue: deque = deque()
        self.prefilling: Dict[int, Request] = {}  # slot -> mid-prefill request
        self._jobs: Dict[int, object] = {}        # slot -> engine _PrefillJob
        self.running: Dict[int, Request] = {}     # slot -> decoding request
        # terminal ledgers: delivered requests are GC'd past keep-last-k
        # (insertion order = completion order) so a long-lived serving loop
        # doesn't accrete per-request host state forever. In-flight requests
        # are never evicted — exactly-once delivery happens through the
        # step() return value before its tick's GC can touch an entry.
        self.finished: Dict[int, Request] = {}    # rid -> request
        self.cancelled: Dict[int, Request] = {}   # rid -> cancelled/expired
        self._next_rid = 0
        # of the last pull: when its tokens reached the host, on perf_counter_ns (-1: none stamped yet), and the engine's
        # count of prefill programs at the launch of its step
        self._arrived_ns = -1
        self._pulled_programs = 0

    # ----------------------------------------------------------- lifecycle
    def submit(self, prompt, max_new_tokens: int = 16, eos_token_id: Optional[int] = None,
               seed: int = 0, deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None) -> int:
        """Enqueue one prompt; returns the request id. Validation happens
        here (not at admission) so a bad request fails its caller, not the
        serving loop. ``deadline_s`` bounds the request's TOTAL time from
        submission: a request still queued, prefilling, or decoding when it
        expires is reclaimed on the next tick with status
        ``deadline_exceeded`` (its slot frees mid-decode — no drain wait).
        ``trace_id`` links this request to an existing distributed trace
        (the fleet passes its id down so submit→admit→prefill→decode→finish
        all correlate); without one a fresh id is allocated when tracing is
        enabled."""
        from ..observability import runlog as _runlog
        from ..observability import trace as _trace
        from ..observability.metrics import counter_inc, gauge_set

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n + int(max_new_tokens) > self.engine.max_seq_len:
            raise ValueError(f"prompt {n} + max_new_tokens {max_new_tokens} exceeds "
                             f"engine max_seq_len {self.engine.max_seq_len}")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.engine.bucket_for(n)  # raises if no bucket/chunk tiling fits
        if trace_id is None:
            trace_id = _trace.new_trace_id("serving")
        r = Request(self._next_rid, prompt, max_new_tokens, eos_token_id, seed,
                    deadline_s=deadline_s, trace_id=trace_id)
        self._next_rid += 1
        self.queue.append(r)
        counter_inc("serving.requests_submitted")
        gauge_set("serving.queue_depth", len(self.queue))
        _runlog.emit("request", id=r.rid, status="submitted", component="serving",
                     prompt_tokens=n, max_new_tokens=int(max_new_tokens),
                     queue_depth=len(self.queue), trace=r.trace_id)
        return r.rid

    def cancel(self, rid: int, status: str = "cancelled") -> bool:
        """Cancel one in-flight request wherever it is: still queued, mid-
        prefill, or mid-decode (its slot frees immediately — the next
        admission reuses it; write-before-attend cache hygiene makes the
        abandoned KV rows harmless). Emits a ``request`` run-log event with
        ``status`` (``cancelled``, or ``deadline_exceeded`` from the deadline
        sweep) and returns True; False when ``rid`` isn't in flight (already
        finished, cancelled, or never submitted)."""
        from ..observability import runlog as _runlog
        from ..observability.metrics import counter_inc, gauge_set

        r = None
        for q in self.queue:
            if q.rid == rid:
                r = q
                self.queue.remove(q)  # noqa: PTA104 (host-side serving loop, never traced)
                gauge_set("serving.queue_depth", len(self.queue))
                break
        if r is None:
            for slot, cand in list(self.prefilling.items()):  # noqa: PTA102 (host-side serving loop, never traced)
                if cand.rid == rid:
                    r = cand
                    del self.prefilling[slot], self._jobs[slot]
                    self.engine.free_slot(slot)
                    break
        if r is None:
            for slot, cand in list(self.running.items()):  # noqa: PTA102 (host-side serving loop, never traced)
                if cand.rid == rid:
                    r = cand
                    del self.running[slot]
                    self.engine.free_slot(slot)
                    break
        if r is None:
            return False
        r.status = status
        r.finished_ts = time.perf_counter()
        self.cancelled[rid] = r
        counter_inc("serving.deadline_exceeded" if status == "deadline_exceeded"
                    else "serving.requests_cancelled")
        gauge_set("serving.active_slots", len(self.running))
        _runlog.emit("request", id=rid, status=status, component="serving",
                     prompt_tokens=len(r.prompt), new_tokens=len(r.tokens),
                     seconds=r.finished_ts - r.submitted_ts,
                     deadline_s=r.deadline_s, trace=r.trace_id)
        return True

    def find(self, rid: int):
        """The in-flight :class:`Request` with id ``rid`` wherever it is
        (queued, prefilling, or decoding), else None — the live-progress
        view a streaming front end polls without touching slot state."""
        for q in self.queue:
            if q.rid == rid:
                return q  # noqa: PTA101 (host-side serving transport, never traced)
        for cand in self.prefilling.values():
            if cand.rid == rid:
                return cand  # noqa: PTA101 (host-side serving transport, never traced)
        for cand in self.running.values():
            if cand.rid == rid:
                return cand  # noqa: PTA101 (host-side serving transport, never traced)
        return None

    def _expire_deadlines(self) -> None:
        """Reclaim every in-flight request whose deadline has passed (one
        sweep per tick: queued, prefilling, and decoding alike)."""
        now = time.perf_counter()
        expired = [r.rid for r in list(self.queue) if r.deadline_expired(now)]
        expired += [r.rid for r in list(self.prefilling.values())
                    if r.deadline_expired(now)]
        expired += [r.rid for r in list(self.running.values())
                    if r.deadline_expired(now)]
        for rid in expired:
            self.cancel(rid, status="deadline_exceeded")

    def _admit(self) -> None:
        """Claim free slots for queued requests (prefix-cache inserts happen
        here — cheap copy dispatches, no model compute). The model prefill
        dispatches are driven chunk-at-a-time by :meth:`_prefill_tick`."""
        from ..observability.metrics import gauge_set

        free = self.engine.free_slots()
        while self.queue and free:
            r = self.queue.popleft()
            slot = free.pop(0)
            r.slot = slot  # noqa: PTA104 (host-side serving loop)
            r.bucket = self.engine.bucket_for(len(r.prompt))  # noqa: PTA104 (host-side serving loop)
            r.status = "prefilling"  # noqa: PTA104 (host-side serving loop, never traced)
            r.admitted_ts = time.perf_counter()  # noqa: PTA104 (host-side serving loop)
            job = self.engine.begin_prefill(
                r.prompt, slot, max_new_tokens=r.max_new_tokens,
                eos_token_id=r.eos_token_id, seed=r.seed)
            r.prefix_tokens = job.reused_tokens  # noqa: PTA104 (host-side serving loop)
            self.prefilling[slot] = r  # noqa: PTA104 (host-side serving loop)
            self._jobs[slot] = job  # noqa: PTA104 (host-side serving loop)
            gauge_set("serving.queue_depth", len(self.queue))

    def _prefill_tick(self) -> None:
        """ONE prefill dispatch per mid-prefill admission: in chunked mode a
        C-token chunk, in bucketed mode the whole padded prompt. Decode runs
        between ticks, so a long admission interleaves instead of holding
        the stream."""
        from ..observability import runlog as _runlog
        from ..observability import trace as _trace
        from ..observability.metrics import counter_inc, gauge_set, observe

        for slot in list(self.prefilling):
            r = self.prefilling[slot]
            job = self._jobs[slot]
            # the last chunk ran a tick ago and left its first token on the
            # device (a decode step was in flight): this call only pulls it
            pull_only = job.pending is not None
            t0 = time.perf_counter()
            done = self.engine.prefill_step(job)
            dt = time.perf_counter() - t0
            if not pull_only:
                r.prefill_chunks += 1  # noqa: PTA104 (host-side serving loop)
                if r.trace_id is not None:
                    _trace.span_event("serving.prefill_chunk", trace_id=r.trace_id,
                                      seconds=dt, id=r.rid, slot=slot,
                                      chunk=r.prefill_chunks, done=job.pending is not None or bool(done))
            if not done:
                continue
            now_ns = time.perf_counter_ns()
            r.first_token_ts = now_ns / 1e9  # noqa: PTA104 (host-side serving loop)
            if flag("FLAGS_monitor"):
                r.last_token_ns = now_ns  # noqa: PTA104 (host-side serving loop)
                r.prefilled_at = getattr(job, "programs", 0)  # noqa: PTA104 (host-side serving loop)
            r.tokens.append(job.first)  # noqa: PTA104 (host-side serving loop)
            del self.prefilling[slot], self._jobs[slot]
            counter_inc("serving.requests_admitted")
            observe("serving.ttft_seconds", r.ttft_seconds)
            observe("serving.queue_seconds", r.queue_seconds)
            gauge_set("serving.active_slots", len(self.running) + 1)
            _runlog.emit("request", id=r.rid, status="admitted", component="serving",
                         slot=slot, bucket=r.bucket, queue_depth=len(self.queue),
                         queue_seconds=r.queue_seconds, seconds=r.prefill_seconds,
                         prefix_tokens=r.prefix_tokens, chunks=r.prefill_chunks, trace=r.trace_id)
            if job.more:
                r.status = "running"  # noqa: PTA104 (host-side serving loop, never traced)
                self.running[slot] = r  # noqa: PTA104 (host-side serving loop)
            else:
                self._finish(r)

    def _finish(self, r: Request) -> None:
        from ..observability import runlog as _runlog
        from ..observability.metrics import counter_inc, gauge_set, observe

        r.status = "finished"
        r.finished_ts = time.perf_counter()
        self.engine.free_slot(r.slot)
        self.running.pop(r.slot, None)
        self.finished[r.rid] = r
        counter_inc("serving.requests_completed")
        counter_inc("serving.tokens_generated", len(r.tokens))
        observe("serving.latency_seconds", r.total_seconds)
        gauge_set("serving.active_slots", len(self.running))
        extra = {}
        if r.max_gap_ns:
            extra["max_gap_seconds"] = r.max_gap_seconds  # noqa: PTA104 (host-side serving loop)
        if getattr(self.engine, "spec_k", 0):
            stats = self.engine.spec_stats()
            extra["spec_k"] = stats["spec_k"]  # noqa: PTA104 (host-side serving loop)
            extra["spec_acceptance"] = stats["acceptance_rate"]  # noqa: PTA104 (host-side serving loop)
        _runlog.emit("request", id=r.rid, status="finished", component="serving",
                     prompt_tokens=len(r.prompt), new_tokens=len(r.tokens),
                     queue_seconds=r.queue_seconds, prefill_seconds=r.prefill_seconds,
                     decode_seconds=r.decode_seconds, total_seconds=r.total_seconds,
                     ttft_seconds=r.ttft_seconds, fuse=self.engine.fuse,
                     prefix_tokens=r.prefix_tokens,
                     kv_bytes_per_slot=getattr(
                         self.engine, "kv_bytes_per_slot", lambda: 0)(),
                     trace=r.trace_id, **extra)

    def step(self) -> List[Request]:
        """One scheduler tick: admit queued requests into free slots, run
        one prefill dispatch per mid-prefill admission, then advance every
        decoding slot in a single decode dispatch (a ``[D, B]`` token stack
        at fuse depth D, drained in order). Returns requests finished this
        tick.

        **When a token reaches the host.** The tick asks the engine to run
        one step ahead (``decode_step(ahead=True)``): it launches this
        tick's decode step and drains the *previous* tick's tokens, so the
        device works on the next step while the host drains, logs and
        admits. A request's first token comes from its prefill, in the tick
        that finishes it — or, when a decode step was in flight as the
        last chunk was launched, at the start of the next tick: the engine
        leaves it on the device rather than wait for everything queued
        before it (:meth:`DecodeEngine.prefill_step`) — and every later one
        arrives one tick after the tick that launched its step. A request finishes, and frees its slot, in
        the tick its last token arrives (the step launched meanwhile already
        sits that slot out, in-graph), and stays in ``running`` until then,
        so :meth:`run` and the fleet's loop keep ticking until it is
        delivered. Tokens are appended by slot: the engine reports a slot as
        emitted only if nobody freed it or was admitted into it since the
        step's launch, so a request cancelled or expired while its step was
        in flight, and a new request admitted to its slot in the same tick,
        never see the late token. An engine with a draft model or a fuse
        depth over 1 declines and steps synchronously, as before.

        The tick is one ``infer.sched.step`` span with a child per phase:
        ``infer.sched.admit`` (deadline sweep, slot claim, prefix inserts),
        ``infer.sched.prefill``, the engine's ``infer.decode_step`` (launch
        and token pull) and ``infer.sched.drain`` (token appends, finishes,
        ledger GC, SLO hook; ``slots`` = slots that decoded). A request's
        path through the decode phase is these tick spans between its first
        token and its finish.

        **The token gap.** A token that the drain appends arrived when the
        engine's pull ended (``engine.arrived_ns``: the end of its
        ``infer.decode_sync``, no clock read of the scheduler's), and its
        gap is that instant less the request's last (``Request.
        last_token_ns``; the first token's is the clock read of
        ``first_token_ts``). Slots that decoded in the tick before share one
        gap and a slot whose last token was its first has its own, so a tick
        has a handful of distinct gaps; tokens that one pull brings together
        (a ``fuse`` > 1 stack, a draft's accepted run) are 0 apart. With
        each gap goes the number of prefill programs the device ran inside
        it, from the engine's running count (the device runs one stream in
        launch order): for the shared gap what the engine dispatched between
        the launches of the two pulled steps (``engine.pulled_at`` then and
        now), and for a request's first gap what it dispatched after the
        program that sampled the first token (its own prefill ran before the
        gap began). Once a tick each distinct gap goes to
        ``serving.itl_seconds`` with its count, and the tick's span record is
        noted with ``gaps`` = the distinct ``[gap_ns, count, chunks inside]``.
        With FLAGS_monitor off nothing is stamped, observed or noted: the two
        integer comparisons a token that the drain then still makes find
        nothing to do."""
        from ..observability import slo as _slo
        from ..observability import span as _span
        from ..observability.metrics import observe

        engine = self.engine
        monitored = flag("FLAGS_monitor")
        with _span("infer.sched.step") as tick:
            before = set(self.finished)
            before_cancelled = set(self.cancelled)
            with _span("infer.sched.admit"):
                self._expire_deadlines()
                self._admit()
            with _span("infer.sched.prefill"):
                self._prefill_tick()
            decoded = len(self.running)
            if decoded:
                toks, emitted, active = engine.decode_step(ahead=True)
            gaps = {}           # (gap in ns, prefill programs the device ran inside it) -> tokens of this tick with that gap
            with _span("infer.sched.drain", slots=decoded):
                if decoded:
                    # when these tokens reached the host: 0 where nothing stamps (FLAGS_monitor off, an engine without the
                    # field); and the engine's count of prefill programs as it launched the step they are of
                    arrived = getattr(engine, "arrived_ns", 0) if monitored else 0
                    pulled = getattr(engine, "pulled_at", 0)
                    # a slot that decoded in the pull before too was stamped with that pull's arrival: all such share one
                    # gap, counted here and taken once; only a slot whose last token was its first has a gap of its own
                    since, shared = self._arrived_ns, 0
                    common = arrived - since
                    for tok_row, emitted_row in zip(np.atleast_2d(toks).tolist(), np.atleast_2d(emitted).tolist()):
                        for slot, r in self.running.items():  # noqa: PTA102 (host-side serving loop)
                            if emitted_row[slot]:
                                r.tokens.append(tok_row[slot])  # noqa: PTA104 (host-side serving loop)
                                last = r.last_token_ns
                                if last == since:
                                    shared += 1
                                    r.last_token_ns = arrived  # noqa: PTA104 (host-side serving loop)
                                    if common > r.max_gap_ns:
                                        r.max_gap_ns = common  # noqa: PTA104 (host-side serving loop)
                                elif arrived and last:
                                    gap = arrived - last
                                    r.last_token_ns = arrived  # noqa: PTA104 (host-side serving loop)
                                    if gap > r.max_gap_ns:
                                        r.max_gap_ns = gap  # noqa: PTA104 (host-side serving loop)
                                    # since its first token: what was dispatched after its last prefill program (a gap of 0:
                                    # a later token of one pull's stack, which nothing ran before)
                                    own = (gap, pulled - r.prefilled_at if gap else 0)
                                    gaps[own] = gaps.get(own, 0) + 1  # noqa: PTA104 (host-side serving loop)
                    if arrived:
                        if shared:      # between the two pulls: what the engine queued between their steps' launches
                            both = (common, pulled - self._pulled_programs)
                            gaps[both] = gaps.get(both, 0) + shared  # noqa: PTA104 (host-side serving loop)
                        self._arrived_ns, self._pulled_programs = arrived, pulled  # noqa: PTA104 (host-side serving loop)
                    for (gap, _), n in gaps.items():  # noqa: PTA102 (host-side serving loop)
                        observe("serving.itl_seconds", gap / 1e9, n)
                    for slot, r in list(self.running.items()):  # noqa: PTA102 (host-side serving loop)
                        if not active[slot]:
                            self._finish(r)
                done = [self.finished[rid] for rid in self.finished if rid not in before]
                fresh = ({r.rid for r in done}
                         | {rid for rid in self.cancelled if rid not in before_cancelled})
                self._gc_ledgers(protect=fresh)
                # judgment layer: cadence-gated host-side evaluate — a single
                # flag check per tick until FLAGS_slo (or an explicit install)
                # arms it
                _slo.on_tick()
            if monitored:
                tick.note(gaps=[[gap, gaps[gap, inside], inside] for gap, inside in sorted(gaps)])
        return done

    def _gc_ledgers(self, protect=()) -> None:
        """Keep-last-k GC of the terminal ledgers: evict the OLDEST entries
        past ``keep_finished`` (dict insertion order is completion order).
        ``protect`` holds THIS tick's rids — never evicted, so the caller of
        :meth:`step` (the fleet's harvest) always sees them, even when a
        mass deadline expiry terminates more than k requests in one tick."""
        protect = set(protect)
        overflow = len(self.finished) - self.keep_finished
        for rid in [r for r in self.finished
                    if r not in protect][:max(0, overflow)]:
            del self.finished[rid]
        overflow = len(self.cancelled) - self.keep_finished
        for rid in [r for r in self.cancelled
                    if r not in protect][:max(0, overflow)]:
            del self.cancelled[rid]

    def run(self, max_steps: Optional[int] = None) -> Dict[int, Request]:
        """Drive :meth:`step` until queue and slots drain (or ``max_steps``
        ticks); returns ``{rid: Request}`` for everything finished during
        the run — accumulated across ticks, so completions the keep-last-k
        ledger GC has since evicted are still returned."""
        done: Dict[int, Request] = dict(self.finished)
        steps = 0
        while self.queue or self.prefilling or self.running:
            for r in self.step():
                done[r.rid] = r  # noqa: PTA104 (host-side serving loop)
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        done.update(self.finished)
        return done

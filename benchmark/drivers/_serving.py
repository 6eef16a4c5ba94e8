"""What the two serving drivers share: building the served system from a
configuration, drawing lengths, and the tick loop with its per-tick log.

The system under test is the program's own path, in this process (a chip
belongs to one process): ``ServingFleet(replicas=1)`` -> ``EngineReplica`` ->
``ContinuousBatchingScheduler`` -> ``DecodeEngine``. The benchmark's clocks sit
around the calls into each layer: around ``fleet.step()`` (a tick), and, by
wrapping the engine's bound methods, around ``begin_prefill``/``prefill_step``
(admission and prefill) and ``decode_step`` (which returns after its tokens
are on the host, so its time is synced).

A token's time is the host clock when the ``fleet.step()`` that produced it
returned; a request's first token carries the scheduler's own
``first_token_ts`` (set right after the prefill that produced it).
"""
from __future__ import annotations

import gc
import time
from typing import List, Optional

import numpy as np

from benchmark.harness import trace as _trace
from benchmark.harness.records import Records, RequestRecord

clock = time.perf_counter


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths from a log-normal with the given median and sigma,
    clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"length distribution {spec['dist']!r}")
    x = rng.lognormal(mean=np.log(spec["median"]), sigma=spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def fit_total(prompt: np.ndarray, output: np.ndarray, max_total: int):
    """Cut outputs so that prompt + output stays inside the context."""
    return prompt, np.minimum(output, max_total - prompt)


class EngineClocks:
    """The benchmark's clocks around the engine's entry points."""

    def __init__(self, engine):
        self.decode_s = self.prefill_s = 0.0
        self.decode_tokens = self.admitted = self.prefill_dispatches = 0
        decode, begin, step = engine.decode_step, engine.begin_prefill, engine.prefill_step

        def decode_step(*a, **k):
            t0 = clock()
            out = decode(*a, **k)
            dt = clock() - t0
            self.decode_s += dt
            self.decode_tokens += int(np.sum(out[1]))
            return out

        def begin_prefill(*a, **k):
            t0 = clock()
            out = begin(*a, **k)
            self.prefill_s += clock() - t0
            self.admitted += 1
            return out

        def prefill_step(*a, **k):
            t0 = clock()
            out = step(*a, **k)
            self.prefill_s += clock() - t0
            self.prefill_dispatches += 1
            return out

        engine.decode_step, engine.begin_prefill, engine.prefill_step = decode_step, begin_prefill, prefill_step

    def take(self):
        out = (self.decode_s, self.prefill_s, self.decode_tokens, self.admitted, self.prefill_dispatches)
        self.decode_s = self.prefill_s = 0.0
        self.decode_tokens = self.admitted = self.prefill_dispatches = 0
        return out


class GcClock:
    """Seconds the interpreter's cyclic collector ran, by ``gc.callbacks``."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = clock()
        else:
            self.seconds += clock() - self._t0

    def take(self):
        s, self.seconds = self.seconds, 0.0
        return s

    def close(self):
        gc.callbacks.remove(self._on)


def program_builds() -> int:
    """Programs the engine has compiled or loaded so far (its own counters)."""
    from paddle_tpu.observability import metrics

    c = metrics.counters("infer.")
    return int(c.get("infer.compiles", 0) + c.get("infer.aot_cache_hits", 0))


class Served:
    """The served system and the tick loop's log."""

    def __init__(self, records: Records, devices):
        from paddle_tpu.inference import ServingFleet

        cfg, serving = records.cell.config, records.cell.config["serving"]
        model = records.cell.family.build_model(cfg, records.seed, serving["dtype"])
        model.eval()
        self.records = records
        self.fleet = ServingFleet(
            model, replicas=int(serving["replicas"]), max_queue_depth=int(serving["max_queue_depth"]),
            max_batch_slots=int(serving["slots"]), max_seq_len=int(serving["context"]),
            prefill_chunk=int(serving["prefill_chunk"]), fuse=int(serving["fuse"]),
            prefix_cache_mb=float(serving["prefix_cache_mb"]), device=devices[0])
        self.replica = next(iter(self.fleet.replicas.values()))
        self.scheduler, self.engine = self.replica.scheduler, self.replica.engine
        self.clocks = EngineClocks(self.engine)
        self.gc = GcClock()
        self.live: List[list] = []       # [RequestRecord, the scheduler's Request, tokens seen]
        self.last_end = clock()
        self.trace_on = False
        self.refused = 0
        records.slots, records.context = int(serving["slots"]), int(serving["context"])

    # ------------------------------------------------------------ requests
    def submit(self, prompt: np.ndarray, output_tokens: int, due: float, in_window: bool = True,
               seed: int = 0) -> Optional[list]:
        """Send one request now. Returns its live entry, or None if the fleet
        refused it (which counts as failed)."""
        from paddle_tpu.inference.fleet import FleetOverloadError

        rec = RequestRecord(due=due, submitted=clock(), prompt_tokens=len(prompt),
                            output_tokens=int(output_tokens), in_window=in_window)
        self.records.requests.append(rec)
        try:
            self.fleet.submit(prompt, max_new_tokens=int(output_tokens), seed=seed)
        except FleetOverloadError:
            self.refused += 1
            return None
        entry = [rec, self.scheduler.queue[-1], 0]
        self.live.append(entry)
        return entry

    def busy(self) -> bool:
        s = self.scheduler
        return bool(s.queue or s.prefilling or s.running)

    def cancel_all(self):
        for fid, freq in list(self.fleet.requests.items()):
            if freq.status not in self.fleet._TERMINAL:
                self.fleet.cancel(fid)
        self.live.clear()

    # ---------------------------------------------------------------- tick
    def tick(self):
        """One ``fleet.step()`` and its line in the log."""
        r = self.records
        t0 = clock()
        with _trace.annotate("bench.tick", self.trace_on):
            self.fleet.step()
        t1 = clock()
        with _trace.annotate("bench.log", self.trace_on):
            tokens, rows, still = 0, 0, []
            for entry in self.live:
                rec, req, seen = entry
                n = len(req.tokens)
                if n > seen:
                    if seen == 0:
                        rec.first_token = req.first_token_ts
                        rec.queue_s = req.queue_seconds
                        rec.token_times.append(req.first_token_ts)
                        seen = 1
                    rec.token_times.extend([t1] * (n - seen))
                    tokens += n - entry[2]
                    entry[2] = n
                if req.status == "running":
                    rows += rec.prompt_tokens + n
                if req.status in ("queued", "prefilling", "running"):
                    still.append(entry)
                else:
                    rec.finished = req.status == "finished"
            self.live = still
            decode_s, prefill_s, decode_tokens, admitted, dispatches = self.clocks.take()
            r.tick_end.append(t1)
            r.tick_tokens.append(tokens)
            r.tick_decoding.append(decode_tokens)
            r.tick_live_rows.append(rows)
            r.tick_admitted.append(admitted)
            r.tick_prefill_dispatches.append(dispatches)
            r.tick_parts.append({"decode_s": decode_s, "prefill_s": prefill_s,
                                 "scheduler_s": (t1 - t0) - decode_s - prefill_s,
                                 "between_ticks_s": t0 - self.last_end, "python_gc_s": self.gc.take()})
            self.last_end = t1
        return t1


def finish(served: Served, records: Records, builds_at_open: int):
    """After the window: what the program counted, the lumps, the reference."""
    from benchmark.harness import stats

    records.compiles_in_window = program_builds() - builds_at_open
    served.cancel_all()
    served.gc.close()
    inside = records.inside(records.tick_end)
    records.notes.update({
        "window_ticks": len(inside),
        "window_tokens": int(sum(records.tick_tokens[i] for i in inside)),
        "window_admissions": int(sum(records.tick_admitted[i] for i in inside)),
        "window_prefill_dispatches": int(sum(records.tick_prefill_dispatches[i] for i in inside)),
        "window_python_gc_s": float(sum(records.tick_parts[i]["python_gc_s"] for i in inside)),
        "longest_tick_gaps": stats.longest_gaps(
            [records.tick_end[i] for i in inside], [records.tick_parts[i] for i in inside], 10) if len(inside) > 1 else [],
        "admission_ticks": [[i - inside[0], records.tick_admitted[i]] for i in inside if records.tick_admitted[i]][:64]
        if inside else [],
    })
    t0 = clock()
    records.check = records.cell.family.check_serving(served.engine, records.cell.config, records.seed)
    records.check["seconds"] = clock() - t0

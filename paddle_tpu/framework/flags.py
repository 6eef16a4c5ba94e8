"""Global flag registry.

Paddle parity: ``PADDLE_DEFINE_EXPORTED_*`` gflags exposed to Python via
``paddle.set_flags``/``get_flags`` (reference: paddle/fluid/platform/flags.cc,
paddle/fluid/pybind/global_value_getter_setter.cc). Flags are overridable from
the environment (``FLAGS_*``) just like the reference.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}
# side-effecting flags: callback fired when the value is defined (import) or
# changed via set_flags — e.g. FLAGS_compile_cache_dir pushing jax.config
_ON_SET: Dict[str, Any] = {}


def on_flag_set(name: str, callback):
    """Register ``callback(value)`` to run now (with the current value) and
    on every subsequent ``set_flags`` of ``name``."""
    _ON_SET[name] = callback
    callback(_REGISTRY[name])


def define_flag(name: str, default, help_str: str = ""):
    env = os.environ.get(name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes", "on")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _REGISTRY[name] = value
    return value


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if k not in _REGISTRY:
            raise KeyError(f"unknown flag {k!r}")
        _REGISTRY[k] = v
        if k in _ON_SET:
            _ON_SET[k](v)


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    return {k: _REGISTRY[k] for k in flags}


def flag(name: str):
    return _REGISTRY[name]


# Core flags (subset of reference platform/flags.cc relevant on TPU).
define_flag("FLAGS_check_nan_inf", False, "check outputs for nan/inf after each eager op")
define_flag("FLAGS_benchmark", False, "synchronize after each op for timing")
define_flag("FLAGS_use_flash_attention", True, "use the Pallas flash-attention kernel when on TPU")
define_flag("FLAGS_flash_flat", False, "use the flat-lane (zero-relayout) flash kernels for packed qkv attention; not timed on the chip against the classic pair (ROADMAP D2), so it stays opt-in")
define_flag("FLAGS_kernel_overrides", "", "force kernel-registry implementations per kernel, e.g. 'moe=dense,sdpa=xla' (see paddle_tpu.ops.registry); forced impls bypass availability predicates; unknown impl names raise at dispatch")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "compat no-op: XLA/PJRT manages buffers")
define_flag("FLAGS_allocator_strategy", "auto_growth", "compat no-op: PJRT BFC allocator is used")
define_flag("FLAGS_remat_policy", "none", "default rematerialization policy for jit steps")
define_flag("FLAGS_static_check", False, "run the paddle_tpu.analysis passes over each Program before its first compile in Executor.run; warnings are reported via the warnings module, error-severity diagnostics raise ProgramAnalysisError")
define_flag("FLAGS_executor_donate", False, "Executor.run donates parameter and optimizer-state buffers to the compiled program on training runs (flat param memory; stale outside handles raise StaleHandleError)")
define_flag("FLAGS_shard_check", False, "run the paddle_tpu.analysis.spmd PTA2xx passes over every lowered program once per new specialization (Executor.run, jit.TrainStep, inference.DecodeEngine, auto_parallel.Engine.prepare): implicit all-gathers, spec-mismatch reshards and decode-loop collectives warn with bytes-moved estimates, an HBM-budget overrun (FLAGS_hbm_budget_mb) raises ProgramAnalysisError before dispatch")
define_flag("FLAGS_hbm_budget_mb", 0.0, "per-device memory budget in MiB for the PTA204 pre-flight: a lowered program whose XLA memory_analysis estimate exceeds this raises under FLAGS_shard_check before the first dispatch (0 = unlimited)")
define_flag("FLAGS_compile_cache_dir", "", "persistent XLA compilation cache directory (jax_compilation_cache_dir) and root of the AOT executable store: repeated runs of the same program skip recompiles. When the environment sets JAX_COMPILATION_CACHE_DIR that directory is used and this flag places nothing (see compile_cache_dir())")

#: where entry points (chip_smoke.py, benchmark/run.py, the launcher) keep
#: the cache when nothing outside placed it: one fixed path inside the
#: checkout — the path is part of the cache key, so a directory that moves
#: never hits
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".compile_cache")


def compile_cache_dir() -> str:
    """The compile-cache directory in force: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it (then no code sets another), else
    ``FLAGS_compile_cache_dir``; ``""`` when neither placed one. The AOT
    executable store (``inference.aot_cache``) lives under the same root."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_REGISTRY["FLAGS_compile_cache_dir"] or "")


def ensure_compile_cache() -> str:
    """Entry points call this once: keep the cache where the environment or
    the flag already put it, else at ``DEFAULT_COMPILE_CACHE_DIR``. Returns
    the directory in force."""
    if not compile_cache_dir():
        set_flags({"FLAGS_compile_cache_dir": DEFAULT_COMPILE_CACHE_DIR})
    return compile_cache_dir()


def _apply_compile_cache_dir(path):
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not (path or from_env):
        return
    import jax

    if not from_env:  # jax.config already holds the environment's directory
        jax.config.update("jax_compilation_cache_dir", str(path))
    # cache every hit: the default 1s floor would skip exactly the small
    # specializations an Executor compiles dozens of
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


on_flag_set("FLAGS_compile_cache_dir", _apply_compile_cache_dir)

# Dispatch-hygiene runtime sanitizer (paddle_tpu/analysis/sanitizer.py).
define_flag("FLAGS_sanitize", False, "runtime dispatch sanitizer: jax.transfer_guard('disallow') scoped around every hot-path dispatch (TrainStep, Executor.run, DecodeEngine — implicit device<->host transfers raise with the offending op named), a recompile-churn sentinel at every _dispatch site (> FLAGS_sanitize_max_recompiles signatures per logical callsite => RecompileChurnError naming the diffing aval), donated-state poisoning (reusing a donated TrainStep/DecodeEngine state leaf raises a structured StaleStateError instead of an XLA deleted-buffer crash), and a host-ledger growth sentinel on the serving-fleet tick")
define_flag("FLAGS_sanitize_max_recompiles", 8, "recompile-churn threshold: one logical dispatch callsite compiling more than this many distinct signatures trips the sentinel (warn by default, raise under FLAGS_sanitize_strict)")
define_flag("FLAGS_sanitize_strict", False, "escalate warn-only sanitizer findings (recompile churn, ledger growth) to raises; transfer-guard and stale-state violations always raise")

# Observability spine (paddle_tpu/observability/).
define_flag("FLAGS_monitor", True, "always-on runtime telemetry: step/compile/checkpoint run-log events, timeline spans and span histograms (spans become no-ops when off)")
define_flag("FLAGS_run_log_dir", "", "directory for the structured run log (JSONL, one run-<pid>.jsonl per process); empty keeps events only in the in-memory ring")
define_flag("FLAGS_run_log_max_mb", 64.0, "size-based run-log rotation: when run-<pid>.jsonl exceeds this many MiB it is renamed to run-<pid>.1.jsonl (replacing any prior rotation) and a fresh file is opened; 0 disables rotation (unbounded growth)")
define_flag("FLAGS_run_log_keep", 16, "keep-last-k GC of stale run logs: when a process opens its run log it deletes dead pids' run-*.jsonl files under FLAGS_run_log_dir beyond the newest k (by mtime); 0 disables the GC")
define_flag("FLAGS_trace", True, "distributed tracing plane (observability/trace.py): deterministic per-request/per-run trace ids propagated through ServingFleet submit->route->prefill->decode->requeue->delivery and run_resilient per-step/per-incident spans, emitted as 'span' run-log events; off allocates no ids and emits no span events")
define_flag("FLAGS_metrics_port", 0, "live metrics export (observability/exporter.py): serve /metrics (Prometheus text), /healthz and /snapshot (JSON) on this localhost port from a stdlib HTTP server started by ServingFleet and run_resilient workers; 0 (default) disables the server")
define_flag("FLAGS_flightrec_events", 256, "crash flight recorder (observability/flightrec.py): dump the last N run-log ring events plus a metrics snapshot to flightrec-<pid>.json on replica death, DivergenceFault, PTA204/205 analysis errors and unhandled dispatch exceptions; 0 disables the recorder")
define_flag("FLAGS_slo", False, "judgment layer (observability/slo.py + regress.py): auto-install the default SLO spec set on the first serving/training tick and evaluate it on the FLAGS_slo_eval_every_s cadence — error budgets, multi-window burn-rate alerts ('alert' run-log events, /alerts, degraded /healthz while a page fires) and the perf-regression sentinel; off keeps every tick-loop hook a single flag check (explicit slo.install() still works)")
define_flag("FLAGS_slo_eval_every_s", 5.0, "SLOMonitor evaluation cadence in seconds: tick-loop hooks (scheduler/fleet/procfleet step, TrainStep.run_steps) evaluate the registered spec set at most this often; evaluation is host-side reads of the lock-free metrics registries — never a device sync")
define_flag("FLAGS_slo_fast_window_s", 300.0, "fast burn-rate window (seconds) for SLO alerting: the page-severity window — a burn rate >= the spec's page_burn sustained over this window pages. ~5 minutes in production; tests shrink it to sub-second")
define_flag("FLAGS_slo_slow_window_s", 3600.0, "slow burn-rate window (seconds) for SLO alerting: the warn-severity window and the second gate of the page condition for ratio SLOs (classic multi-window burn-rate alerting). ~1 hour in production")

# Fault-tolerance runtime (distributed/resilience.py).
define_flag("FLAGS_collective_timeout_s", 0.0, "watchdog: report a cross-process collective still pending after this many seconds (0 = off)")
define_flag("FLAGS_store_retry_jitter", True, "full jitter on the resilience.retry/RetryingStore exponential backoff: attempt i sleeps uniform(0, min(max_delay, base_delay*2**i)) instead of the deterministic cap, so N replicas retrying a dead store spread out instead of thundering-herding. The jitter stream is seeded via framework.random (paddle.seed + PADDLE_TRAINER_ID), so chaos tests replay bitwise; off restores the pre-jitter deterministic sleeps")

# Training-health guard (jit.TrainStep guard / paddle_tpu.stability).
define_flag("FLAGS_train_guard", False, "fuse an all-finite check over loss+grads into every jit.TrainStep program and skip the param/opt/rng update in-graph when it trips (state stays bitwise at its pre-step value); read at TrainStep construction")
define_flag("FLAGS_dataloader_max_bad_batches", 0, "DataLoader: skip up to this many batches whose sample/collate raised (per iteration) instead of killing the iterator; 0 keeps the raise-through behavior")

# Deterministic fault injection (testing/chaos.py). All hooks are no-ops
# unless FLAGS_chaos is on; each knob below selects one failure mode.
define_flag("FLAGS_chaos", False, "master switch for deterministic fault injection")
define_flag("FLAGS_chaos_crash_point", "", "named crash point to fire (e.g. 'checkpoint_save', 'train_step')")
define_flag("FLAGS_chaos_crash_at_step", -1, "step index at which the crash point fires (-1: first hit)")
define_flag("FLAGS_chaos_corrupt_ckpt", False, "flip bytes in the next published checkpoint (on-disk corruption)")
define_flag("FLAGS_chaos_store_drop_ops", "", "comma list of store ops to fail, each 'op' or 'op:key-prefix'")
define_flag("FLAGS_chaos_store_drop_count", -1, "fail only the first N matching store ops, then heal (-1: always)")
define_flag("FLAGS_chaos_store_delay_s", 0.0, "sleep this long before every store op")
define_flag("FLAGS_chaos_freeze_heartbeat", "", "comma list of elastic node ids whose heartbeat stops refreshing")
define_flag("FLAGS_chaos_nan_at_step", -1, "inject non-finite gradients in-graph at this TrainStep step index (fires exactly once; read at TrainStep construction; -1 = off)")
define_flag("FLAGS_chaos_nan_steps", 1, "number of consecutive steps the NaN-gradient injection fires for (default 1)")
define_flag("FLAGS_chaos_replica_kill_at", "", "kill a serving-fleet engine replica mid-stream: 'R:K' kills replica R after its K-th decode tick (fires exactly once per replica per process). Drives the fleet kill/requeue tests")
define_flag("FLAGS_chaos_replica_slow_ms", "", "inject per-tick latency into serving-fleet replicas: 'MS' slows every replica, 'R:MS' only replica R, by MS milliseconds per scheduler tick (a straggler/overloaded host; long enough and the fleet's heartbeat tracking declares it dead)")
define_flag("FLAGS_chaos_replica_sigkill_at", "", "SIGKILL a cross-process serving replica mid-stream: 'R:K' makes the ProcServingFleet parent send SIGKILL to replica R's subprocess after harvesting its K-th tick message (fires exactly once per replica per process). The real-process form of FLAGS_chaos_replica_kill_at — no Python exception, the child just dies")
define_flag("FLAGS_chaos_replica_hang_ms", "", "wedge a cross-process serving replica without exiting: 'MS' (every replica) or 'R:MS' (one) makes the child stop publishing heartbeats for MS milliseconds after its first served tick while the process stays alive (a zombie the parent's stale-beat sweep must catch). Fires exactly once per replica per process")
define_flag("FLAGS_chaos_socket_drop_at", "", "kill the fast-path RPC socket mid-stream: 'R:K' (replica R) or 'K' (any) makes a SocketChannel writer kill its connection right before its K-th socket send (fires exactly once per replica per process). The channel must degrade to the store transport with no chunk lost or duplicated — the socket-fallback chaos pin")
define_flag("FLAGS_chaos_ingress_disconnect_at", -1, "drop the HTTP client connection mid-stream: the ingress force-closes a streaming response socket after writing N chunks (fires exactly once per process; -1 = off). Drives the client-disconnect -> mid-decode cancel() test without a real flaky client")
define_flag("FLAGS_chaos_net_delay_ms", 0.0, "sleep this many milliseconds before every fast-path socket frame send (both directions, both ends) — deterministic WAN latency for the transport-lag backpressure and TTFT-under-latency tests")

"""One process, one cell, one run.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, lets its driver set up, warm the
cell's own shapes, measure for ``--seconds`` and compare with the plain
reference, then prints the result as the last line of standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, the device's busy time and the breakdown. Without a TPU
(or with fewer chips than the cell asks for) it prints no result and exits
non-zero: a CPU time is never printed under a device metric's name.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def compute_metrics(cell, entries, records) -> dict:
    out = {}
    for m in entries:
        value = cell.readers[m["name"]].read(records)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, records, devices, trace_on: bool) -> dict:
    from .harness import device

    entries = cell.per_layer if trace_on else cell.end_to_end
    line = {
        "correct": bool(records.check.get("correct")) and records.compiles_in_window == 0,
        "attempted": int(records.attempted), "failed": int(records.failed),
        "metrics": compute_metrics(cell, entries, records),
        "device": device.describe(devices, records.trace if trace_on else None),
    }
    if trace_on and records.trace is not None:
        line["breakdown"] = {"device_ops": records.trace.top_ops(10),
                             "idle_gaps": records.trace.top_gaps(10)}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from .harness import device, manifest, peaks
    from .harness.records import Records

    root = manifest.repo_root()
    cell = manifest.resolve_cell(manifest.load_manifest(root), args.workload, root)
    import paddle_tpu  # noqa: F401  (a bare benchmark directory fails here, before any output)

    try:
        devices = device.require_tpu(cell.chips)
    except device.NoAccelerator as e:
        device.log(f"benchmark: no accelerator for {cell.name}: {e}")
        return 3
    cache_dir = device.compile_cache_dir(root)
    records = Records(cell=cell, seed=args.seed, seconds=args.seconds, chips=cell.chips, devices=list(devices),
                      peaks=peaks.peaks_for(devices[0].device_kind))
    cell.driver.run(records, devices, process_start=_PROCESS_START, trace_on=bool(args.trace),
                    trace_dir=f"{root}/.bench_trace/{cell.name}")
    print(json.dumps({"cell": cell.name, "seed": args.seed, "seconds": args.seconds,
                      "trace": args.trace, "compile_cache_dir": cache_dir,
                      "setup_s": records.setup_s, "window_s": records.window_close - records.window_open,
                      "compiles_in_window": records.compiles_in_window,
                      "check": records.check, **records.notes}), flush=True)
    print(json.dumps(result_line(cell, records, devices, bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

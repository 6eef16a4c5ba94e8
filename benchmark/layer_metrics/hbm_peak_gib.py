"""The allocator's ``peak_bytes_in_use`` on the fullest chip, after the window
and the comparison with the reference. It counts what the allocator handed out
at once (weights, cache, state, batches); a program's own temporaries are not
in it (PERF.md Findings sets it against the compiler's ``memory_analysis()``)."""
from benchmark.harness import device


def read(records):
    return device.memory_peak_bytes(records.devices) / 2 ** 30 if records.devices else None

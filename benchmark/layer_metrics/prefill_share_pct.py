"""Share of the traced window's device time that went to the engine's prefill
programs (chunk and final chunk). From the device trace, not the host clock:
an intermediate chunk is dispatched without a sync, so a clock around
``prefill_step`` sees its launch and the next ``decode_step`` sees its work."""


def read(records):
    t = records.trace
    if t is None or not t.modules:
        return None
    prefill = sum(sum(d) for name, d in t.modules.items()
                  if any(p in name for p in records.cell.family.PREFILL_PROGRAMS))
    return 100.0 * prefill / (t.window_ns[1] - t.window_ns[0])

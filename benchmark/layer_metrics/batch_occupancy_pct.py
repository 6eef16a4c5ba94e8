"""Slots that decoded a token in a tick over the engine's slots, mean over the
window's ticks."""


def read(records):
    idx = records.inside(records.tick_end)
    if not idx or not records.slots:
        return None
    return 100.0 * sum(records.tick_decoding[i] for i in idx) / (len(idx) * records.slots)

"""Cache rows that hold a live token (prompt and generated tokens of the
requests decoding) over the rows reserved (slots x context), mean over the
window's ticks."""


def read(records):
    idx = records.inside(records.tick_end)
    if not idx or not records.slots:
        return None
    return 100.0 * sum(records.tick_live_rows[i] for i in idx) / (len(idx) * records.slots * records.context)

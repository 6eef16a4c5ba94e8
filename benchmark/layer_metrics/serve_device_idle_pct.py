"""Share of the traced window in which no operation ran on the device."""


def read(records):
    return None if records.trace is None else 100.0 * records.trace.idle_share

"""Share of the traced window's attributed idle time (the device's gaps of
0.1 ms and more) that falls inside one of the program's own serving spans
(``infer.*``), the rest lying in the benchmark's spans or in none. From the
device trace's gap attribution."""


def read(records):
    t = records.trace
    if t is None:
        return None
    attributed = {label: ns for label, ns in t.gap_ns.items() if label != "short_gaps"}
    total = sum(attributed.values())
    if not total:
        return None
    return 100.0 * sum(ns for label, ns in attributed.items() if label.startswith("infer.")) / total

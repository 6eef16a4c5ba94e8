"""Device time inside all-gather / all-reduce / reduce-scatter (and the other
collectives) while no other operation runs on that chip, as a share of the
traced window; mean over the chips."""


def read(records):
    t = records.trace
    if t is None or not t.collective_exposed_ns:
        return None
    exposed = sum(t.collective_exposed_ns.values()) / len(t.collective_exposed_ns)
    return 100.0 * exposed / (t.window_ns[1] - t.window_ns[0])

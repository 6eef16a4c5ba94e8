"""How late the load generator ran: p95 of submit time minus due time. A
starved generator must not be read as a fast server."""
from benchmark.harness import stats


def read(records):
    late = [r.submitted - r.due for r in records.requests if r.in_window]
    p = stats.percentile(late, 95.0)
    return None if p is None else 1e3 * p

"""The 95th percentile of the gaps between consecutive tokens of one request,
over all such gaps of all requests whose later token fell inside the window."""
from benchmark.harness import stats


def read(records):
    p = stats.percentile(records.itl_samples(), 95.0)
    return None if p is None else 1e3 * p

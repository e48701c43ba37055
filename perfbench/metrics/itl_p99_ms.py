"""99th percentile of every gap between consecutive output tokens that ends
in the window."""
from perfbench import stats


def read(run, name):
    p = stats.percentile(stats.token_gaps(run.served, run.w0, run.w1), 99)
    return None if p is None else p * 1e3

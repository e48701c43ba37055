"""90th percentile of time to first token over the requests due in the
window, from scheduled arrival; a request not yet served counts with its
wait so far."""
from perfbench import stats


def read(run, name):
    due = stats.due(run.served, run.w0, run.w1)
    p = stats.percentile(stats.ttfts(due, run.w1), 90)
    return None if p is None else p * 1e3

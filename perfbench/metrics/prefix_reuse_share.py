"""Share of the window's prompt tokens served from cached prefix blocks
(engine counters: reused over reused plus computed)."""


def read(run, name):
    reused = run.counters["reused_prefix_tokens"]
    total = reused + run.counters["prefill_tokens"]
    return 100.0 * reused / total if total else None

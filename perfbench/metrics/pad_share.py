"""Share of the window's step positions that were padding: 1 - real
fine-tune, prefill and decode tokens over the bucket positions they were
padded to (``step_tokens``, ``step_positions`` on each ``serve.assemble``
span in the traced run's trace)."""
from perfbench import progspans


def read(run, name):
    a = (progspans.window(run) or {}).get("attrs", {})
    pos = a.get("serve.assemble:step_positions")
    return (100.0 * (1.0 - a["serve.assemble:step_tokens"] / pos)
            if pos else None)

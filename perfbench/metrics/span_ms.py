"""Mean host milliseconds of one engine span per window tick that ran a
step, from the traced run's trace: ``span_ms.plan.*`` the tick up to its
assembled batch (``serve.plan``), ``.dispatch`` the step call until it
returns (``serve.dispatch``), ``.readback`` the tick's copies of logits
and losses to the host (its ``serve.readback`` spans, summed)."""
from perfbench import progspans


def read(run, name):
    got = progspans.window(run)
    return (got["span_ms"].get("serve." + name.split(".")[1]) if got
            else None)

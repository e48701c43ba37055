"""Share of the traced window in which the device was idle while the
engine did its own host work (``serve.plan`` or ``serve.scatter`` on the
trace's host plane)."""
from perfbench import progspans


def read(run, name):
    if not run.trace:
        return None
    from perfbench import harness
    red = progspans.reduce_dir(harness.TRACE_DIR)
    if red["idle_host_s"] is None or not red["window_s"]:
        return None
    return 100.0 * red["idle_host_s"] / red["window_s"]

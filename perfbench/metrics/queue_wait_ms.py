"""Mean wait from arrival to first admission of the requests the window's
ticks admitted (the ``admissions`` and ``queue_wait_s`` each
``serve.admit`` span carries in the traced run's trace)."""
from perfbench import progspans


def read(run, name):
    a = (progspans.window(run) or {}).get("attrs", {})
    n = a.get("serve.admit:admissions")
    return 1e3 * a["serve.admit:queue_wait_s"] / n if n else None

"""Mean host time of a tick that ran a step: tick wall time less the step
call's (which ends in block_until_ready).  Scheduling, batch assembly,
logits to the host and the scatter of results."""


def read(run, name):
    t = [x.t1 - x.t0 - x.step_s for x in run.ticks if x.kind]
    return 1e3 * sum(t) / len(t) if t else None

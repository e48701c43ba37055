"""Mean wall time of the window's step calls of one kind (``step_ms.grad``:
grad steps; ``step_ms.fwd.*``: forward steps), each ending in
block_until_ready."""


def read(run, name):
    kind = name.split(".")[1]
    t = [x.step_s for x in run.ticks if x.kind == kind]
    return 1e3 * sum(t) / len(t) if t else None

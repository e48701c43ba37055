"""Output tokens emitted in the window over the window."""


def read(run, name):
    n = sum(1 for r in run.served for t in r.token_times
            if run.w0 <= t <= run.w1)
    return n / run.window_s

"""Fine-tune target tokens of the window's steps over the window."""


def read(run, name):
    return run.counters["finetune_tokens"] / run.window_s

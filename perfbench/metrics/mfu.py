"""Model FLOPs of every step in the window (the architecture's
``step_flops``) over the
window times the chips times the chip's bf16 peak."""


def read(run, name):
    flops = sum(x.flops for x in run.ticks)
    peak = run.peaks.get("bf16_flops_per_s")
    if not flops or not peak:
        return None
    return 100.0 * flops / (run.window_s * run.chips * peak)

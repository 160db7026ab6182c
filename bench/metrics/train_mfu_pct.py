"""model step, whole: model FLOPs of every step of the window (the
benchmark's own count, no recomputation) over the window's seconds, over
one chip's bf16 peak."""


def read(run):
    n = run.counters.get("steps")
    if not n:
        return None
    achieved = run.counters["model_flops_per_step"] * n / run.window_s
    return 100.0 * achieved / run.peak.bf16_flops_per_s

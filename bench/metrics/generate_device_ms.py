"""generate: device milliseconds per round of the engine's group program
(`engine_jax._group_device_sim`), from the trace."""


def read(run):
    n = run.counters.get("rounds")
    t = run.trace.program_time("_group_device_sim") if run.trace else 0.0
    return 1e3 * t / n if n and t > 0 else None

"""model step: device milliseconds per step of the train-step program
(`Trainer.step_fn`, jitted `train_step`), from the trace."""


def read(run):
    n = run.counters.get("steps")
    t = run.trace.program_time("train_step") if run.trace else 0.0
    return 1e3 * t / n if n and t > 0 else None

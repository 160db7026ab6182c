"""Run one cell of the benchmark once.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name, warms up every shape
(set-up), measures for --seconds, then checks what the timed path produced
against the plain reference.  With --trace 0 the result carries the cell's
end-to-end metrics; with --trace 1 a profiler trace of the window gives its
per-layer metrics.  The last lines of standard error, and the last key of
the result, are the numbers compared with their limits.  The last line of
standard output is the result: one JSON object.

It runs only on the accelerator the cell asks for: with no TPU, or fewer
chips than the cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from bench import spec  # noqa: E402

#: longest stretch of a --trace 1 window that is traced
TRACE_MAX_S = 20.0


class Window:
    """Start and stop of the measured window: the profiler around it in a
    --trace 1 run, and a count of the programs compiled (or fetched from
    the persistent cache) inside it, which should be none."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax, trace: bool):
        self.jax, self.trace = jax, trace
        self.dir, self.events, self.compiles, self._open = None, None, 0, False

        def listen(name, _secs, **_kw):
            if self._open and name == self.COMPILE_EVENT:
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listen)

    def start(self):
        if self.trace:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._open = True

    def stop(self):
        self._open = False
        if self.trace:
            from bench import trace
            self.jax.profiler.stop_trace()
            self.events = trace.load_xplane(self.dir)
            shutil.rmtree(self.dir, ignore_errors=True)


class Device:
    """The chips this run may use, as JAX reports them."""

    def __init__(self, jax, chips: int):
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise SystemExit(f"bench: needs a TPU; JAX's first device is "
                             f"{devs[0].platform!r} ({devs[0].device_kind})")
        if len(devs) < chips:
            raise SystemExit(f"bench: the cell needs {chips} chips, JAX "
                             f"sees {len(devs)}")
        self.devs = devs[:chips]
        self.platform, self.kind = devs[0].platform, devs[0].device_kind
        self.count = len(devs)

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devs)


def enable_cache(jax) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    every program in it, so only a cell's first run there compiles."""
    path = str(spec.ROOT / ".jax_cache")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(cell, run, device, trace: bool) -> dict:
    """The contract's result object; `compared` comes last."""
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = spec.load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = (run.setup_s if m["name"] == "setup_s"
                     else run.end_to_end[m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.kind,
           "count": device.count,
           "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.gap_labels()}
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in run.compared}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    import jax
    device = Device(jax, cell.chips)
    enable_cache(jax)
    from bench.peaks import peak_for
    peak = peak_for(device.kind)
    window = Window(jax, bool(args.trace))
    seconds = min(args.seconds, TRACE_MAX_S) if args.trace else args.seconds
    driver = spec.load_module("drivers", cell.config["driver"])
    run = driver.run(cell, args.seed, seconds, window, T_START, device)
    run.peak = peak
    if args.trace:
        from bench import trace as tr
        run.trace = tr.reduce(window.events)
    out = result_line(cell, run, device, bool(args.trace))
    print(f"bench: {cell.name} seed {args.seed}: {run.attempted} attempted, "
          f"{window.compiles} programs compiled inside the window, set-up "
          f"{run.setup_s:.3f} s, window {run.window_s:.3f} s",
          file=sys.stderr)
    for c in run.compared:
        print(f"compared {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""TPU-side acquisition: `TpuProfilerBackend` over a duty-cycle/clock
transport shim.

TPUs expose the same two-signal story as DCGM GPUs — a hardware
tensorcore duty-cycle metric (libtpu's `tensorcore_utilization` /
megacore duty cycle, the TPU analogue of PIPE_TENSOR_ACTIVE) and a
power-management clock stream — so the backend is the same shape:
§IV-C window policy, staleness tracking, reconnect-with-backoff, all
shared via `ResilientBackendMixin`.  Only the transport differs:
`TpuTransport.read(device)` returns one `(duty, clock_mhz, t_s)`
triple instead of DCGM field ids.

`LibtpuTransport` is the hardware wiring point, gated on libtpu being
importable; CI runs the engine-driven `fake.FakeTpuTransport` through
the identical backend code path, so the deploy path is exercised end to
end minus the final syscall.
"""
from __future__ import annotations

import time

from repro.telemetry.backends.transport import (ResilientBackendMixin,
                                                TransportError)
from repro.telemetry.counters import CounterBackend, check_scrape_interval


class TpuTransport:
    """Interface: one `(duty_cycle, clock_mhz, t_s)` triple per device.

    Same lifecycle contract as `FieldTransport` (`connect()` is the
    reconnect path, `close()` idempotent, every failure a
    `TransportError`).
    """

    def connect(self) -> None:
        """Establish (or re-establish) the telemetry channel."""

    def close(self) -> None:
        """Tear the channel down (idempotent)."""

    @property
    def n_devices(self) -> int:
        raise NotImplementedError

    def read(self, device: int) -> tuple:
        """(duty_cycle in [0,1], clock_mhz, transport timestamp s)."""
        raise NotImplementedError

    def __enter__(self):
        self.connect()
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class LibtpuTransport(TpuTransport):
    """Hardware transport over libtpu's telemetry surface.

    Duty cycle comes from the `tensorcore_utilization`/megacore
    duty-cycle metric (`tpu-info`'s source), clock from the
    power-management stream.  Gated: this CPU container has no libtpu,
    so `connect()` raises a clear `TransportError` pointing at the fake
    — the same pattern as `PynvmlTransport` without its module.
    """

    def __init__(self, *, clock=time.monotonic):
        self._clock = clock
        self._lib = None

    def connect(self) -> None:
        import importlib.util
        for mod in ("libtpu", "tpu_info"):
            if importlib.util.find_spec(mod) is not None:
                self._lib = importlib.import_module(mod)
                break
        else:
            raise TransportError(
                "no libtpu telemetry available (neither 'libtpu' nor "
                "'tpu_info' is importable in this container); use "
                "FakeTpuTransport / --transport fake for hardware-less "
                "runs")

    def close(self) -> None:
        self._lib = None

    @property
    def n_devices(self) -> int:  # pragma: no cover - hardware only
        if self._lib is None:
            raise TransportError("libtpu transport is not connected")
        chips = getattr(self._lib, "device", None)
        if chips is not None and hasattr(chips, "get_local_chips"):
            return len(chips.get_local_chips())
        raise TransportError("libtpu is present but exposes no local "
                             "chip enumeration this shim recognizes")

    def read(self, device: int):  # pragma: no cover - hardware only
        if self._lib is None:
            raise TransportError("libtpu transport is not connected")
        metrics = getattr(self._lib, "metrics", None)
        if metrics is None or not hasattr(metrics, "get_chip_usage"):
            raise TransportError(
                "libtpu is present but exposes no duty-cycle metric "
                "this shim recognizes (expected metrics.get_chip_usage)")
        usage = metrics.get_chip_usage()[device]
        clock_mhz = getattr(usage, "clock_mhz", None)
        if clock_mhz is None:
            raise TransportError(
                "libtpu chip usage carries no clock_mhz; OFU needs the "
                "clock, and a missing one must not read as 0 MHz")
        return (float(usage.duty_cycle_pct) / 100.0, float(clock_mhz),
                float(self._clock()))


class TpuProfilerBackend(ResilientBackendMixin, CounterBackend):
    """Deploy target for TPU fleets: the `CounterBackend` the paper's
    TPU deployments poll, now functional over any `TpuTransport`.

    Constructed with no transport it wires `LibtpuTransport` (the
    hardware default — in this container that raises a clear
    `TransportError` on first poll, pointing at the fake); CI
    constructs it over `FakeTpuTransport` and runs the identical
    policy/retry/staleness code path.
    """

    def __init__(self, device: int = 0, transport: TpuTransport = None, *,
                 strict: bool = True, max_retries: int = 3,
                 backoff_s: float = 0.05, backoff_mult: float = 2.0,
                 max_stale_polls: int = 3, sleep=None):
        self.device = int(device)
        self.strict = bool(strict)
        self._init_resilience(
            transport if transport is not None else LibtpuTransport(),
            max_retries=max_retries, backoff_s=backoff_s,
            backoff_mult=backoff_mult, max_stale_polls=max_stale_polls,
            sleep=sleep)

    def _read_once(self) -> tuple:
        duty, clock_mhz, t_s = self.transport.read(self.device)
        if not 0.0 <= duty <= 1.0:
            raise TransportError(
                f"duty cycle {duty!r} outside [0, 1] on device "
                f"{self.device}")
        self._note_freshness(("duty", self.device), t_s)
        return duty, clock_mhz

    # -- CounterBackend -------------------------------------------------
    def poll(self, window_s: float) -> tuple:
        """(hardware-averaged duty cycle, clock sample), §IV-C enforced
        identically to the DCGM side."""
        check_scrape_interval(window_s, strict=self.strict)
        duty, clock_mhz = self._with_retries(self._read_once)
        self.polls += 1
        return duty, clock_mhz

"""Acquisition-tier unit tests (ISSUE 10 tentpole): the transport
protocol, the `dcgmi dmon` parser, snapshot-per-round batching, the
shared retry/backoff/staleness policy, and the engine-driven fakes.

The end-to-end bit-identity claim (fake transport -> backend -> source
-> collector -> HTTP == pure simulator) lives in
`tools/fleet_live.py --self-check`; these tests pin the pieces.
"""
import numpy as np
import pytest

from repro.telemetry.backends import (
    DCGM_FI_DEV_SM_CLOCK, DCGM_FI_PROF_PIPE_TENSOR_ACTIVE,
    DcgmFieldBackend, DcgmiTransport, FakeDcgmTransport, FakeTpuTransport,
    FieldSample, LibtpuTransport, PynvmlTransport, TpuProfilerBackend,
    TransportError, make_dcgm_backends, parse_dmon,
)
from repro.telemetry.backends.fake import quantize_wire
from repro.telemetry.counters import StepProfile
from repro.telemetry.source import BackendSource, SimulatorSource

PROFILE = StepProfile(mxu_time_s=0.84, step_time_s=2.0)
TPA, CLK = DCGM_FI_PROF_PIPE_TENSOR_ACTIVE, DCGM_FI_DEV_SM_CLOCK


def _fake(**kw):
    kw.setdefault("duration_s", 600.0)
    kw.setdefault("interval_s", 30.0)
    kw.setdefault("n_devices", 2)
    kw.setdefault("seed", 3)
    t = FakeDcgmTransport(PROFILE, **kw)
    t.connect()
    return t


# ---------------------------------------------------------------------------
# parse_dmon
# ---------------------------------------------------------------------------
def test_parse_dmon_both_row_shapes_and_headers():
    text = """\
# Entity  TENSO  SMCLK
# Id
GPU 0     0.412  1410
GPU 1     0.000  210
2         0.985  1980

"""
    out = parse_dmon(text, (TPA, CLK))
    assert out == {0: {TPA: 0.412, CLK: 1410.0},
                   1: {TPA: 0.0, CLK: 210.0},
                   2: {TPA: 0.985, CLK: 1980.0}}


def test_parse_dmon_na_is_missing_not_zero():
    out = parse_dmon("GPU 0  N/A  1410\n", (TPA, CLK))
    assert out == {0: {CLK: 1410.0}}        # TPA absent, not 0.0


@pytest.mark.parametrize("row", [
    "GPU zero 0.4 1410",            # bad entity id
    "GPU 0 0.4",                    # too few values
    "0 0.4 fast",                   # unparsable value
])
def test_parse_dmon_garbage_raises(row):
    with pytest.raises(TransportError):
        parse_dmon(row, (TPA, CLK))


# ---------------------------------------------------------------------------
# DcgmiTransport with an injected runner
# ---------------------------------------------------------------------------
class _Runner:
    """Scripted dcgmi: answers --version, serves dmon snapshots in
    sequence (last one repeats), counts invocations."""

    def __init__(self, snapshots):
        self.snapshots = list(snapshots)
        self.dmon_calls = 0
        self.version_calls = 0

    def __call__(self, cmd):
        if "--version" in cmd:
            self.version_calls += 1
            return "dcgmi version 3.0\n"
        assert cmd[1] == "dmon" and "-e" in cmd
        self.dmon_calls += 1
        k = min(self.dmon_calls - 1, len(self.snapshots) - 1)
        return self.snapshots[k]


def test_dcgmi_snapshot_per_round_batching():
    """One dmon invocation covers every GPU; a GPU reading twice marks
    the new round and refreshes the snapshot."""
    r = _Runner(["GPU 0  0.10  1000\nGPU 1  0.20  1100\n",
                 "GPU 0  0.30  1200\nGPU 1  0.40  1300\n"])
    t = DcgmiTransport(runner=r)
    t.connect()
    assert r.version_calls == 1
    assert t.n_devices == 2 and r.dmon_calls == 1
    s0 = t.read(0, (TPA, CLK))
    s1 = t.read(1, (TPA, CLK))
    assert r.dmon_calls == 1                 # same snapshot served both
    assert s0[TPA].value == 0.10 and s1[TPA].value == 0.20
    assert t.read(0, (TPA, CLK))[TPA].value == 0.30   # round 2 refresh
    assert r.dmon_calls == 2
    assert t.read(1, (TPA, CLK))[CLK].value == 1300.0
    assert r.dmon_calls == 2


def test_dcgmi_percent_scale_and_error_paths():
    r = _Runner(["GPU 0  41.2  1410\n"])     # percent-reporting build
    t = DcgmiTransport(runner=r)
    t.connect()
    assert t.read(0, (TPA, CLK))[TPA].value == pytest.approx(0.412)
    with pytest.raises(TransportError, match="absent from dmon"):
        t.read(7, (TPA, CLK))
    t.close()
    with pytest.raises(TransportError, match="not connected"):
        t.read(0, (TPA, CLK))
    # a missing (N/A) profiling field is fatal at read, with a hint
    t2 = DcgmiTransport(runner=_Runner(["GPU 0  N/A  1410\n"]))
    t2.connect()
    with pytest.raises(TransportError, match="N/A for GPU 0"):
        t2.read(0, (TPA, CLK))
    # an empty snapshot is a transport failure, not 0 devices
    t3 = DcgmiTransport(runner=_Runner(["# nothing\n"]))
    t3.connect()
    with pytest.raises(TransportError, match="no GPU rows"):
        t3.read(0, (TPA, CLK))


def test_dcgmi_connect_requires_binary_on_path():
    t = DcgmiTransport(binary="definitely-not-a-real-dcgmi-binary")
    with pytest.raises(TransportError, match="not found on PATH"):
        t.connect()


def test_pynvml_connect_is_gated_on_module():
    try:
        import pynvml  # noqa: F401
        pytest.skip("pynvml installed; gating path not reachable")
    except ImportError:
        pass
    with pytest.raises(TransportError, match="pynvml"):
        PynvmlTransport().connect()


# ---------------------------------------------------------------------------
# DcgmFieldBackend policy: ranges, staleness, retry/backoff
# ---------------------------------------------------------------------------
class _ScriptedTransport:
    """Serves a scripted list of (tpa, clk, t_s) triples; entries that
    are exceptions raise instead."""

    def __init__(self, script):
        self.script = list(script)
        self.i = 0
        self.connects = 0
        self.closes = 0

    def connect(self):
        self.connects += 1

    def close(self):
        self.closes += 1

    @property
    def n_devices(self):
        return 1

    def read(self, gpu, field_ids):
        item = self.script[min(self.i, len(self.script) - 1)]
        self.i += 1
        if isinstance(item, Exception):
            raise item
        tpa, clk, t_s = item
        return {TPA: FieldSample(tpa, t_s), CLK: FieldSample(clk, t_s)}


def test_backend_rejects_out_of_range_readings():
    for bad in [(1.7, 1400.0, 1.0), (-0.1, 1400.0, 1.0),
                (0.5, -3.0, 1.0), (0.5, 99_999.0, 1.0)]:
        be = DcgmFieldBackend(0, _ScriptedTransport([bad]),
                              max_retries=0, sleep=lambda s: None)
        with pytest.raises(TransportError, match="outside"):
            be.poll(30.0)
        assert not be.healthy


def test_backend_staleness_tolerates_then_escalates():
    """A frozen timestamp is tolerated for max_stale_polls reads (DCGM
    legitimately repeats when over-polled), then escalates."""
    frozen = [(0.4, 1400.0, 5.0)] * 10      # t_s never advances
    be = DcgmFieldBackend(0, _ScriptedTransport(frozen), max_retries=0,
                          max_stale_polls=3, sleep=lambda s: None)
    assert be.poll(30.0) == (0.4, 1400.0)   # first: fresh
    for _ in range(3):                      # tolerated repeats
        assert be.poll(30.0) == (0.4, 1400.0)
    assert be.healthy
    with pytest.raises(TransportError, match="stale for 4 consecutive"):
        be.poll(30.0)
    # 3 tolerated polls count both fields; the 4th counts tpa then
    # escalates before reaching clk
    assert be.stale_reads == 7 and not be.healthy


def test_backend_retry_backoff_schedule_and_reconnect():
    t = _ScriptedTransport([TransportError("boom 1"),
                            TransportError("boom 2"),
                            (0.4, 1400.0, 1.0)])
    naps = []
    be = DcgmFieldBackend(0, t, max_retries=3, backoff_s=0.05,
                          backoff_mult=2.0, sleep=naps.append)
    assert be.poll(30.0) == (0.4, 1400.0)
    assert naps == [0.05, 0.1]              # exponential schedule
    assert be.retries == 2 and be.reconnects == 2
    assert t.closes == 2 and t.connects == 3   # close -> backoff -> connect
    assert be.healthy and be.polls == 1


def test_backend_gives_up_after_max_retries():
    t = _ScriptedTransport([TransportError("dead daemon")] * 10)
    be = DcgmFieldBackend(0, t, max_retries=2, sleep=lambda s: None)
    with pytest.raises(TransportError, match="gave up after 2"):
        be.poll(30.0)
    assert not be.healthy and be.retries == 2


def test_backend_enforces_scrape_window():
    be = DcgmFieldBackend(0, _ScriptedTransport([(0.4, 1400.0, 1.0)]))
    with pytest.raises(ValueError, match="30"):
        be.poll(45.0)                        # §IV-C: > hardware window
    lax = DcgmFieldBackend(0, _ScriptedTransport([(0.4, 1400.0, 1.0)]),
                           strict=False)
    with pytest.warns(RuntimeWarning):
        lax.poll(45.0)


# ---------------------------------------------------------------------------
# fakes + make_dcgm_backends + BackendSource integration
# ---------------------------------------------------------------------------
def test_fake_transport_matches_simulator_bitwise():
    t = _fake(chunk_s=300.0)
    # chunk seeds derive from the poll COUNT, so the reference simulator
    # must be polled at the fake's chunk_s cadence (as a collector with
    # round_s == chunk_s does)
    sim = SimulatorSource(profile=PROFILE, duration_s=600.0,
                          interval_s=30.0, n_devices=2, seed=3)
    want = sim.poll(300.0)
    want2 = sim.poll(300.0)
    want = np.concatenate([want.tpa, want2.tpa], axis=1)
    got = np.empty_like(want)
    # device-major like BackendSource: exercises the per-GPU cursors
    for d in range(2):
        for i in range(20):
            got[d, i] = t.read(d, (TPA,))[TPA].value
    np.testing.assert_array_equal(got, want)
    assert t.exhausted
    with pytest.raises(TransportError, match="exhausted"):
        t.read(0, (TPA,))


def test_fake_transport_validation_and_quantize():
    t = _fake(quantize=True)
    s = t.read(0, (TPA, CLK))
    assert s[TPA].value == round(s[TPA].value, 3)
    assert s[CLK].value == round(s[CLK].value, 0)
    with pytest.raises(TransportError, match="no such GPU"):
        t.read(9, (TPA,))
    with pytest.raises(TransportError, match="unsupported DCGM field"):
        t.read(0, (123,))
    t.close()
    with pytest.raises(TransportError, match="not connected"):
        t.read(0, (TPA,))
    with pytest.raises(ValueError, match="finite duration"):
        FakeDcgmTransport(PROFILE, duration_s=float("inf"),
                          interval_s=30.0)


def test_quantize_wire_shapes():
    tpa, clk = quantize_wire(np.array([0.123456, 0.5]),
                             np.array([1410.7, 899.2]))
    np.testing.assert_array_equal(tpa, [0.123, 0.5])
    np.testing.assert_array_equal(clk, [1411.0, 899.0])


def test_make_dcgm_backends_and_source_roundtrip():
    t = _fake(chunk_s=300.0)
    backends = make_dcgm_backends(t, sleep=lambda s: None)
    assert len(backends) == 2
    assert [b.gpu for b in backends] == [0, 1]
    src = BackendSource(backends=backends, duration_s=600.0,
                        interval_s=30.0)
    sim = SimulatorSource(profile=PROFILE, duration_s=600.0,
                          interval_s=30.0, n_devices=2, seed=3)
    # poll both at the fake's chunk cadence: chunk seeds match poll
    # count, so the grids must be bit-identical round by round
    for _ in range(2):
        grid = src.poll(300.0)
        want = sim.poll(300.0)
        np.testing.assert_array_equal(grid.tpa, want.tpa)
        np.testing.assert_array_equal(grid.clock_mhz, want.clock_mhz)
    assert all(b.healthy and b.polls == 20 for b in backends)


def test_fault_injection_is_sample_transparent():
    clean = _fake(chunk_s=300.0)
    flaky = _fake(chunk_s=300.0, fail_every=13)
    b_clean = make_dcgm_backends(clean, 2, sleep=lambda s: None)
    b_flaky = make_dcgm_backends(flaky, 2, sleep=lambda s: None)
    g1 = BackendSource(backends=b_clean, duration_s=600.0,
                       interval_s=30.0).poll(600.0)
    g2 = BackendSource(backends=b_flaky, duration_s=600.0,
                       interval_s=30.0).poll(600.0)
    np.testing.assert_array_equal(g1.tpa, g2.tpa)
    assert sum(b.retries for b in b_flaky) > 0
    assert all(b.healthy for b in b_flaky)


# ---------------------------------------------------------------------------
# TPU side
# ---------------------------------------------------------------------------
def test_tpu_backend_polls_through_fake_transport():
    be = TpuProfilerBackend(0, FakeTpuTransport(
        PROFILE, duration_s=300.0, interval_s=30.0, n_devices=1, seed=5))
    duty, clock = be.poll(30.0)
    assert 0.0 <= duty <= 1.0 and clock > 0.0
    assert be.healthy and be.polls == 1


def test_tpu_backend_validates_duty_range():
    class Bad(FakeTpuTransport):
        def read(self, device):
            return (1.5, 940.0, 1.0)

    be = TpuProfilerBackend(0, Bad(PROFILE, duration_s=300.0,
                                   interval_s=30.0),
                            max_retries=0, sleep=lambda s: None)
    with pytest.raises(TransportError, match="outside"):
        be.poll(30.0)


def test_tpu_default_transport_is_gated_libtpu():
    be = TpuProfilerBackend(0, max_retries=0, sleep=lambda s: None)
    assert isinstance(be.transport, LibtpuTransport)
    # whether libtpu imports or not, a CPU container cannot serve duty
    # cycles — the poll must fail with an actionable TransportError
    with pytest.raises(TransportError):
        be.poll(30.0)


def test_libtpu_transport_missing_clock_raises():
    """A chip-usage record without a clock fails loudly instead of
    reading as 0 MHz (which would make OFU zero)."""
    from types import SimpleNamespace as NS
    t = LibtpuTransport(clock=lambda: 7.0)
    usage = [NS(duty_cycle_pct=50.0), NS(duty_cycle_pct=40.0, clock_mhz=1400)]
    t._lib = NS(metrics=NS(get_chip_usage=lambda: usage))
    with pytest.raises(TransportError, match="clock_mhz"):
        t.read(0)
    assert t.read(1) == (0.4, 1400.0, 7.0)


def test_lazy_reexport_from_counters():
    """`telemetry.counters.TpuProfilerBackend` stays importable (PEP 562
    forward) so pre-backends callers keep working."""
    from repro.telemetry import counters
    assert counters.TpuProfilerBackend is TpuProfilerBackend
    with pytest.raises(AttributeError):
        counters.NoSuchThing

"""Program spans and counters (`repro.core.spans`): recorded only while a
JAX profiler trace is active, nested and thread-safe, and present in the
trace the profiler writes."""
import glob
import os
import subprocess
import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from repro.core import spans  # noqa: E402


@pytest.fixture(autouse=True)
def clean():
    spans.reset()
    yield
    spans.reset()


def _record():
    with spans.span("outer"):
        time.sleep(0.01)
        with spans.span("inner"):
            time.sleep(0.02)
        with spans.span("inner"):
            spans.count("items", 3)
    spans.count("items")


def test_nothing_is_recorded_with_the_profiler_off():
    assert not spans.recording()
    _record()
    assert spans.snapshot() == {"spans": {}, "counters": {}}


def test_module_loads_without_jax():
    code = ("import sys; from repro.core import spans; spans.count('c'); "
            "s = spans.span('s'); s.__enter__(); s.__exit__(None, None, "
            "None); assert 'jax' not in sys.modules; "
            "assert spans.snapshot() == {'spans': {}, 'counters': {}}")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def test_names_counts_parents_and_self_time(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert spans.recording()
        _record()
    assert not spans.recording()
    snap = spans.snapshot()
    outer, inner = snap["spans"]["outer"], snap["spans"]["inner"]
    assert (outer["count"], inner["count"]) == (1, 2)
    assert (outer["parent"], inner["parent"]) == (None, "outer")
    assert snap["counters"] == {"items": 4}
    assert inner["total_s"] >= 0.02 and outer["total_s"] >= 0.03
    assert inner["self_s"] == pytest.approx(inner["total_s"])
    assert outer["self_s"] == pytest.approx(outer["total_s"]
                                            - inner["total_s"])
    assert 0.01 <= outer["self_s"] < outer["total_s"]


def test_reset_forgets_everything(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _record()
        assert spans.snapshot()["spans"]
        spans.reset()
        assert spans.snapshot() == {"spans": {}, "counters": {}}
        with spans.span("after"):
            pass
    assert list(spans.snapshot()["spans"]) == ["after"]


def test_threads_record_without_loss(tmp_path):
    """More threads than cores, switching often: a lost update to a
    count or a parent taken from another thread's stack would show."""
    n, k = 500, 2 * (os.cpu_count() or 1)
    go = threading.Barrier(k)

    def work(tag):
        go.wait()
        with spans.span(f"root.{tag}"):
            for _ in range(n):
                with spans.span("leaf"):
                    spans.count("leaves")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(k)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = spans.snapshot()
    assert snap["counters"] == {"leaves": k * n}
    assert snap["spans"]["leaf"]["count"] == k * n
    # each thread's leaves nest under its own root, never the other's
    assert snap["spans"]["leaf"]["parent"].startswith("root.")
    for tag in range(k):
        root = snap["spans"][f"root.{tag}"]
        assert root["count"] == 1 and root["parent"] is None
        assert 0 <= root["self_s"] < root["total_s"]
    leaves = snap["spans"]["leaf"]["total_s"]
    roots = sum(snap["spans"][f"root.{t}"]["total_s"]
                - snap["spans"][f"root.{t}"]["self_s"] for t in range(k))
    assert roots == pytest.approx(leaves)


def test_the_trace_holds_the_spans_as_host_events(tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        _record()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(spans.PREFIX)]
    assert sorted(names) == ["repro.inner", "repro.inner", "repro.outer"]

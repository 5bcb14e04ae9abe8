"""The tracer's mirror into ``jax.profiler`` and its ring buffer.

A recorded span is also a ``TraceAnnotation``: under ``jax.profiler`` it
lands on the xplane's host plane, on the profiler's clock.  Tracing off
builds no annotation.  The ring buffer keeps the newest spans and counts
every one it drops."""

import time

import jax
import pytest

from repro import obs
from repro.obs import tracer
from repro.obs.tracer import NULL_SPAN, TRACER

CLOCK = "bench.clock"


@pytest.fixture(autouse=True)
def _reset():
    obs.disable()
    obs.clear_spans()
    yield
    obs.configure(mode="off", buffer=65536)
    obs.clear_spans()


def _host_events(path):
    """(name, start ns, end ns) of every event on the host planes."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_recorded_span_lands_on_the_host_plane(tmp_path):
    import glob
    obs.enable("full")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # the stamp that ties the profiler's clock to perf_counter
        stamp = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(f"{CLOCK}@{stamp}"):
            pass
        with obs.span("mirror.outer", "t"):
            time.sleep(0.01)
            with obs.span("mirror.inner", "t"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _host_events(path)
    (clock,) = [(n, s) for n, s, _ in events if n.startswith(CLOCK + "@")]
    offset_ns = clock[1] - int(clock[0].split("@")[1])
    spans = {s.name: s for s in obs.finished_spans()}
    for name in ("mirror.outer", "mirror.inner"):
        (ev,) = [e for e in events if e[0] == name]
        start, end = ((t - offset_ns) / 1e9 for t in ev[1:])
        assert abs(start - spans[name].t0) < 1e-3
        assert abs(end - spans[name].t1) < 1e-3
    assert spans["mirror.inner"].parent_id == spans["mirror.outer"].span_id


def test_no_annotation_is_built_when_off(monkeypatch):
    built = []

    class Probe:
        def __init__(self, name):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    obs.enable("full")              # resolves the mirror once
    monkeypatch.setattr(TRACER, "_annotation", Probe)
    obs.disable()
    with obs.span("off.span") as sp:
        pass
    assert sp is NULL_SPAN and not sp.recording
    assert built == []
    obs.enable("full")
    with obs.span("on.span") as sp:
        assert sp.recording and sp._mirror is not None
    assert built == ["on.span"] and sp._mirror is None


def test_sampled_out_spans_do_not_record():
    obs.enable("sampled", sample_every=2)
    flags = []
    for _ in range(4):
        with obs.span("root") as sp:
            flags.append(sp.recording)
    assert flags.count(True) == 2 and flags.count(False) == 2


def test_mirror_needs_jax(monkeypatch):
    monkeypatch.setattr(TRACER, "_annotation", None)
    monkeypatch.setattr(tracer, "_profiler_annotation", lambda: None)
    obs.enable("full")
    with obs.span("plain") as sp:
        assert sp._mirror is None
    assert [s.name for s in obs.finished_spans()] == ["plain"]


@pytest.mark.parametrize("new, kept, dropped", [(4, 4, 8), (32, 12, 0)])
def test_ring_buffer_resize_keeps_the_newest(new, kept, dropped):
    obs.enable("full", buffer=16)
    for i in range(12):
        with obs.span(f"s{i}"):
            pass
    obs.configure(buffer=new)
    names = [s.name for s in obs.finished_spans()]
    assert names == [f"s{i}" for i in range(12 - kept, 12)]
    assert TRACER.dropped == dropped
    for i in range(12, 12 + new):
        with obs.span(f"s{i}"):
            pass
    assert len(obs.finished_spans()) == new
    assert TRACER.dropped == dropped + (kept + new) - new

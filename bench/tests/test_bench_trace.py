"""The trace reduction and the roofline arithmetic, on the CPU: a small
trace recorded here, and hand-made events with a known union and known
gaps."""

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness import core, readers, roofline  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.core import Run, Span, Unit  # noqa: E402


def ev(name, start, dur, dev="/device:TPU:0"):
    return tr.DeviceEvent(name=name, start_s=start, dur_s=dur, device=dev)


def test_union_and_gaps_of_known_intervals():
    iv = [(1.0, 2.0), (1.5, 3.0), (4.0, 5.0), (4.2, 4.4), (9.0, 12.0)]
    assert tr.union_length(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert tr.union_length(iv, 1.75, 4.5) == pytest.approx(1.75)
    assert tr.gaps(iv, 0.0, 10.0) == [(0.0, 1.0), (3.0, 4.0), (5.0, 9.0)]
    assert tr.gaps([], 2.0, 3.0) == [(2.0, 3.0)]
    assert tr.union_length([], 0.0, 1.0) == 0.0


def test_busy_kernels_top_ops_and_idle_attribution():
    t = tr.Trace(events=[ev("_kernel_padded", 1.0, 0.5),
                         ev("_perm_kernel", 1.5, 0.25),
                         ev("fusion.3", 1.6, 0.5),
                         ev("copy.1", 3.0, 1.0)],
                 devices=["/device:TPU:0"], host_spans=[])
    assert tr.busy_s(t, 0.0, 5.0) == pytest.approx(2.1)
    for fam, name in (("hash_partition", "_kernel_padded"),
                      ("scatter_perm", "_perm_kernel")):
        found = tr.kernel_events(t, tr.KERNELS[fam], 0, 5)
        assert [e.name for e in found] == [name]
    assert tr.top_ops(t, 0.0, 5.0)[0] == ["copy.1", 1.0]
    spans = [("exec.join", 0.0, 0.9), ("exec.run", 0.0, 4.9),
             ("exec.aggregate", 2.2, 2.9)]
    idle = dict(map(tuple, tr.idle_by_host(t, 0.0, 5.0, spans)))
    # gaps: [0, 1) in join, [2.1, 3) in aggregate, [4, 5) in the run
    assert idle == pytest.approx({"exec.join": 1.0, "exec.aggregate": 0.9,
                                  "exec.run": 1.0})


def test_kernels_known_by_their_hlo_custom_calls():
    perm = ev('%scatter_permutation.1 = s32[8388608]{0:T(1024)} custom-call('
              's32[8388608]{0:T(1024)} %and_select_fusion, s32[1,128]{1,0:T('
              '1,128)S(1)} %pad.5), custom_call_target="tpu_custom_call"',
              0.0, 1.0)
    assert tr.event_kernel(perm) == "scatter_perm"
    assert tr.op_label(perm) == "scatter_perm"
    hashp = ev('%padded_partition_ids.3 = (s32[1024]{0:T(1024)}, s32[33]{0'
               '}) custom-call(s32[1024]{0} %p), custom_call_target="x"', 0, 1)
    assert tr.hlo_parts(hashp.name) == (
        "padded_partition_ids.3", "(s32[1024], s32[33])", "custom-call")
    assert tr.event_kernel(hashp) == "hash_partition"
    hashk = ev('%padded_partition_ids.3 = s32[1024]{0} custom-call('
               's32[1024]{0} %p)', 0, 1)
    assert tr.event_kernel(hashk) == "hash_partition"
    fusion = ev('%fusion.2 = u8[16777217,44]{0,1:T(8,128)(4,1)} fusion('
                'u8[16777217,44]{0,1:T(8,128)(4,1)} %b), kind=kCustom', 0, 1)
    assert tr.event_kernel(fusion) is None
    assert tr.op_label(fusion, "jit_fn") == "jit_fn/fusion.2 u8[16777217,44]"


def test_recorded_cpu_trace_lands_on_the_perf_clock(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 3).sum())
    x = jnp.ones(4096)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        stamp = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(f"{tr.CLOCK_ANNOTATION}@{stamp}"):
            pass
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("query.probe"):
            f(x).block_until_ready()
            time.sleep(0.02)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    t = tr.read_xplane(tr.find_xplane(str(tmp_path)))
    (probe,) = [s for s in t.host_spans if s[0] == "query.probe"]
    assert abs(probe[1] - t0) < 5e-3 and abs(probe[2] - t1) < 5e-3
    assert probe[2] - probe[1] >= 0.02
    # the CPU has no device plane: nothing reads as device time
    assert t.events == [] and tr.busy_s(t, t0, t1) == 0.0


def test_peaks_table_refuses_an_unknown_chip():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_roofline_share_from_dispatch_spans_and_kernel_events():
    n, m = 1 << 20, 32
    per_call = roofline.hash_partition_bytes({"rows": n, "m": m}) / 819e9
    run = Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
              traced=True, device_kind="TPU v5 lite")
    run.window = (0.0, 10.0)
    run.spans = [Span("shuffle.dispatch", 1.0 + i, 1.5 + i,
                      {"rows": n, "m": m, "op": "rebucket"})
                 for i in range(2)]
    # each call takes 4x its bandwidth bound: 25% of the roofline
    run.trace = tr.Trace(events=[ev("_kernel_padded", 1.1 + i, 4 * per_call)
                                 for i in range(2)],
                         devices=["/device:TPU:0"], host_spans=[])
    hashp, perm = (core.load_module("metrics", f"{k}_roofline.query").read
                   for k in ("hash_partition", "scatter_perm"))
    assert hashp(run) == pytest.approx(25.0)
    # no scatter_perm events: nothing to read, not a zero
    assert perm(run) is None
    # an event without its dispatch is not paired up
    run.spans = run.spans[:1]
    assert hashp(run) is None


def test_idle_share_and_span_readers():
    run = Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
              traced=True)
    run.window = (0.0, 4.0)
    run.trace = tr.Trace(events=[ev("fusion", 1.0, 1.0)],
                         devices=["/device:TPU:0"], host_spans=[])
    assert readers.idle_pct(run) == pytest.approx(75.0)
    run.units = [Unit("query.q04", 0.0, 2.0), Unit("query.q17", 2.0, 4.0),
                 Unit("query.q18", 4.0, 4.5, error="boom")]
    run.spans = [Span("exec.join", 0.1, 0.6, {}),
                 Span("exec.join", 2.1, 2.4, {})]
    assert readers.span_s_per_unit(run, "query.", "exec.join") == \
        pytest.approx(0.4)

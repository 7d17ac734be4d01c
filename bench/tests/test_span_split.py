"""The idle split by program span, checked by hand on a synthetic trace
with nested spans, against the harness's reduction of the same trace, and
on a real (CPU) profile of a drain."""
from __future__ import annotations

import pytest

from bench import span_split as ss
from bench import trace_reduce as tr

DEV = "/device:TPU:0"


def nested():
    """``bench.window`` [0, 1000] holds ``bench.drain`` [100, 900], which
    holds ``hc.admit`` [120, 180] and ``hc.dispatch`` [200, 800], which
    holds ``hc.read.decode`` [500, 700].  The device is busy in
    [150, 250], [400, 550] and [650, 720]."""
    ops = {DEV: [("a", 150, 100), ("b", 400, 150), ("c", 650, 70)]}
    modules = {DEV: [("jit_batched_scan_fused(1)", 150, 100),
                     ("jit_batched_scan_fused(2)", 400, 150),
                     ("jit_apply_snapshot_delta(3)", 650, 70)]}
    spans = [("bench.window", 0, 1000), ("bench.drain", 100, 800),
             ("hc.admit", 120, 60), ("hc.dispatch", 200, 600),
             ("hc.read.decode", 500, 200)]
    return ops, modules, spans


def test_gaps_take_the_innermost_span():
    ops, modules, spans = nested()
    out = tr.reduce(ops, modules, spans, tr.window_of(spans))
    # [720, 1000] mid 860: past hc.dispatch, inside bench.drain;
    # [550, 650] lies inside hc.read.decode inside bench.drain
    assert out["idle_gaps"] == [["bench.drain", pytest.approx(280e-9)],
                                ["bench.window", pytest.approx(150e-9)],
                                ["hc.dispatch", pytest.approx(150e-9)],
                                ["hc.read.decode", pytest.approx(100e-9)]]


def test_idle_by_span_by_hand():
    ops, modules, spans = nested()
    out = ss.split(ops, modules, spans, tr.window_of(spans))
    # [0, 150]: window 100, drain 20, admit 30; [250, 400]: dispatch 150;
    # [550, 650]: decode 100; [720, 1000]: dispatch 80, drain 100,
    # window 100
    want = {"bench.window": 200e-9, "bench.drain": 120e-9,
            "hc.admit": 30e-9, "hc.dispatch": 230e-9,
            "hc.read.decode": 100e-9}
    assert out["idle_by_span"] == pytest.approx(want)
    busy = tr.reduce(ops, modules, spans, tr.window_of(spans))["busy_s"]
    assert out["idle_s"] == pytest.approx(out["window_s"] - busy)
    assert sum(out["idle_by_span"].values()) == pytest.approx(680e-9)
    assert out["idle_in_program_s"] == pytest.approx(360e-9)
    assert out["idle_in_program_share"] == pytest.approx(0.36)
    assert out["span_s"]["hc.dispatch"] == pytest.approx(600e-9)
    assert out["span_calls"]["hc.read.decode"] == 1
    assert out["dispatch_covered"] == pytest.approx(200 / 600)


def test_program_spans_leave_the_device_numbers_alone():
    """Adding the program's spans moves no device number of the harness's
    reduction: only the gap labels see spans."""
    ops, modules, spans = nested()
    bench_only = [s for s in spans if s[0].startswith("bench.")]
    window = tr.window_of(spans)
    a = tr.reduce(ops, modules, bench_only, window)
    b = tr.reduce(ops, modules, spans, window)
    for key in ("busy_s", "window_s", "idle_share", "modules",
                "module_calls", "device_ops"):
        assert a[key] == b[key], key
    assert [g[1] for g in a["idle_gaps"]] == [g[1] for g in b["idle_gaps"]]


def test_window_clips_spans_and_gaps():
    ops, modules, spans = nested()
    out = ss.split(ops, modules, spans, (300, 600))
    # idle [300, 400] in dispatch, [550, 600] in decode
    assert out["idle_by_span"] == pytest.approx(
        {"hc.dispatch": 100e-9, "hc.read.decode": 50e-9})
    assert out["span_s"]["hc.dispatch"] == pytest.approx(300e-9)


def test_real_profile_of_a_drain(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import (HoneycombConfig, HoneycombService,
                            HoneycombStore, Put, Scan)
    from repro.core.keys import int_key
    st = HoneycombStore(HoneycombConfig(), heap_capacity=512)
    for i in range(64):
        st.put(int_key(i), b"v" * 8)
    svc = HoneycombService(st, batch_size=8)

    def drain():
        svc.submit_many([Put(int_key(200), b"w" * 8)]
                        + [Scan(int_key(i), int_key(i + 3), 4)
                           for i in range(16)])
        svc.drain()

    drain()                               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.drain"):
            drain()
    jax.profiler.stop_trace()
    out = ss.from_trace_dir(str(tmp_path))
    for name in ("hc.admit", "hc.export", "hc.sync.barrier", "hc.dispatch",
                 "hc.read.pack", "hc.read.fetch", "hc.read.decode"):
        assert out["span_calls"].get(name), name
    # no TPU plane on the CPU: the whole window is idle
    assert out["idle_s"] == pytest.approx(out["window_s"])
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    assert 0 < out["idle_in_program_share"] < 1
    assert 0 < out["dispatch_covered"] <= 1
    assert out["idle_gaps"] == []         # gaps come from device planes

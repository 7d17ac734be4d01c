"""The trace reduction, checked by hand on a synthetic trace, and its
reading of a real (CPU) profile."""
from __future__ import annotations

import pytest

from bench import trace_reduce as tr


def synthetic():
    dev = "/device:TPU:0"
    ops = {dev: [("a", 100, 50), ("b", 120, 60), ("c", 300, 100)]}
    modules = {dev: [("jit_batched_get_fused(1)", 100, 80),
                     ("jit_apply_snapshot_delta(7)", 300, 100)]}
    spans = [("bench.window", 50, 450), ("bench.drain", 90, 350),
             ("bench.submit", 60, 30)]
    return ops, modules, spans


def test_reduce_by_hand():
    ops, modules, spans = synthetic()
    out = tr.reduce(ops, modules, spans, tr.window_of(spans))
    # busy union [100, 180] + [300, 400] = 180 ns of a 450 ns window
    assert out["busy_s"] == pytest.approx(180e-9)
    assert out["window_s"] == pytest.approx(450e-9)
    assert out["idle_share"] == pytest.approx(1 - 180 / 450)
    assert out["modules"] == pytest.approx(
        {"batched_get_fused": 80e-9, "apply_snapshot_delta": 100e-9})
    assert out["module_calls"] == {"batched_get_fused": 1,
                                   "apply_snapshot_delta": 1}
    assert [n for n, _ in out["device_ops"]] == ["c", "b", "a"]
    assert out["device_ops"][0][1] == pytest.approx(100e-9)
    # gaps [180, 300] in drain, [400, 500] after drain, [50, 100] in submit
    gaps = out["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.drain", "bench.window",
                                    "bench.submit"]
    assert [g[1] for g in gaps] == pytest.approx([120e-9, 100e-9, 50e-9])


def test_window_clips_events():
    ops, modules, spans = synthetic()
    out = tr.reduce(ops, modules, spans, (130, 350))
    # [130, 180] + [300, 350] busy in a 220 ns window
    assert out["busy_s"] == pytest.approx(100e-9)
    assert out["window_s"] == pytest.approx(220e-9)
    # only modules that start inside the window count
    assert out["modules"] == pytest.approx({"apply_snapshot_delta": 100e-9})


def test_union_and_names():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.module_name("jit_batched_scan_fused(12)") == "batched_scan_fused"
    assert tr.module_name("fusion.3") == "fusion.3"
    assert tr.op_name("%copy.19 = u32[131072,1280]{1,0:T(8,128)} copy("
                      "u32[131072,1280]{1,0} %snap_image.1)") \
        == "%copy.19 u32[131072,1280]"
    assert tr.op_name("%batched_scan_fused.1 = (s32[256,1]{1,0}, "
                      "s32[256,32,8]{2,1,0}) custom-call(s32[2]{0} %p)") \
        == "%batched_scan_fused.1 s32[256,1]"
    assert tr.op_name("fusion.4") == "fusion.4"


def test_real_profile_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.drain"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, modules, spans = tr.from_profile(tr.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in spans}
    assert {"bench.window", "bench.drain"} <= names
    t0, t1 = tr.window_of(spans)
    assert t1 > t0
    out = tr.reduce(ops, modules, spans, (t0, t1))
    assert out["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    # no TPU plane on the CPU: nothing ran "on the device"
    assert out["busy_s"] == 0.0

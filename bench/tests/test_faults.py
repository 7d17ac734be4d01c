"""Faults planted under a run (bench/faults.py) make ``correct`` false:
the rest of a run is driven as bench/run.py drives it, with the chip
check skipped and the timed path broken underneath."""
from __future__ import annotations

import types

import pytest

from bench.faults import FAULTS
from bench.harness import result_line, run_cell
from bench.tests.small import RECORDS, SECONDS, small_cell

OPEN = "cloud-scan.uniform.open"

CPU = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]


def run(name, on_ready=None, seed=2 ** 31 + 7, trace=False):
    bm, cell, config, mix = small_cell(name)
    out = run_cell(config, mix, seed, SECONDS, trace, records=RECORDS,
                   on_ready=on_ready, log=lambda *_: None)
    return out, result_line(bm, cell, out, CPU)


def listed(name, kind):
    """Names of the metrics of one kind BENCHMARK.json gives the cell."""
    bm = small_cell(name)[0]
    return {m["name"] for m in bm[kind]
            if name in m.get("workloads", [name])}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_run_incorrect(fault):
    _, res = run(OPEN, FAULTS[fault])
    assert res["correct"] is False
    assert res["failed"] > 0
    checks = res["checks"]
    assert checks["wrong_answers"]["value"] + checks["unanswered"]["value"] \
        > checks["wrong_answers"]["limit"]
    assert list(res)[-1] == "checks"


def test_sound_run_is_correct():
    out, res = run(OPEN)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == listed(OPEN, "end_to_end")
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    # set-up's warm-up left no program to build inside the window
    assert res["programs_in_window"] == {"traced": 0, "compiled": 0,
                                         "fetched": 0}
    assert out["ctx"]["writes"] > 0 and out["ctx"]["syncs"] > 0


def test_traced_run_reports_per_layer_metrics():
    """A traced run (on the CPU: no device ops, so the trace readers find
    nothing) reports the per-layer metrics its counters and host clock
    give, and only metrics listed for the cell."""
    _, res = run(OPEN, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert got and got <= listed(OPEN, "per_layer")
    assert {"front_us_per_op", "vmem_hit_share"} <= got
    assert "device_idle_share" not in got         # no TPU plane on the CPU
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0


def test_write_epochs_leave_no_sync_to_build():
    """After set-up's write epochs, syncs of drains that hold up to the
    largest write count warmed build no program."""
    from bench.harness import Client, CompileCounter, Session, Spans, \
        warm_writes
    bm, cell, config, mix = small_cell(OPEN)
    s = Session(config, mix, 11, RECORDS, log=lambda *_: None)
    client = Client(s.svc, Spans(False))
    n = warm_writes(client, s.gen, 8, int(config["store"]["log_cap"]),
                    s.width)
    # 4 plain epochs of 1, 2 | 2, 3 | 4, 5, 6 | 8, 9, 12 writes, then 1, 2,
    # 4 and 8 hot keys with 1, 2, 4 and 8 writes
    assert n == 4 * 10 + 4 * 4
    cc = CompileCounter()
    for w in (1, 3, 5, 8):
        reqs = s.gen.writes(w)
        client.serve(reqs, s.ops(reqs), measured=False)
    assert cc.counts["compiled"] == 0

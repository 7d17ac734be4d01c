"""The YCSB-C cell at its documented 1 KB record, shrunk to the CPU: a
sound run is correct and serves every value from the device value image,
planted faults in the value path make ``correct`` false, and the closed
loop times whole epochs."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import result_line, run_cell
from bench.metrics import value_gather_roofline
from bench.tests.small import RECORDS, SECONDS, small_cell
from bench.tests.test_faults import CPU, listed

CELL = "ycsb-c.zipf.closed"


def run(on_ready=None, trace=False, seed=2 ** 31 + 15):
    bm, cell, config, mix = small_cell(CELL)
    out = run_cell(config, mix, seed, SECONDS, trace, records=RECORDS,
                   on_ready=on_ready, log=lambda *_: None)
    return out, result_line(bm, cell, out, CPU)


def test_sound_run_is_correct_from_the_value_image():
    bm, cell, config, mix = small_cell(CELL)
    assert config["value_bytes"] == 1000 and mix["ops"] == {"get": 1.0}
    out, res = run()
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == listed(CELL, "end_to_end") \
        == {"p50_ms", "setup_s"}
    assert res["programs_in_window"]["compiled"] == 0
    shard = out["ctx"]["shard"]
    assert shard["device_values"] == out["ctx"]["ops"]["get"] > 0
    assert shard["read_copies"] == shard["read_batches"]


def _truncate_values(session) -> None:
    """Fault: each GET answer cut to its 16 inline bytes."""
    store = session.store
    get_batch = store.get_batch

    def get(keys, **kw):
        return [v if v is None else v[:16] for v in get_batch(keys, **kw)]
    store.get_batch = get


def _neighbour_slot(monkeypatch):
    """Fault: the gather returns the row of the neighbouring slot."""
    from repro.core import shard as shard_mod
    real = shard_mod._jit_gather_values

    def gather(packed, values, **kw):
        return real(packed, jnp.roll(values, -1, axis=0), **kw)

    def plant(session):
        monkeypatch.setattr(shard_mod, "_jit_gather_values", gather)
    return plant


@pytest.mark.parametrize("fault", ["truncated", "neighbour_slot"])
def test_value_faults_make_run_incorrect(fault, monkeypatch):
    plant = (_truncate_values if fault == "truncated"
             else _neighbour_slot(monkeypatch))
    out, res = run(plant)
    assert res["correct"] is False
    window = out["ctx"]["ops"]["get"]
    assert res["checks"]["wrong_answers"]["value"] >= window > 0


def test_traced_run_leaves_device_metrics_out_off_the_chip():
    out, res = run(trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) <= listed(CELL, "per_layer")
    # no device trace on the CPU: the gather's device metrics are left out
    assert "value_gather_us_per_req" not in res["metrics"]
    assert "value_gather_roofline" not in res["metrics"]


def test_value_gather_roofline_counts_each_row_twice():
    ctx = {"trace": {"modules": {"gather_values": 1e-3,
                                 "batched_get_fused": 5.0}},
           "shard": {"device_values": 1000}, "store": {"overflow_words": 256},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100.0 * 2 * 1000 * 1024 / (1e-3 * 819e9)
    assert value_gather_roofline.read(ctx) == pytest.approx(want)
    assert value_gather_roofline.read(dict(ctx, trace=None)) is None


def test_closed_loop_times_whole_epochs():
    out, _ = run()
    ctx = out["ctx"]
    clients = small_cell(CELL)[3]["clients"]
    assert ctx["requests"] % clients == 0 and ctx["requests"] > 0
    lat = ctx["latencies_s"]
    epochs = ctx["requests"] // clients
    per_epoch = lat.reshape(epochs, clients)
    # one latency per epoch, shared by all its requests
    assert np.all(per_epoch == per_epoch[:, :1])
    assert per_epoch[:, 0].sum() <= ctx["window_s"] + 1e-9

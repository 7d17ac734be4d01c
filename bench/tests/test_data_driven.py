"""A new traffic mix, loop, configuration and per-layer metric are new
files plus new BENCHMARK.json entries: the harness runs them with no edit
to a file it already has."""
from __future__ import annotations

import json
import shutil

from bench.harness import ROOT, metric_values, result_line, run_cell
from bench.tests.small import RECORDS, SECONDS, small_cell
from bench.tests.test_faults import CPU

# a new loop: a closed loop of ``clients`` requests per drain, each epoch
# sent when the last returns
CLOSED_LOOP = '''
import time
import numpy as np


class Loop:
    def __init__(self, session, seconds, rate=None):
        self.s, self.seconds = session, seconds
        self.epoch_max = int(session.mix["clients"])

    def _epoch(self, measured):
        reqs = self.s.gen.requests(self.epoch_max)
        return self.s.client.serve(reqs, self.s.ops(reqs), measured)

    def warm_round(self):
        self._epoch(False)

    def run(self, on_window=lambda: None):
        on_window()
        t0 = end = time.perf_counter()
        while end - t0 < self.seconds:
            end = self._epoch(True)
        return t0, end

    def window_requests(self, win0):
        eps = [e for e in self.s.client.epochs if e.measured]
        return (np.concatenate([np.full(len(e.reqs), e.end - e.start)
                                for e in eps]),
                np.concatenate([e.reqs.kind for e in eps]))

    def lateness(self):
        return None
'''


def test_dummy_mix_loop_config_and_metric(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    # a new configuration: the cloud one with skewed keys
    config = json.loads(
        (ROOT / "bench" / "configs" / "cloud-uniform-1m.json").read_text())
    config.update(name="dummy-zipf", requestdistribution="zipfian",
                  zipfian_constant=0.9)
    (bench / "configs" / "dummy-zipf.json").write_text(json.dumps(config))
    bm["configs"].append({"name": "dummy-zipf", "source": "test",
                          "file": "bench/configs/dummy-zipf.json",
                          "reduced": [], "why": "test"})
    # a new mix, sent by a new loop module: GETs and SCANs mixed with
    # updates, 64 clients in a closed loop
    (bench / "traffic" / "closed.py").write_text(CLOSED_LOOP)
    (bench / "traffic" / "dummy.mixed.json").write_text(json.dumps({
        "loop": "closed", "clients": 64,
        "ops": {"get": 0.5, "scan": 0.3, "update": 0.2},
        "scan_items": [1, 6]}))
    bm["workloads"].append({"name": "dummy.cell", "config": "dummy-zipf",
                            "traffic": "dummy.mixed", "chips": 1,
                            "why": "test"})
    # a new per-layer metric, with its reader
    (bench / "metrics" / "dummy_gets.py").write_text(
        "def read(ctx):\n    return ctx['ops']['get'] or None\n")
    for m in bm["per_layer"] + bm["end_to_end"]:
        shutil.copy(ROOT / "bench" / "metrics" / f"{m['name']}.py",
                    bench / "metrics")
    bm["per_layer"].append({"name": "dummy_gets", "unit": "ops",
                            "better": "higher", "source": "program_counter",
                            "layer": "front end", "moves": "p50_ms",
                            "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    bm2, cell, config2, mix = small_cell("dummy.cell", tmp_path)
    assert config2["requestdistribution"] == "zipfian"
    out = run_cell(config2, mix, 4242, SECONDS, False, records=RECORDS,
                   log=lambda *_: None)
    res = result_line(bm2, cell, out, CPU)
    assert res["correct"] is True
    ctx = out["ctx"]
    assert ctx["ops"]["get"] > 0 and ctx["ops"]["scan"] > 0
    assert ctx["requests"] % 64 == 0          # whole closed-loop epochs
    assert set(res["metrics"]) == {"p50_ms", "setup_s"}
    layer = metric_values(bm2, cell, ctx, "per_layer", tmp_path)
    assert layer["dummy_gets"]["value"] == ctx["ops"]["get"]
    assert "front_us_per_op" not in layer      # not listed for this cell

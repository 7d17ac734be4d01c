"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json.  The run builds
the store from the cell's configuration with records made from the seed,
publishes it, warms up every shape the window reaches, measures for
``--seconds``, and checks every answer against the plain reference
(bench/harness.py).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared with its limit.  The same numbers end standard error.

The run refuses to measure anything but a TPU: with no TPU, or fewer
chips than the cell asks for, it exits non-zero and prints no result.
JAX's compilation cache is kept in ``<checkout>/.jax_cache``.

    python3 bench/run.py --workload <open cell> --sweep 2000,4000,... --seconds 5

serves the cell's traffic at each offered rate in turn after one set-up
and prints, per rate, what was completed and how the latency moved: the
knee of the open-loop cells is found this way, once, by hand.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, flush=True)


def sweep(config, mix, seed, rates, seconds):
    """Offered-rate steps after one set-up (open-loop cells only)."""
    import numpy as np
    from bench.harness import Session, Traffic, check, diff
    from bench.traffic.open import serve
    s = Session(config, mix, seed, log=log)
    warm = Traffic(s, seconds, rate=rates[0])
    log(f"setup: {s.times}, {warm.warm_rounds} warm-up rounds")
    for rate in rates:
        reqs = s.gen.open_arrivals(seconds, rate)
        n0 = len(s.client.epochs)
        programs = dict(s.compiles.counts)
        t0, end, late = serve(s.client, reqs, s.ops(reqs), 0.0, seconds)
        lat = np.concatenate([e.end - (t0 + e.reqs.due)
                              for e in s.client.epochs[n0:]])
        q = len(lat) // 4
        step = {"offered_ops_per_s": rate, "requests": len(lat),
                "completed_ops_per_s": len(lat) / (end - t0),
                "overrun_s": end - t0 - seconds,
                "epochs": len(s.client.epochs) - n0,
                "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                "p99_ms": float(np.percentile(lat, 99)) * 1e3,
                "first_quarter_p50_ms": float(np.median(lat[:q])) * 1e3,
                "last_quarter_p50_ms": float(np.median(lat[-q:])) * 1e3,
                "late_p99_ms": float(np.percentile(late, 99)) * 1e3,
                "programs": diff(s.compiles.counts, programs)}
        log("SWEEP " + json.dumps(step))
    epochs = s.close()
    chk = check(epochs, s.records, s.width)
    log(f"check: {chk['wrong']} wrong, {chk['unanswered']} unanswered")
    return 0 if chk["wrong"] == chk["unanswered"] == 0 else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (ops/s)")
    args = ap.parse_args(argv)

    # the compile cache sits at one fixed path inside the checkout; the
    # program's own cache placement (repro.compile_cache) takes it from
    # this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from bench.harness import load_cell, read_json
    bm, cell, config, mix = load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found ({devices[0].platform}); this benchmark "
              f"measures the chip only", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: the cell asks for {cell['chips']} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peaks = read_json(ROOT / "bench" / "peaks.json")
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        return sweep(config, mix, args.seed, rates, args.seconds)

    from bench.harness import result_line, run_cell
    out = run_cell(config, mix, args.seed, args.seconds, bool(args.trace),
                   peaks=peaks[kind], t_process=T_PROCESS, log=log)
    result = result_line(bm, cell, out, devices)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

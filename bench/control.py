"""Read the program and the control on the chip at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds a,b,c --seconds 10
        [--fault stale_snapshot]

For each seed, in one process: set up the cell as a measured run does,
serve one window and check it (the program's reading), plant the fault
(bench/faults.py; ``stale_snapshot``, the control, by default), serve a
second window of the same traffic and check what was served after the
plant (the fault's reading).  Prints one ``CONTROL`` JSON line per seed.
The readings are the wrong and unanswered counts the result line of
bench/run.py compares with their limits.  Measured runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(config, mix, seed, seconds, fault, records=None, log=print):
    """(program's reading, fault's reading) of one seed: each is
    {"wrong": n, "unanswered": n}."""
    from bench.faults import FAULTS
    from bench.harness import Session, Traffic, check
    s = Session(config, mix, seed, records, log=log)
    Traffic(s, seconds).loop.run()
    n_program = len(s.client.epochs)
    FAULTS[fault](s)
    Traffic(s, seconds).loop.run()
    epochs = s.close()
    program = check(epochs[:n_program], s.records, s.width)
    both = check(epochs, s.records, s.width)
    for ex in both["examples"][:3]:
        log(f"MISMATCH {ex}")
    keys = ("wrong", "unanswered")
    return ({k: program[k] for k in keys},
            {k: both[k] - program[k] for k in keys})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="stale_snapshot")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from bench.harness import load_cell
    from repro.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 2
    enable_compile_cache()
    _, cell, config, mix = load_cell(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        program, fault = readings(config, mix, seed, args.seconds, args.fault)
        print("CONTROL " + json.dumps({
            "workload": cell["name"], "seed": seed, "fault": args.fault,
            "program": program, "fault_reading": fault}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

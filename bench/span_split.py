"""Split a traced run's device idle time by the program span it falls in.

The harness's reduction (bench/trace_reduce.py) labels idle gaps by the
benchmark's own ``bench.*`` spans.  The program opens ``hc.*`` spans on
the same profiler clock (``repro.core.telemetry.span``: admit, export, the
sync steps, dispatch and the read steps, inside ``bench.drain``).
``split`` reads both kinds and gives, for one traced window:

  idle_s                 the window's idle time on the first device
  idle_by_span           {innermost span: idle seconds}: every idle
                         interval cut at each span edge, each piece given
                         to the innermost span the host was in ("none"
                         outside every span); sums to ``idle_s``
  idle_in_program_s      idle seconds with the host inside an ``hc.*`` span
  idle_in_program_share  that over the window
  span_s, span_calls     host seconds and count of each span in the window
  dispatch_covered       share of ``hc.dispatch`` that its ``hc.read.*``
                         steps cover

``from_trace_dir`` adds ``idle_gaps``, the harness's ten longest gaps
labelled with both kinds of span.

    python3 bench/span_split.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of the cell, as ``bench/run.py --trace 1`` does,
prints the run's result line and then the split as one JSON line; the
trace is kept under ``--out`` when one is given.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

PROGRAM = "hc."
READ_STEPS = ("hc.read.pack", "hc.read.launch", "hc.read.fetch",
              "hc.read.decode", "hc.read.host_scan")


def segments(spans, t0: float, t1: float) -> list[tuple[float, float, str]]:
    """``[(start, end, innermost span)]`` tiling ``[t0, t1]``: between two
    consecutive span edges the host is inside the same spans, and the
    shortest of them is the innermost ("none" when there is none)."""
    points = sorted({t0, t1, *(x for _, s, d in spans for x in (s, s + d)
                               if t0 < x < t1)})
    starts = sorted((s, i) for i, (_, s, _d) in enumerate(spans))
    ends = sorted((s + d, i) for i, (_, s, d) in enumerate(spans))
    active: set[int] = set()
    si = ei = 0
    out = []
    for a, b in zip(points, points[1:]):
        while si < len(starts) and starts[si][0] <= a:
            active.add(starts[si][1])
            si += 1
        while ei < len(ends) and ends[ei][0] <= a:
            active.discard(ends[ei][1])
            ei += 1
        name = (spans[min(active, key=lambda i: spans[i][2])][0]
                if active else "none")
        out.append((a, b, name))
    return out


def idle_gaps(ops, modules, t0: float, t1: float) -> list[tuple[float, float]]:
    """The first device's idle intervals in ``[t0, t1]``, as
    ``trace_reduce.reduce`` finds them."""
    from bench.trace_reduce import _clip, union
    devices = sorted(ops) or sorted(modules)
    if not devices:
        return [(t0, t1)]
    dev = devices[0]
    busy = union(_clip(ops.get(dev) or modules.get(dev, []), t0, t1))
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def split(ops, modules, spans, window: tuple[float, float]) -> dict:
    """The split of one traced window (times in ns in, seconds out)."""
    t0, t1 = window
    segs = segments(spans, t0, t1)
    idle: dict[str, float] = defaultdict(float)
    j = 0
    for ga, gb in idle_gaps(ops, modules, t0, t1):
        while segs[j][1] <= ga:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < gb:
            a, b, name = segs[k]
            idle[name] += (min(b, gb) - max(a, ga)) * 1e-9
            k += 1
    span_s: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    for name, s, d in spans:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            span_s[name] += (b - a) * 1e-9
            calls[name] += 1
    window_s = (t1 - t0) * 1e-9
    in_program = sum(v for k, v in idle.items() if k.startswith(PROGRAM))
    dispatch = span_s.get("hc.dispatch", 0.0)
    return {
        "window_s": window_s,
        "idle_s": sum(idle.values()),
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_in_program_s": in_program,
        "idle_in_program_share": in_program / window_s if window_s else None,
        "span_s": dict(sorted(span_s.items(), key=lambda kv: -kv[1])),
        "span_calls": dict(calls),
        "dispatch_covered": (sum(span_s.get(n, 0.0) for n in READ_STEPS)
                             / dispatch if dispatch else None),
    }


def from_trace_dir(trace_dir: str) -> dict:
    """The split of the ``bench.window`` of the trace under ``trace_dir``,
    with its longest idle gaps."""
    from bench import trace_reduce
    ops, modules, spans = trace_reduce.from_profile(
        trace_reduce.find_xplane(trace_dir), span_prefix=("bench.", PROGRAM))
    window = trace_reduce.window_of(spans)
    out = split(ops, modules, spans, window)
    out["idle_gaps"] = trace_reduce.reduce(ops, modules, spans,
                                           window)["idle_gaps"]
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import shutil
    import sys
    import tempfile
    root = Path(__file__).resolve().parents[1]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="directory to keep the trace in")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    import jax
    from bench.harness import load_cell, read_json, result_line, run_cell
    from repro.compile_cache import enable_compile_cache
    bm, cell, config, mix = load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"span_split: no TPU found ({devices[0].platform})",
              file=sys.stderr)
        return 2
    peaks = read_json(root / "bench" / "peaks.json")[devices[0].device_kind]
    enable_compile_cache()
    trace_dir = args.out or tempfile.mkdtemp(prefix="span_split_")
    out = run_cell(config, mix, args.seed, args.seconds, True, peaks=peaks,
                   keep_trace=trace_dir)
    print(json.dumps(result_line(bm, cell, out, devices)), flush=True)
    print(json.dumps({"span_split": from_trace_dir(trace_dir)}), flush=True)
    if args.out is None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

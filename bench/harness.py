"""Set-up, measured window and check of one benchmark cell.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration (``bench/configs/<config>.json``: records, key skew, store
and service settings, guarantees) and a traffic mix
(``bench/traffic/<traffic>.json``, read by ``bench/traffic/generator.py``;
its ``loop`` names the module under ``bench/traffic/`` that sends the
requests, such as ``open.py``).  ``run_cell`` does, in order:

  1. make the records from the seed and load them with one ``put`` each,
     in the generator's random order;
  2. publish the snapshot;
  3. warm up every shape the window reaches, through the service alone:
     each read batch bucket (``warm_reads``), write epochs of every size
     and page-table load a drain of the window can carry
     (``warm_writes``), then rounds of the mix's own traffic until no
     program is built any more;
  4. measure: the loop module drives ``HoneycombService.submit`` /
     ``drain`` for ``seconds`` seconds;
  5. read the device's peak memory, free the store, and check every
     answer served (warm-up included) against ``bench/reference.py``.

Nothing here reaches into the store beyond its public facade, its
service and the meters it exposes (``pipeline_stats``, ``sync_stats``,
``cache_stats``, the service's ``stats``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

from bench import trace_reduce  # noqa: E402
from bench.reference import Reference  # noqa: E402
from bench.traffic.generator import (DELETE, GET, KINDS, PUT, SCAN,  # noqa: E402
                                     UPDATE, Generator, Requests, key_bytes)

# warm-up rounds of the mix's own traffic: at least WARMUP_ROUNDS, then
# until QUIET_ROUNDS in a row built no program, at most WARMUP_MAX_ROUNDS
WARMUP_ROUNDS = 8
QUIET_ROUNDS = 3
WARMUP_MAX_ROUNDS = 30
# write epochs of each size served as the mix writes (``warm_writes``):
# how many leaves' logs a drain's writes fill varies from drain to drain
PLAIN_WRITE_EPOCHS = 4


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_values(bm: dict, cell: dict, ctx: dict, kind: str,
                  root: Path = ROOT) -> dict:
    """The cell's metrics of one kind ("end_to_end" or "per_layer"), each
    read by ``bench/metrics/<name>.py``; a reader that finds nothing is
    left out."""
    out = {}
    for m in bm[kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT):
    """(BENCHMARK.json, cell, configuration, mix) for a workload name.
    The mix carries ``_root``, where its loop module is looked up."""
    bm = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bm["configs"] if c["name"] == cell["config"])
    config = read_json(root / entry["file"])
    mix = read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    mix["_root"] = str(root)
    return bm, cell, config, mix


def loop_module(mix: dict):
    """The module that sends the mix's requests:
    ``bench/traffic/<loop>.py``."""
    root = Path(mix.get("_root", ROOT))
    return load_module(root / "bench" / "traffic" / f"{mix['loop']}.py",
                       f"bench_loop_{mix['loop']}")


# ----------------------------------------------------------------- store
def build_store(config: dict):
    """The store the configuration describes, behind its public facade:
    one shard and one replica, the only layout the program serves from a
    single chip."""
    from repro.core import HoneycombConfig, HoneycombStore
    if int(config.get("shards", 1)) != 1 or int(config.get("replicas", 1)) != 1:
        raise ValueError("the harness runs one shard with one replica")
    return HoneycombStore(HoneycombConfig(**config["store"]))


def meters(store, svc) -> dict:
    """The program's meters as plain numbers (differenced over a window)."""
    def fields(obj):
        return {f.name: getattr(obj, f.name)
                for f in dataclasses.fields(obj)
                if isinstance(getattr(obj, f.name), (int, float))}
    return {"sched": fields(svc.stats), "shard": fields(store.pipeline_stats),
            "sync": fields(store.sync_stats),
            "cache": fields(store.cache_stats), "syncs": svc.syncs}


def diff(after, before):
    if isinstance(after, dict):
        return {k: diff(after[k], before[k]) for k in after}
    return after - before


# -------------------------------------------------------------- requests
def make_ops(reqs: Requests, width: int) -> list:
    """The typed ops (core/api.py) of a request stream."""
    from repro.core import Delete, Get, Put, Scan, Update
    keys = key_bytes(reqs.key, width)
    his = key_bytes(reqs.hi, width)
    out = []
    for j, kind in enumerate(reqs.kind.tolist()):
        if kind == GET:
            out.append(Get(keys[j]))
        elif kind == SCAN:
            out.append(Scan(keys[j], his[j], expected_items=int(reqs.items[j])))
        elif kind == PUT:
            out.append(Put(keys[j], reqs.value[j].tobytes()))
        elif kind == UPDATE:
            out.append(Update(keys[j], reqs.value[j].tobytes()))
        else:
            out.append(Delete(keys[j]))
    return out


class Spans:
    """Host-clock totals of the benchmark's own spans; each span is also a
    ``jax.profiler.TraceAnnotation`` named ``bench.<name>`` while a trace
    is being recorded."""

    def __init__(self, traced: bool):
        self.totals: dict[str, float] = {}
        self.traced = traced
        if traced:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.traced:
            with self._ann("bench." + name):
                yield
        else:
            yield
        self.totals[name] = self.totals.get(name, 0.0) + \
            time.perf_counter() - t0


@dataclasses.dataclass
class Epoch:
    """One ``drain``: its requests, their answers and when it ran.  An
    answer is ``(status, value or SCAN items)``, or None when the request
    was left unanswered — plain data, so that the window's answers add
    little for Python's collector to walk."""
    reqs: Requests
    answers: list
    start: float               # host clock when the first was submitted
    end: float                 # host clock at the end of the drain
    measured: bool


class Client:
    """Sends requests through ``HoneycombService`` and keeps every epoch's
    answers for the check."""

    def __init__(self, svc, spans: Spans):
        self.svc = svc
        self.spans = spans
        self.epochs: list[Epoch] = []

    def serve(self, reqs: Requests, ops: list, measured: bool) -> float:
        start = time.perf_counter()
        with self.spans("submit"):
            tickets = [self.svc.submit(op) for op in ops]
        with self.spans("drain"):
            self.svc.drain()
        end = time.perf_counter()
        with self.spans("resolve"):
            answers = []
            for t in tickets:
                if t.done:
                    r = t.result()
                    answers.append((r.status, r.value if r.items is None
                                    else r.items))
                else:
                    answers.append(None)
        self.epochs.append(Epoch(reqs, answers, start, end, measured))
        return end


# ----------------------------------------------------------------- warm-up
def pow2_upto(n: int) -> list[int]:
    out, b = [], 1
    while b <= n:
        out.append(b)
        b *= 2
    return out


def warm_reads(client: Client, gen: Generator, mix: dict, batch: int,
               width: int) -> None:
    """One epoch of each power-of-two read batch bucket up to the batch
    size, per read kind of the mix."""
    for kind in ("get", "scan"):
        if mix["ops"].get(kind, 0) <= 0:
            continue
        for b in pow2_upto(batch):
            reqs = gen.requests(b)
            reqs.kind[:] = KINDS.index(kind)
            if kind == "get":
                reqs.hi[:] = reqs.key
                reqs.items[:] = 1
            client.serve(reqs, make_ops(reqs, width), measured=False)


def warm_writes(client: Client, gen: Generator, writes_max: int,
                log_cap: int, width: int) -> int:
    """Write epochs of every shape the window's syncs can take; returns
    how many were served.

    A sync's programs are sized by its dirty rows and its page-table
    commands, each rounded up to a power of two, and which pair a drain
    needs depends on how many leaves its writes dirty and how many log
    merges they happen to force: about one per ``log_cap`` writes, more
    or fewer from drain to drain.  Traffic alone meets the rarer pairs
    only in the window, where building a program stalls the loop for
    about a second; and after a host stall an open loop's next drain
    carries the whole backlog.  So for each power of two ``b`` of writes
    up to the first at or above ``writes_max``, set-up serves
    ``PLAIN_WRITE_EPOCHS`` epochs of the mix's own writes at each of
    ``b``, ``b + b / log_cap`` (rows just past the bucket ``b`` with few
    merges, a pair the window meets after a stall) and ``1.5 b``; then
    epochs of ``b`` writes with each power of two of hot keys up to an
    eighth of them (at least 8).
    A hot key is updated ``log_cap + 1`` times in the epoch, which forces
    at least one merge of its leaf's log: a node moved, a page-table
    command sent.  Every write goes through the service and is checked
    with the rest."""
    n = 0
    for b in pow2_upto(2 * writes_max - 1):
        counts = sorted({b, b + max(1, b // log_cap), 3 * b // 2})
        for w in [w for w in counts for _ in range(PLAIN_WRITE_EPOCHS)]:
            reqs = gen.writes(w)
            client.serve(reqs, make_ops(reqs, width), measured=False)
            n += 1
        for hot in pow2_upto(max(8, b // 8)):
            reqs = gen.writes(b, hot, log_cap + 1)
            client.serve(reqs, make_ops(reqs, width), measured=False)
            n += 1
    return n


# ------------------------------------------------------------------- check
def check(epochs: list[Epoch], records, width: int) -> dict:
    """Replay the served epochs on the reference: each epoch's writes in
    submission order, then its reads (the store admits an epoch's writes,
    syncs, then serves its reads).  Counts answers that differ from the
    reference and requests left unanswered."""
    ref = Reference(records.values, width)
    wrong = unanswered = 0
    examples: list[str] = []
    for ep in epochs:
        r = ep.reqs
        kinds = r.kind.tolist()
        keys = r.key.tolist()
        for j, kind in enumerate(kinds):
            if kind in (PUT, UPDATE):
                ref.write(keys[j], r.value[j].tobytes())
            elif kind == DELETE:
                ref.write(keys[j], None)
        his = r.hi.tolist()
        for j, (kind, answer) in enumerate(zip(kinds, ep.answers)):
            if answer is None:
                unanswered += 1
                continue
            status, got = answer
            if kind == GET:
                want = ref.get(keys[j])
                ok = got == want and status == (
                    "ok" if want is not None else "not_found")
            elif kind == SCAN:
                want = ref.scan(keys[j], his[j])
                got = [tuple(x) for x in (got or [])]
                ok = status == "ok" and got == want
            else:
                want, got = "ok", status
                ok = got == want
            if not ok:
                wrong += 1
                if len(examples) < 5:
                    examples.append(f"{KINDS[kind]} id {keys[j]}: got "
                                    f"{got!r} want {want!r}")
    return {"wrong": wrong, "unanswered": unanswered, "examples": examples}


# --------------------------------------------------------------- set-up
class CompileCounter:
    """Counts programs JAX traced, compiled or fetched from the persistent
    cache (none should be in the window).  ``counts`` accumulate;
    ``fresh()`` is the compiled plus fetched total, the number warm-up
    waits to see stop growing."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_retrieval_time_sec": "fetched"}

    def __init__(self):
        import jax.monitoring
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if name in self.EVENTS:
            self.counts[self.EVENTS[name]] += 1

    def fresh(self) -> int:
        return self.counts["compiled"] + self.counts["fetched"]


class GcWatch:
    """Python's collections while ``on``: count and seconds per
    generation (a long pause in the window shows here)."""

    def __init__(self):
        self.on = False
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.longest = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            dt = time.perf_counter() - self._t0
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += dt
            self.longest = max(self.longest, dt)

    def close(self):
        gc.callbacks.remove(self._cb)


class Session:
    """A loaded and published store with its service."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 records: int | None = None, traced: bool = False,
                 log=print):
        import jax
        from repro.core import HoneycombService, ServiceConfig
        self.config, self.mix, self.log = config, mix, log
        self.width = int(config["key_bytes"])
        self.gen = Generator(config, mix, seed, records)
        self.records = self.gen.records()
        self.store = build_store(config)
        t0 = time.perf_counter()
        put = self.store.put
        values = self.records.values
        for k, i in zip(key_bytes(2 * self.records.order, self.width),
                        self.records.order.tolist()):
            put(k, values[i].tobytes())
        t1 = time.perf_counter()
        jax.block_until_ready(self.store.export_snapshot())
        t2 = time.perf_counter()
        self.svc = HoneycombService(self.store,
                                    ServiceConfig(**config["service"]))
        self.spans = Spans(traced)
        self.client = Client(self.svc, self.spans)
        self.compiles = CompileCounter()
        self.times = {"load_s": t1 - t0, "publish_s": t2 - t1}

    def ops(self, reqs: Requests) -> list:
        return make_ops(reqs, self.width)

    def meters(self) -> dict:
        return meters(self.store, self.svc)

    def close(self) -> list[Epoch]:
        """Free the program's state; the served epochs stay for the
        check."""
        epochs = self.client.epochs
        del self.client, self.svc, self.store
        return epochs


# --------------------------------------------------------------- the cell
class Traffic:
    """The mix's loop for one window of ``seconds``, warmed up: read
    buckets, write epochs up to the largest drain the loop can produce,
    then rounds of the mix itself until no program has been compiled or
    fetched from the compile cache for ``QUIET_ROUNDS`` rounds in a row
    (at least ``WARMUP_ROUNDS``, at most ``WARMUP_MAX_ROUNDS``)."""

    def __init__(self, session: Session, seconds: float,
                 rate: float | None = None):
        s, mix = session, session.mix
        t0 = time.perf_counter()
        self.loop = loop_module(mix).Loop(s, seconds, rate)
        warm_reads(s.client, s.gen, mix, s.svc.cfg.batch_size, s.width)
        write_share = sum(mix["ops"].get(k, 0.0)
                          for k in ("put", "update", "delete"))
        self.write_epochs = 0
        if write_share > 0:
            writes_max = max(1, int(self.loop.epoch_max * write_share))
            self.write_epochs = warm_writes(
                s.client, s.gen, writes_max,
                int(s.config["store"]["log_cap"]), s.width)
        t1 = time.perf_counter()
        cc = s.compiles
        quiet, i = 0, 0
        while i < WARMUP_MAX_ROUNDS and (i < WARMUP_ROUNDS
                                         or quiet < QUIET_ROUNDS):
            before = cc.fresh()
            self.loop.warm_round()
            quiet = quiet + 1 if cc.fresh() == before else 0
            i += 1
        self.warm_rounds = i
        s.times.update(warmup_shapes_s=t1 - t0,
                       warmup_rounds_s=time.perf_counter() - t1)


def run_cell(config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, records: int | None = None,
             peaks: dict | None = None, t_process: float | None = None,
             on_ready=None, keep_trace: str | None = None, log=print) -> dict:
    """One run of a cell; returns what the result line is made of.
    ``records`` shrinks the configuration (tests); ``on_ready(session)``
    runs after set-up, before the window (the fault tests plant faults
    there); ``keep_trace`` names a directory to write the trace to and
    keep."""
    import jax
    t_process = time.perf_counter() if t_process is None else t_process
    s = Session(config, mix, seed, records, trace, log)
    client = s.client
    traffic = Traffic(s, seconds)
    loop = traffic.loop
    if on_ready is not None:
        on_ready(s)
    # what set-up made and keeps (the request pool, the load's leftovers)
    # is moved out of the collector's generations, as a long-running
    # server does after start-up: a full collection in the window then
    # walks what the window allocates, not what set-up left behind
    gc.collect()
    gc.freeze()
    gcw = GcWatch()
    trace_dir = None
    if trace:
        import tempfile
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: ours and XLA's
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # one more round, unmeasured, so that the profiler's own start-up
        # stays out of the window
        loop.warm_round()
    programs = dict(s.compiles.counts)
    at_open = {}

    def on_window():
        # the meters, spans and epochs of the window are differences from
        # here: an open loop's lead-in is set-up
        at_open.update(meters=s.meters(), spans=dict(s.spans.totals),
                       epochs=len(client.epochs))

    gcw.on = True
    win0, win1 = loop.run(on_window)
    gcw.on = False
    gcw.close()
    gc.unfreeze()
    programs = diff(s.compiles.counts, programs)
    m = diff(s.meters(), at_open["meters"])
    if trace:
        jax.profiler.stop_trace()
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    height = int(s.store.tree.height)
    spans = {k: v - at_open["spans"].get(k, 0.0)
             for k, v in s.spans.totals.items()}
    setup_s = win0 - t_process
    t = s.times
    log(f"setup_s {setup_s}: load_s {t['load_s']} publish_s "
        f"{t['publish_s']} warmup_shapes_s {t['warmup_shapes_s']} "
        f"({traffic.write_epochs} write epochs) warmup_rounds_s "
        f"{t['warmup_rounds_s']} ({traffic.warm_rounds} rounds)")

    lat, kinds = loop.window_requests(win0)
    window_s = win1 - win0
    counts = {k: int((kinds == i).sum()) for i, k in enumerate(KINDS)}
    log(f"window_s {window_s}: {len(lat)} requests {counts}; "
        f"programs in the window {programs}; collections in the window "
        f"{gcw.count} taking {gcw.seconds} s, longest {gcw.longest} s")
    eps = [e for e in s.client.epochs if e.measured]
    longest = sorted(((e.end - e.start, len(e.reqs)) for e in eps),
                     reverse=True)[:5]
    pause = max((b.start - a.end for a, b in zip(eps, eps[1:])), default=0.0)
    log(f"epochs in the window: {len(eps)}; longest (s, requests) {longest}; "
        f"longest pause between epochs {pause} s")
    if len(lat):
        q = np.percentile(lat, [50, 90, 95, 99, 99.9]) * 1e3
        log(f"latency ms in the window: p50 {q[0]} p90 {q[1]} p95 {q[2]} "
            f"p99 {q[3]} p99.9 {q[4]} max {lat.max() * 1e3}")
    late = loop.lateness()
    if late is not None and len(late):
        log(f"generator lateness in the window: p50_ms "
            f"{np.percentile(late, 50) * 1e3} p99_ms "
            f"{np.percentile(late, 99) * 1e3} max_ms {late.max() * 1e3}")

    tr = None
    if trace:
        import shutil
        ops_ev, mod_ev, span_ev = trace_reduce.from_profile(
            trace_reduce.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        tr = trace_reduce.reduce(ops_ev, mod_ev, span_ev,
                                 trace_reduce.window_of(span_ev))
        log(f"trace: busy_s {tr['busy_s']} window_s {tr['window_s']} "
            f"modules {tr['modules']} launches {tr['module_calls']}")

    ctx = {
        "requests": int(len(lat)), "ops": counts,
        "reads": counts["get"] + counts["scan"],
        "writes": counts["put"] + counts["update"] + counts["delete"],
        "syncs": m["syncs"], "sched": m["sched"], "shard": m["shard"],
        "sync": m["sync"], "cache": m["cache"], "window_s": window_s,
        "front_s": spans.get("submit", 0.0) + spans.get("resolve", 0.0),
        "front_requests": sum(len(e.reqs)
                              for e in client.epochs[at_open["epochs"]:]),
        "store": config["store"], "tree_height": height, "peaks": peaks,
        "trace": tr, "setup_s": setup_s, "latencies_s": lat,
    }
    epochs = s.close()
    t_check = time.perf_counter()
    chk = check(epochs, s.records, s.width)
    log(f"check: {chk['wrong']} wrong, {chk['unanswered']} unanswered of "
        f"{sum(len(e.reqs) for e in epochs)} served (warm-up included), "
        f"{time.perf_counter() - t_check} s")
    for ex in chk["examples"]:
        log(f"MISMATCH {ex}")
    return {"check": chk, "ctx": ctx, "memory_peak": mem,
            "served": sum(len(e.reqs) for e in epochs),
            "programs": programs}


def result_line(bm: dict, cell: dict, out: dict, devices) -> dict:
    """The run's result object (the last line of standard output): the
    cell's end-to-end metrics untraced, its per-layer metrics traced, the
    programs traced, compiled or fetched inside the window (none, as a
    rule: one such program stalls the loop), and last the numbers
    compared with their limits."""
    ctx, chk = out["ctx"], out["check"]
    tr = ctx["trace"]
    metrics = metric_values(bm, cell, ctx,
                            "per_layer" if tr is not None else "end_to_end")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak"]}
    result = {"correct": chk["wrong"] == 0 and chk["unanswered"] == 0,
              "attempted": out["served"],
              "failed": chk["wrong"] + chk["unanswered"],
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["programs_in_window"] = out["programs"]
    result["checks"] = {
        "wrong_answers": {"value": chk["wrong"], "limit": 0},
        "unanswered": {"value": chk["unanswered"], "limit": 0}}
    return result

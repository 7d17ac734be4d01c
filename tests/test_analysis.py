"""honeylint + kernel checker + EpochSan (repro/analysis).

Three layers, mirroring the analysis package:

  * lint rules — each rule catches a known-bad fixture (written to
    tmp_path and run through ``lint_file``), and the repo at HEAD lints
    clean under the shipped baseline;
  * kernel checker — ``check_jaxpr`` flags a deliberately mis-aliased
    in-place scatter, a split "fused" path, an f64 leak, a host
    callback, and a VMEM-budget overrun; the real kernel registry
    traces clean;
  * EpochSan — each injected protocol violation (unflipped standby
    read, pinned-epoch GC, follower freshness, stale cache rows,
    unflipped standby after export) raises ``EpochSanViolation`` at the
    seam, and the same flows run clean without the injected bug.
"""
from __future__ import annotations

import json
import os
import textwrap

import pytest

from repro.analysis import epochsan, kernel_check, lint
from repro.analysis.lint import Finding


# --------------------------------------------------------------------------
# lint rules against bad fixtures
# --------------------------------------------------------------------------

def _lint_src(tmp_path, rel, source):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return lint.lint_file(path, root=tmp_path)


def _rules(findings):
    return {f.rule for f in findings}


def test_no_raw_clock_flags_time_calls(tmp_path):
    fs = _lint_src(tmp_path, "mod.py", """\
        import time

        def f():
            t0 = time.perf_counter()
            return time.time() - t0
    """)
    assert [f.rule for f in fs] == ["no-raw-clock", "no-raw-clock"]
    assert "telemetry.CLOCK" in fs[0].message


def test_no_raw_clock_exempts_the_clock_owner(tmp_path):
    fs = _lint_src(tmp_path, "core/telemetry.py", """\
        import time

        def now():
            return time.perf_counter()
    """)
    assert fs == []


def test_inline_suppression_with_reason(tmp_path):
    fs = _lint_src(tmp_path, "mod.py", """\
        import time

        def f():
            # honeylint: disable=no-raw-clock -- calibrating CLOCK itself
            return time.perf_counter()
    """)
    assert fs == []


def test_no_bare_except_flags_broad_handlers(tmp_path):
    fs = _lint_src(tmp_path, "mod.py", """\
        def f():
            try:
                g()
            except:
                pass
            try:
                g()
            except Exception:
                pass
            try:
                g()
            except (ValueError, KeyError):
                raise
    """)
    assert [f.rule for f in fs] == ["no-bare-except", "no-bare-except"]


def test_no_aliased_publish_flags_live_array_asarray(tmp_path):
    # jnp.asarray of an attribute chain (live host heap) inside a
    # publish-path function of a publish file — the PR 1 flake class
    fs = _lint_src(tmp_path, "core/shard.py", """\
        import jax.numpy as jnp

        def _publish_image(h):
            rows = h.ntype
            return jnp.asarray(rows)
    """)
    assert _rules(fs) == {"no-aliased-publish"}


def test_no_aliased_publish_passes_copied_arrays(tmp_path):
    fs = _lint_src(tmp_path, "core/shard.py", """\
        import jax.numpy as jnp
        import numpy as np

        def _publish_image(h):
            rows = np.array(h.ntype, copy=True)
            return jnp.asarray(rows)

        def helper(h):
            return jnp.asarray(h.ntype)   # not a publish-path function
    """)
    assert fs == []


def test_no_magic_image_offsets_flags_literal_indices(tmp_path):
    fs = _lint_src(tmp_path, "src/repro/kernels/bad.py", """\
        def kern(rows_ref, out_ref):
            r = rows_ref[0]
            out_ref[r, 1217 + 3] = 1
    """)
    assert _rules(fs) == {"no-magic-image-offsets"}
    assert "1217" in fs[0].message


def test_no_magic_image_offsets_passes_layout_derived(tmp_path):
    fs = _lint_src(tmp_path, "src/repro/kernels/good.py", """\
        def kern(rows_ref, out_ref, *, offs):
            r = rows_ref[0]
            out_ref[r, offs[0] + 3] = 1     # layout-derived
            out_ref[r, 4] = 2               # small lane arithmetic is fine
    """)
    assert fs == []


def test_stats_must_collect(tmp_path):
    fs = _lint_src(tmp_path, "mod.py", """\
        import dataclasses

        @dataclasses.dataclass
        class OrphanStats:
            n: int = 0

        @dataclasses.dataclass
        class WiredStats:
            n: int = 0

            def collect(self):
                return []

        @dataclasses.dataclass
        class NotAStatsThing:
            n: int = 0
    """)
    assert [f.rule for f in fs] == ["stats-must-collect"]
    assert "OrphanStats" in fs[0].message


def test_baseline_suppresses_by_rule_and_path(tmp_path):
    (tmp_path / "mod.py").write_text("import time\nt = time.time()\n")
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps(
        [{"rule": "no-raw-clock", "path": "mod.py", "reason": "test debt"}]))
    findings, suppressed = lint.run_lint(
        ("mod.py",), root=tmp_path, baseline=bp, golden=None)
    assert findings == [] and suppressed == 1
    # without the baseline the finding comes back
    findings, suppressed = lint.run_lint(
        ("mod.py",), root=tmp_path, baseline=None, golden=None)
    assert _rules(findings) == {"no-raw-clock"} and suppressed == 0


def test_repo_at_head_lints_clean():
    """The acceptance gate: zero findings on HEAD with <= 2 baselined
    suppressions (the shipped baseline has exactly one justified entry)."""
    findings, suppressed = lint.run_lint()
    assert findings == [], "\n".join(map(str, findings))
    assert suppressed <= 2
    base = lint.load_baseline()
    assert all(b.get("reason") for b in base), "baseline entries need reasons"
    # with NO baseline the only exposure is the deliberately-kept (and
    # justified) broad handler in the dry-run sweep driver
    bare, n = lint.run_lint(baseline=None)
    assert {(f.rule, f.path) for f in bare} <= {
        ("no-bare-except", "src/repro/launch/dryrun.py")} and n == 0


def test_golden_schema_pin_roundtrip(tmp_path):
    golden = tmp_path / "golden.json"
    assert _rules(lint.check_golden(golden)) == {"schema-golden-drift"}
    lint.pin_golden(golden)
    assert lint.check_golden(golden) == []
    # tamper: a drifted fingerprint must name what changed
    blob = json.loads(golden.read_text())
    blob["sha256"] = "0" * 64
    blob["detail"]["image_words"] = -1
    golden.write_text(json.dumps(blob))
    fs = lint.check_golden(golden)
    assert _rules(fs) == {"schema-golden-drift"}
    assert "image_words" in fs[0].message


def test_repo_golden_matches_current_schema():
    assert lint.check_golden() == []


# --------------------------------------------------------------------------
# kernel checker
# --------------------------------------------------------------------------

def _tiny_pallas_scatter():
    """A pallas_call with NO input_output_aliases — the mis-aliased
    in-place scatter the checker exists to flag."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(dst_ref, upd_ref, out_ref):
        out_ref[...] = dst_ref[...] + upd_ref[...]

    def scatter(dst, upd):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        )(dst, upd)

    a = jax.ShapeDtypeStruct((16, 8), jnp.uint32)
    return scatter, a


def test_kernel_check_flags_missing_inplace_alias():
    import jax
    scatter, a = _tiny_pallas_scatter()
    jaxpr = jax.make_jaxpr(scatter)(a, a)
    fs = kernel_check.check_jaxpr("bad.scatter", "x.py", jaxpr.jaxpr,
                                  in_place=True)
    assert _rules(fs) == {"kernel-inplace-alias"}
    # the same jaxpr audited as a plain kernel is clean
    assert kernel_check.check_jaxpr("ok", "x.py", jaxpr.jaxpr) == []


def test_kernel_check_flags_split_fused_path():
    import jax
    scatter, a = _tiny_pallas_scatter()
    jaxpr = jax.make_jaxpr(lambda d, u: scatter(scatter(d, u), u))(a, a)
    fs = kernel_check.check_jaxpr("split.fused", "x.py", jaxpr.jaxpr,
                                  fused=True)
    assert _rules(fs) == {"kernel-single-dispatch"}
    assert "2 pallas_call" in fs[0].message


def test_kernel_check_flags_vmem_budget_overrun():
    import jax
    scatter, a = _tiny_pallas_scatter()
    jaxpr = jax.make_jaxpr(scatter)(a, a)
    fs = kernel_check.check_jaxpr("fat.kernel", "x.py", jaxpr.jaxpr,
                                  vmem_budget=64)
    assert _rules(fs) == {"kernel-vmem-budget"}


def test_kernel_check_flags_f64():
    import jax
    import jax.numpy as jnp
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jax.ShapeDtypeStruct((8,), jnp.float32))
    fs = kernel_check.check_jaxpr("leaky.f64", "x.py", jaxpr.jaxpr)
    assert "kernel-no-f64" in _rules(fs)


def test_kernel_check_flags_host_callback():
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((8,), jnp.float32), x)

    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32))
    fs = kernel_check.check_jaxpr("chatty", "x.py", jaxpr.jaxpr)
    assert "kernel-no-callback" in _rules(fs)


def test_kernel_registry_traces_clean():
    """Every real Pallas entry point traces and passes all kernel rules
    at the default geometry and VMEM budget."""
    entries = kernel_check.kernel_entries()
    assert {e.name for e in entries} == {
        "delta_scatter.snapshot_delta_scatter",
        "delta_scatter.log_replay_scatter",
        "fused_read.batched_get_fused", "fused_read.batched_scan_fused",
        "key_search.key_search", "key_search.key_search_image",
        "leaf_merge.leaf_merge", "paged_attention.paged_attention"}
    fs = kernel_check.run_kernel_checks()
    assert fs == [], "\n".join(map(str, fs))


# --------------------------------------------------------------------------
# EpochSan
# --------------------------------------------------------------------------

def _seeded_shard(cfg=None, n=20):
    from repro.core.shard import StoreShard
    s = StoreShard(cfg) if cfg is not None else StoreShard()
    for i in range(n):
        s.put(f"k{i:03d}".encode(), b"v" * 8)
    s.export_snapshot()
    return s


def test_epochsan_clean_lifecycle_counts_checks():
    with epochsan.enabled() as san:
        s = _seeded_shard()
        assert s.get_batch([b"k001"]) == [b"v" * 8]
        for i in range(20):
            s.put(f"k{i:03d}".encode(), b"w" * 8)
        s.begin_export()
        s.flip()
        s.collect_garbage()
        assert s.get_batch([b"k001"]) == [b"w" * 8]
    assert san.violations == []
    st = san.stats
    assert st.read_checks > 0 and st.stagings > 0 and st.flips > 0
    assert st.gc_audits > 0 and st.violations == 0


def test_epochsan_catches_standby_read():
    with epochsan.enabled() as san:
        s = _seeded_shard()
        s.put(b"k000", b"x" * 8)
        s.begin_export()            # staged, NOT flipped
        with pytest.raises(epochsan.EpochSanViolation) as ei:
            s._device_get(s._standby, [b"k000"])
        assert ei.value.kind == epochsan.STANDBY_READ
    assert san.stats.violations == 1


def test_epochsan_nonstrict_records_without_raising():
    with epochsan.enabled(strict=False) as san:
        s = _seeded_shard()
        s.put(b"k000", b"x" * 8)
        s.begin_export()
        s._device_get(s._standby, [b"k000"])   # recorded, not raised
    assert [v.kind for v in san.violations] == [epochsan.STANDBY_READ]
    assert san.report()[0]["kind"] == epochsan.STANDBY_READ


def test_epochsan_catches_pinned_epoch_gc(monkeypatch):
    from repro.core import gc as gc_mod
    from repro.core.config import HoneycombConfig

    with epochsan.enabled() as san:
        # "explicit" pins the exported snapshot's accelerator epoch; the
        # default on_read policy holds no pin, so nothing would be
        # wrongly reclaimable there
        s = _seeded_shard(HoneycombConfig(sync_policy="explicit"), n=40)
        for i in range(40):
            s.update(f"k{i:03d}".encode(), b"w" * 8)   # old versions -> gc
        assert s.tree.gc.list, "updates must have deferred garbage"
        # inject the bug: a GC that ignores the pinned epoch window
        monkeypatch.setattr(gc_mod.GarbageCollector, "_reclaimable",
                            lambda self, e: True)
        with pytest.raises(epochsan.EpochSanViolation) as ei:
            s.collect_garbage()
        assert ei.value.kind == epochsan.PINNED_EPOCH_GC
    assert san.stats.violations >= 1


def test_epochsan_catches_follower_freshness(monkeypatch):
    from repro.core.config import ReplicationConfig
    from repro.core.replica import ReplicaGroup
    from repro.core.shard import StoreShard

    with epochsan.enabled() as san:
        g = ReplicaGroup(StoreShard(), ReplicationConfig(replicas=2))
        for i in range(20):
            g.put(f"k{i:03d}".encode(), b"v" * 8)
        g.export_snapshot()
        assert g.get_batch([b"k001"], replica=1) == [b"v" * 8]
        assert san.stats.dispatch_checks > 0 and not san.violations

        # a paused follower falls behind the primary's published epoch;
        # then the freshness rule itself "breaks" and routes to it anyway
        g.pause_follower(1)
        for i in range(20):
            g.put(f"k{i:03d}".encode(), b"w" * 8)
        g.export_snapshot()
        g.resume_follower(1)
        monkeypatch.setattr(ReplicaGroup, "_covers",
                            lambda self, f: True)
        with pytest.raises(epochsan.EpochSanViolation) as ei:
            g.get_batch([b"k001"], replica=1)
        assert ei.value.kind == epochsan.FOLLOWER_FRESHNESS


def test_epochsan_catches_stale_cache_rows():
    with epochsan.enabled() as san:
        s = _seeded_shard()
        s.put(b"k000", b"w" * 8)
        s.tree.pt.remap(0, s.tree.pt.lookup(0))    # remap hits the cache
        s.cache.refresh = lambda tree: None        # "forgot to refresh"
        with pytest.raises(epochsan.EpochSanViolation) as ei:
            s.begin_export()
        assert ei.value.kind == epochsan.STALE_CACHE_ROWS
    assert san.stats.violations == 1


def test_epochsan_remap_then_refresh_stages_clean():
    with epochsan.enabled() as san:
        s = _seeded_shard()
        s.put(b"k000", b"w" * 8)
        s.tree.pt.remap(0, s.tree.pt.lookup(0))
        s.export_snapshot()     # begin_export refreshes the cache itself
    assert san.violations == []


def test_epochsan_catches_unflipped_export():
    from repro.core.scheduler import OutOfOrderScheduler
    from repro.core.shard import StoreShard

    with epochsan.enabled() as san:
        s = StoreShard()
        for i in range(10):
            s.put(f"k{i:03d}".encode(), b"v" * 8)
        sched = OutOfOrderScheduler(pipeline="pipelined")
        s.flip = lambda: None                      # "forgot to publish"
        with pytest.raises(epochsan.EpochSanViolation) as ei:
            sched.stage_export(s)
        assert ei.value.kind == epochsan.UNFLIPPED_EXPORT
    assert san.stats.violations == 1


def test_epochsan_gating_matches_environment():
    """Off by default; `enabled()` scopes strictly and restores the
    previous sanitizer (the env-driven one under HONEYCOMB_EPOCHSAN=1)."""
    before = epochsan.get()
    env_on = os.environ.get(epochsan.ENV_VAR, "").strip() not in (
        "", "0", "false")
    if env_on:
        assert before is not None
    with epochsan.enabled() as san:
        assert epochsan.get() is san and san is not before
    assert epochsan.get() is before


def test_epochsan_stats_collects_registry_samples():
    with epochsan.enabled() as san:
        _seeded_shard(n=5)
        names = {s.name for s in san.stats.collect()}
    assert any("epochsan" in n and "staging" in n for n in names), names


# --------------------------------------------------------------------------
# driver wiring
# --------------------------------------------------------------------------

def test_finding_formatting():
    f = Finding("no-raw-clock", "src/x.py", 7, "msg")
    assert str(f) == "src/x.py:7: [no-raw-clock] msg"
    assert f.to_json() == {"rule": "no-raw-clock", "path": "src/x.py",
                           "line": 7, "message": "msg"}


def test_runner_writes_report(tmp_path):
    from repro.analysis import runner
    out = tmp_path / "report.json"
    rc = runner.main(["--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] and report["lint"] == [] \
        and report["kernel_check"] == []
    assert report["entry_points"] == len(kernel_check.kernel_entries()) \
        == 8 and report["baselined"] <= 2

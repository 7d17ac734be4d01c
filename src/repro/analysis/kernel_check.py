"""Kernel jaxpr analyzer — static checks over every Pallas entry point.

Traces each kernel in ``repro/kernels`` with abstract inputs (no device
execution, no compilation) and audits the jaxpr:

  * ``kernel-no-f64`` — no float64/complex128 value anywhere: the TPU
    lowering would silently demote (or refuse), and the store's device
    contract is u32/i32 lanes throughout.
  * ``kernel-no-callback`` — no host callback primitives inside a kernel
    dispatch: a callback re-enters Python mid-batch and breaks the
    one-dispatch-per-batch budget the paper's PCIe accounting assumes.
  * ``kernel-inplace-alias`` — every in-place scatter
    (``snapshot_delta_scatter``, ``log_replay_scatter``) must declare
    ``input_output_aliases`` on its ``pallas_call``: without donation
    the scatter materializes a second store-sized image per sync.
  * ``kernel-single-dispatch`` — the fused read megakernels lower to
    EXACTLY one ``pallas_call``: the whole point of PR 8's fusion is one
    launch per batch, and a refactor that splits the traversal back into
    per-level calls must fail loudly.
  * ``kernel-vmem-budget`` — per-kernel VMEM footprint (every VMEM
    BlockSpec window, counted twice because the pipeline double-buffers
    it, plus every VMEM scratch buffer) stays under a configurable budget
    (default 4 MiB, override with ``HONEYCOMB_VMEM_BUDGET_BYTES``): ~16 MB
    is the whole core's VMEM.  ANY (HBM) operands, SMEM blocks and
    scratch, and semaphores take no VMEM.

CLI::

    python -m repro.analysis.kernel_check [--json OUT]
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
from pathlib import Path

from .lint import Finding

DEFAULT_VMEM_BUDGET = 4 * 2 ** 20   # bytes; see module docstring


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One traceable Pallas entry point and the properties it must hold."""
    name: str               # display name, e.g. "delta_scatter.log_replay"
    path: str               # repo-relative source file (finding anchor)
    build: "object"         # () -> (fn, args, kwargs) with abstract args
    in_place: bool = False  # must declare input_output_aliases
    fused: bool = False     # must lower to exactly one pallas_call


# ----------------------------------------------------------- jaxpr walking
def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and all nested (closed) jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield from iter_eqns(inner)
            elif hasattr(v, "eqns"):
                yield from iter_eqns(v)


def pallas_eqns(jaxpr):
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _in_vmem(aval) -> bool:
    """Block windows default to VMEM; ANY, SMEM and semaphore memory are
    named explicitly."""
    ms = getattr(aval, "memory_space", None)
    return ms is None or "vmem" in str(ms).lower()


def _block_elems(block_shape) -> int:
    """Elements of one block window (squeezed dims count 1)."""
    n = 1
    for d in block_shape:
        size = getattr(d, "block_size", d)
        n *= size if isinstance(size, int) else 1
    return n


def vmem_block_bytes(eqn) -> int:
    """VMEM bytes the pallas_call occupies: two buffers per VMEM block
    window (the pipeline double-buffers them) plus its VMEM scratch."""
    gm = eqn.params["grid_mapping"]
    total = 0
    for bm in gm.block_mappings:
        if _in_vmem(bm.block_aval):
            total += (2 * _block_elems(bm.block_shape)
                      * bm.array_aval.dtype.itemsize)
    for aval in gm.scratch_avals:
        if _in_vmem(aval):
            total += math.prod(aval.shape) * aval.dtype.itemsize
    return total


def check_jaxpr(name: str, path: str, jaxpr, *, in_place: bool = False,
                fused: bool = False,
                vmem_budget: int | None = None) -> list[Finding]:
    """Audit one traced entry point; pure function of the jaxpr so tests
    can feed deliberately broken kernels through it."""
    import numpy as np
    budget = vmem_budget if vmem_budget is not None else int(os.environ.get(
        "HONEYCOMB_VMEM_BUDGET_BYTES", DEFAULT_VMEM_BUDGET))
    findings: list[Finding] = []
    calls = pallas_eqns(jaxpr)

    for eqn in iter_eqns(jaxpr):
        if "callback" in eqn.primitive.name:
            findings.append(Finding(
                "kernel-no-callback", path, 1,
                f"{name}: host callback primitive "
                f"'{eqn.primitive.name}' inside a kernel dispatch"))
        for v in (*eqn.invars, *eqn.outvars):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and dt in (np.float64, np.complex128):
                findings.append(Finding(
                    "kernel-no-f64", path, 1,
                    f"{name}: {dt} value flows through "
                    f"'{eqn.primitive.name}' — device lanes are 32-bit"))
                break

    if fused and len(calls) != 1:
        findings.append(Finding(
            "kernel-single-dispatch", path, 1,
            f"{name}: fused read path lowered to {len(calls)} pallas_call"
            f"(s), expected exactly 1 — the single-launch contract of the "
            f"fused megakernel is broken"))
    if in_place:
        for eqn in calls:
            if not eqn.params.get("input_output_aliases"):
                findings.append(Finding(
                    "kernel-inplace-alias", path, 1,
                    f"{name}: in-place scatter's pallas_call declares no "
                    f"input_output_aliases — the device will materialize "
                    f"a full copy of the image every sync"))
    for eqn in calls:
        used = vmem_block_bytes(eqn)
        if used > budget:
            findings.append(Finding(
                "kernel-vmem-budget", path, 1,
                f"{name}: VMEM block footprint {used} B exceeds the "
                f"{budget} B budget — shrink the VMEM-pinned blocks or "
                f"raise HONEYCOMB_VMEM_BUDGET_BYTES deliberately"))
    return findings


def trace_entry(entry: KernelEntry):
    import jax
    fn, args, kwargs = entry.build()
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


# --------------------------------------------------------- entry registry
def _abstract(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def kernel_entries() -> list[KernelEntry]:
    """Every Pallas entry point in ``repro/kernels``, with abstract
    inputs at the default geometry (shapes only — nothing executes)."""
    from repro.core.config import HoneycombConfig
    from repro.core.schema import NodeImageLayout

    cfg = HoneycombConfig()
    layout = NodeImageLayout.for_config(cfg)
    IW, LW = layout.image_words, layout.log_entry_words
    KW, VW, C = cfg.key_words, cfg.val_words, cfg.cache_slots
    S, B, E = 64, 8, 4
    u32, i32 = "uint32", "int32"

    def delta():
        from repro.kernels.delta_scatter import snapshot_delta_scatter
        return (snapshot_delta_scatter,
                (_abstract((S, IW), u32), _abstract((E,), i32),
                 _abstract((E, IW), u32)), {})

    def log_replay():
        from repro.kernels.delta_scatter import log_replay_scatter
        return (log_replay_scatter,
                (_abstract((S, IW), u32), _abstract((E,), i32),
                 _abstract((E,), i32), _abstract((E, LW), u32)),
                {"offs": layout.log_replay_offsets()})

    def fused(mode):
        def build():
            from repro.kernels import fused_read
            fn = (fused_read.batched_get_fused if mode == "get"
                  else fused_read.batched_scan_fused)
            args = [_abstract((S, IW), u32), _abstract((2 * S,), i32),
                    _abstract((), i32), _abstract((), i32),
                    _abstract((C,), i32), _abstract((C, IW), u32),
                    _abstract((B, KW), u32), _abstract((B,), i32)]
            if mode == "scan":
                args += [_abstract((B, KW), u32), _abstract((B,), i32)]
            return fn, tuple(args), {"cfg": cfg}
        return build

    def key_search():
        from repro.kernels.key_search import key_search as fn
        N = cfg.node_cap
        return (fn, (_abstract((B, KW), u32), _abstract((B,), i32),
                     _abstract((B, N, KW), u32), _abstract((B, N), i32),
                     _abstract((B, N), i32)), {})

    def key_search_image():
        from repro.kernels.key_search import key_search_image as fn
        offs = layout.offsets()
        return (fn, (_abstract((B, KW), u32), _abstract((B,), i32),
                     _abstract((B, IW), u32)),
                {"keys_off": offs["sc_keys"][0],
                 "lens_off": offs["sc_keylen"][0],
                 "count_off": offs["n_shortcuts"][0],
                 "n_keys": cfg.n_shortcuts, "key_words": KW})

    def leaf_merge():
        from repro.kernels.leaf_merge import leaf_merge as fn
        L = cfg.log_cap
        return (fn, (_abstract((B,), i32), _abstract((B,), i32),
                     _abstract((B, L), i32), _abstract((B, L), i32)),
                {"node_cap": cfg.node_cap, "log_cap": L})

    def paged():
        from repro.kernels.paged_attention import paged_attention as fn
        H, D, P, PS, T = 4, 64, 16, 16, 2
        return (fn, (_abstract((T, H, D), "float32"),
                     _abstract((P, PS, H, D), "float32"),
                     _abstract((P, PS, H, D), "float32"),
                     _abstract((T, 4), i32), _abstract((T,), i32),
                     _abstract((T,), i32)), {})

    k = "src/repro/kernels"
    return [
        KernelEntry("delta_scatter.snapshot_delta_scatter",
                    f"{k}/delta_scatter.py", delta, in_place=True),
        KernelEntry("delta_scatter.log_replay_scatter",
                    f"{k}/delta_scatter.py", log_replay, in_place=True),
        KernelEntry("fused_read.batched_get_fused",
                    f"{k}/fused_read.py", fused("get"), fused=True),
        KernelEntry("fused_read.batched_scan_fused",
                    f"{k}/fused_read.py", fused("scan"), fused=True),
        KernelEntry("key_search.key_search",
                    f"{k}/key_search.py", key_search),
        KernelEntry("key_search.key_search_image",
                    f"{k}/key_search.py", key_search_image),
        KernelEntry("leaf_merge.leaf_merge",
                    f"{k}/leaf_merge.py", leaf_merge),
        KernelEntry("paged_attention.paged_attention",
                    f"{k}/paged_attention.py", paged),
    ]


def run_kernel_checks(vmem_budget: int | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for entry in kernel_entries():
        try:
            jaxpr = trace_entry(entry)
        except Exception as e:  # noqa  # honeylint: disable=no-bare-except -- a kernel that fails to TRACE is itself a finding, whatever the error type
            findings.append(Finding(
                "kernel-trace-error", entry.path, 1,
                f"{entry.name}: failed to trace with abstract inputs: "
                f"{type(e).__name__}: {e}"))
            continue
        findings.extend(check_jaxpr(
            entry.name, entry.path, jaxpr.jaxpr, in_place=entry.in_place,
            fused=entry.fused, vmem_budget=vmem_budget))
    return findings


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="repro.analysis.kernel_check")
    ap.add_argument("--json", help="write findings as JSON to this path")
    ap.add_argument("--vmem-budget", type=int, default=None)
    args = ap.parse_args(argv)
    findings = run_kernel_checks(vmem_budget=args.vmem_budget)
    for f in findings:
        print(f)
    if args.json:
        Path(args.json).write_text(json.dumps(
            {"findings": [f.to_json() for f in findings]}, indent=1) + "\n")
    n = len(kernel_entries())
    print(f"kernel_check: {n} entry points traced, "
          f"{len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

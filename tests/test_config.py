"""The store's configuration: each benchmark configuration's ``store``
section constructs a ``HoneycombConfig``, and the removed second snapshot
layout and device read path are refused by name."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from repro.core import HoneycombConfig

BENCH_CONFIGS = sorted(
    (Path(__file__).resolve().parents[1] / "bench" / "configs").glob("*.json"))


def test_bench_configs_found():
    assert len(BENCH_CONFIGS) >= 2


@pytest.mark.parametrize("path", BENCH_CONFIGS, ids=lambda p: p.stem)
def test_bench_store_section_constructs(path):
    store = json.loads(path.read_text())["store"]
    cfg = HoneycombConfig(**store)
    assert (cfg.layout, cfg.read_backend) == ("packed", "fused")


@pytest.mark.parametrize("field,value", [("layout", "legacy"),
                                         ("read_backend", "reference")])
def test_removed_paths_are_refused(field, value):
    with pytest.raises(ValueError, match=f"{field}='{value}'.*removed"):
        HoneycombConfig(**{field: value})

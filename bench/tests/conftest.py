"""The benchmark's own tests: ``pytest bench/tests`` from the checkout's
root.  They run on the CPU at small sizes (JAX is held to the CPU here)."""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

"""The benchmark's tests import its harness and reference from ``bench/``
and the system under test from ``src/``, as ``bench/run.py`` does."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

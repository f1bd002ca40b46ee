"""Puts the system under test (`src/`) and the repository root (for the
`bench` package) on the import path of the benchmark's tests."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

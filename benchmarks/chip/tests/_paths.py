"""Puts the benchmark's package and the program's source on sys.path."""

import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

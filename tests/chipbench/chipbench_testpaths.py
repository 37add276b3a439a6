"""Puts the checkout's root (for ``chipbench``) and ``src`` (for the
program) on ``sys.path``; the benchmark's test files import it first."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

"""The benchmark's modules live in ``benchmarks/chip``; its tests import
them from there, as ``run.py`` does."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

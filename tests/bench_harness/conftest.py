"""The benchmark's own modules live in ``bench/`` beside ``BENCHMARK.json``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

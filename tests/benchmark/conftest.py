"""The benchmark's own tests (CPU, no chip): its yardstick's arithmetic
and that everything BENCHMARK.json names is found by name."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

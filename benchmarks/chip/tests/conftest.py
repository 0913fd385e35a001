"""CPU tests of the benchmark's own code: ``pytest benchmarks/chip/tests``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parent.parent
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)

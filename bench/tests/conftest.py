"""The benchmark's own tests: `python -m pytest bench/tests` (on the CPU).

They sit outside the repository's tier-1 `testpaths`, and run the
benchmark's pieces at small widths: the trace reduction on a recorded
chip trace, the FLOP count, the references against the program, the
discovery of files by name, and `correct` against planted faults.
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""CPU tests of the benchmark's own code.  They are not under the
repository's ``testpaths`` and run apart from its suite:

  JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for p in (str(HERE), str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

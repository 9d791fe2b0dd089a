"""CPU tests of the benchmark: ``PYTHONPATH=src python -m pytest chipbench/tests``.

They sit outside the repository's tier-1 ``testpaths``.  JAX is held to
the CPU, so no test here needs or touches a chip.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

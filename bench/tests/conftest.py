"""The harness's tests run on the CPU (``JAX_PLATFORMS=cpu``), with the
Pallas kernels in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

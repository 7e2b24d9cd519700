"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They put ``bench/`` and ``src/`` on the path the way ``bench/run.py``
does. The repository's own suite (``tests/``) does not collect them."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

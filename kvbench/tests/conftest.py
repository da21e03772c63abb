"""CPU tests of the harness: ``python -m pytest kvbench/tests -q`` from the
root of the checkout. Not part of the repository's tier-1 suite."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

"""``granite-4.0-h-small-ep2-l10``'s files' own checks (the configuration
against the catalog's row, the counts against the definition, the
reference's recurrence and router, the three readers): their cases live
beside the harness (``kvbench/tests/test_granite_hybrid.py``) and are
collected here too, as ``test_kvbench_harness.py`` collects the others'."""

from kvbench.tests.test_granite_hybrid import *  # noqa: F401,F403
from kvbench.tests.test_granite_hybrid import (  # noqa: F401 (fixtures)
    granite_cfg,
    granite_conf,
)

"""``mellum2-12b-ep4``'s files' own checks (the configuration against the
catalog's row, the counts over both pools, the reference's window, yarn and
router, the five readers): their cases live beside the harness
(``kvbench/tests/test_mellum2.py``) and are collected here too, as
``test_kvbench_falcon.py`` collects Falcon-H1's. The cell's walk at toy
widths stays with the harness's own tests."""

from kvbench.tests.test_mellum2 import *  # noqa: F401,F403
from kvbench.tests.test_mellum2 import (  # noqa: F401 (fixtures)
    mellum_cfg,
    mellum_conf,
)

del test_the_rehearsal_walks_the_cell  # noqa: F821 (80 s: the harness's)

"""``falcon-h1-34b-l9``'s files' own checks (the configuration against the
catalog's row, the counts against the definition, the reference's
recurrence and RoPE, the three readers): their cases live beside the
harness (``kvbench/tests/test_falcon_h1.py``) and are collected here too, as
``test_kvbench_granite.py`` collects granite's."""

from kvbench.tests.test_falcon_h1 import *  # noqa: F401,F403
from kvbench.tests.test_falcon_h1 import (  # noqa: F401 (fixtures)
    falcon_cfg,
    falcon_conf,
)

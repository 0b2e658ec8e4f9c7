"""The benchmark's own tests run on the CPU: ``python -m pytest bench/tests``.

They stand outside the repository's test suite: they check the yardstick
(trace reduction, work counts, traffic) and that a run with its timed
path broken reads ``correct: false``.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

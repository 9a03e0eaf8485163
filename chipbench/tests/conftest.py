"""chipbench's tests run by hand (`pytest chipbench/tests`), on the CPU
backend, and are not collected by the repo's tier-1 run of `tests/`."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "chipbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

"""The benchmark's self-checks run on the CPU, in seconds:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/selfcheck -q

They are not part of the repository's tier-1 tests (`pytest.ini` names
`tests/`); they check the yardstick, which later PRs cannot change."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

"""The TPC-H generator under the name it had before generators were found
by the configuration's `"generator"`: `generators/tpch.py` is the module.
Nothing in `benchmarks/` imports this; it stays while
`tests/test_sf100_mesh.py` still imports `harness.datagen`."""

from generators.tpch import *  # noqa: F401,F403

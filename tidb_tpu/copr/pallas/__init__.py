"""Hand-written Pallas kernel tier below the fusion emitters.

ISSUE 11: where XLA lowering of a fusion core is awkward (dynamic
gathers for dictionary-code re-mapping, the bit-unpack shift chain of
the cold tier), the emitter drops one level and calls a hand-written
Pallas kernel instead of composing jnp ops.  The tier's contract:

- kernels compile to Mosaic on a TPU backend and run in **interpret
  mode** (pure-jax evaluation) everywhere else, so the CPU tier-1
  harness executes them with no Mosaic toolchain; both are compiled for
  a described v5e at the production tile in tests/test_tpu_compile.py;
- ``TIDB_TPU_PALLAS=0`` disables the tier entirely — every call site
  falls back to its plain-XLA composition, the bench's unfused
  comparator (parity is test-asserted both ways);
- every kernel is kernelcheck'd like the rest of the corpus: abstract
  traces on canonical shapes, identical-jaxpr guards across runtime
  operand values, and an executed parity check against the jnp
  reference path.
"""

from .kernels import (  # noqa: F401
    pallas_enabled,
    remap_codes,
    trace_remap_kernel,
    trace_unpack_kernel,
    unpack_codes,
)

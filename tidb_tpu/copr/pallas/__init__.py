"""Hand-written Pallas kernel tier below the fusion emitters.

ISSUE 11: where XLA lowering of a fusion core is awkward (dynamic
gathers for dictionary-code re-mapping, the bit-unpack shift chain of
the cold tier), the emitter drops one level and calls a hand-written
Pallas kernel instead of composing jnp ops.  The tier's contract:

- kernels run in **interpret mode** by default (pure-jax evaluation, so
  the CPU tier-1 harness and any non-TPU backend execute them with no
  Mosaic toolchain); ``TIDB_TPU_PALLAS_COMPILE=1`` opts into compiled
  Mosaic lowering on real TPU backends;
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

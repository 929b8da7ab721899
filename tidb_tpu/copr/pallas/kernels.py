"""The Pallas kernels themselves (see package docstring for the tier's
contract).

Two cores live here today, both "sort/re-map"-adjacent pieces the fused
mesh programs lean on:

- ``remap_codes``: dictionary-code re-mapping — ``out[i] =
  mapping[codes[i]]`` — the device half of computed string group keys
  (the host evaluates the string function once per DICTIONARY entry;
  rows re-map in code space).  A data-dependent gather is exactly the
  shape XLA lowers poorly on TPU (it serializes through scalar loads);
  the kernel states the access pattern directly.
- ``unpack_codes``: the cold tier's bit-unpack (1/2/4/8-bit packed
  dictionary codes -> uint8 code per row) as one vector shift/mask
  kernel instead of the broadcast+reshape chain ``decode_packed``
  composes from jnp ops.

Both take their big operands as RUNTIME arguments — mapping contents and
packed bytes never enter any compiled fingerprint, which kernelcheck
guards with identical-jaxpr traces across shifted operand values.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def pallas_enabled() -> bool:
    """The tier switch: TIDB_TPU_PALLAS=0 restores the plain-XLA
    composition at every call site (the unfused comparator)."""
    return os.environ.get("TIDB_TPU_PALLAS", "1") != "0"


def _interpret() -> bool:
    """Interpret mode unless compiled Mosaic lowering was opted into on
    a TPU backend (TIDB_TPU_PALLAS_COMPILE=1).  Interpret mode evaluates
    the kernel body as jax ops — semantically identical, runs on any
    backend, and is what keeps the tier inside the CPU tier-1 harness."""
    if os.environ.get("TIDB_TPU_PALLAS_COMPILE", "0") != "1":
        return True
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# remap_codes: code-space dictionary re-mapping (a vector gather)
# ---------------------------------------------------------------------------


def _remap_kernel(codes_ref, mapping_ref, out_ref, *, cap: int):
    c = codes_ref[:].astype(jnp.int32)
    c = jnp.clip(c, 0, cap - 1)
    out_ref[:] = mapping_ref[c]


def remap_codes(codes, mapping, n: int):
    """``mapping[clip(codes, 0, cap-1)]`` for int code vectors.

    `mapping` is a runtime operand (pow2-padded to the dictionary cap);
    its VALUES never shape the program.  With the tier disabled this is
    a plain jnp take — the comparator path."""
    cap = mapping.shape[0]
    codes = codes.reshape(n)
    if not pallas_enabled():
        return mapping[jnp.clip(codes.astype(jnp.int32), 0, cap - 1)]
    return pl.pallas_call(
        partial(_remap_kernel, cap=cap),
        out_shape=jax.ShapeDtypeStruct((n,), mapping.dtype),
        interpret=_interpret(),
    )(codes, mapping)


# ---------------------------------------------------------------------------
# unpack_codes: the cold tier's bit-unpack
# ---------------------------------------------------------------------------


def _unpack_kernel(packed_ref, out_ref, *, bits: int, vpb: int):
    p = packed_ref[:]
    # one shift/mask per slot, written as a strided store: the kernel
    # stays in uint8 end to end (narrow VPU lanes, no widening chain)
    mask = jnp.uint8((1 << bits) - 1)
    for s in range(vpb):
        out_ref[s::vpb] = (p >> jnp.uint8(s * bits)) & mask


def unpack_codes(packed, bits: int, n: int):
    """Bit-packed little-endian codes -> one uint8 code per row (the
    inverse of layout/coldtier.pack_codes).  `n` is the row count; the
    packed vector holds ``n * bits / 8`` bytes."""
    vpb = 8 // bits
    p = packed.reshape(-1)
    if vpb == 1:
        return p
    if not pallas_enabled():
        shifts = jnp.arange(vpb, dtype=jnp.uint8) * jnp.uint8(bits)
        return ((p[:, None] >> shifts[None, :])
                & jnp.uint8((1 << bits) - 1)).reshape(n)
    return pl.pallas_call(
        partial(_unpack_kernel, bits=bits, vpb=vpb),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint8),
        interpret=_interpret(),
    )(p)


# ---------------------------------------------------------------------------
# kernelcheck registration: canonical abstract traces
# ---------------------------------------------------------------------------


def trace_remap_kernel(shift: int = 0, n: int = 1024, cap: int = 16):
    """make_jaxpr of the remap kernel on a canonical shape; `shift`
    perturbs the mapping CONTENTS — lint.kernelcheck traces two shifts
    and requires identical jaxprs (mapping values are runtime operands,
    never compiled constants)."""
    import numpy as np

    codes = np.arange(n, dtype=np.int32) % cap
    mapping = (np.arange(cap, dtype=np.int32) + shift)
    return jax.make_jaxpr(lambda c, m: remap_codes(c, m, n))(codes, mapping)


def trace_unpack_kernel(bits: int = 4, n: int = 1024):
    """make_jaxpr of the unpack kernel on a canonical shape."""
    import numpy as np

    vpb = 8 // bits
    packed = np.zeros(n // vpb, dtype=np.uint8)
    return jax.make_jaxpr(lambda p: unpack_codes(p, bits, n))(packed)

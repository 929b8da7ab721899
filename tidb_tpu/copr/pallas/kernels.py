"""The Pallas kernels themselves (see package docstring for the tier's
contract).

Two cores live here today, both "sort/re-map"-adjacent pieces the fused
mesh programs lean on:

- ``remap_codes``: dictionary-code re-mapping — ``out[i] =
  mapping[codes[i]]`` — the device half of computed string group keys
  (the host evaluates the string function once per DICTIONARY entry;
  rows re-map in code space).  The kernel states the gather as a lane
  gather per 128-entry slice of the mapping.
- ``unpack_codes``: the cold tier's bit-unpack (1/2/4/8-bit packed
  dictionary codes -> one code per row): the lane expansion is a one-hot
  matmul on the MXU, the shift/mask runs in 32-bit lanes and lands with
  a sublane-strided store.

Both work on ``(rows, 128)`` blocks over a 1-D grid and take their big
operands as RUNTIME arguments — mapping contents and packed bytes never
enter any compiled fingerprint, which kernelcheck guards with
identical-jaxpr traces across shifted operand values.

The kernel bodies are traced with x64 off: the process runs with
``jax_enable_x64`` and Mosaic has no 64-bit types, so a weakly typed
Python scalar in a kernel would otherwise become an i64 it cannot lower.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

_LANES = 128
#: the remap kernel walks the mapping 128 entries at a time; past this
#: width XLA's own gather is the path
REMAP_MAX_CAP = 1024


def pallas_enabled() -> bool:
    """The tier switch: TIDB_TPU_PALLAS=0 restores the plain-XLA
    composition at every call site (the unfused comparator)."""
    return os.environ.get("TIDB_TPU_PALLAS", "1") != "0"


def _interpret() -> bool:
    """Compiled Mosaic on a TPU backend; interpret mode (the kernel body
    evaluated as jax ops) everywhere else, which is what keeps the tier
    inside the CPU tier-1 harness."""
    return jax.default_backend() != "tpu"


def _row_blocks(n_rows: int, block: int):
    """(block rows, padded row count) for a 1-D grid over `n_rows`
    128-lane rows: whole blocks, sublane-aligned for 8-bit data."""
    if n_rows < block:
        block = -(-n_rows // 32) * 32
    return block, -(-n_rows // block) * block


def _rows_spec(rows: int):
    return pl.BlockSpec((rows, _LANES), lambda i: (i, jnp.int32(0)))


# ---------------------------------------------------------------------------
# remap_codes: code-space dictionary re-mapping (a lane gather)
# ---------------------------------------------------------------------------


def _remap_kernel(codes_ref, mapping_ref, out_ref, *, cap: int):
    c = jnp.clip(codes_ref[:], 0, cap - 1)
    acc = jnp.zeros_like(c)
    for j in range(mapping_ref.shape[0]):  # static: cap / 128 slices
        local = c - j * _LANES
        row = jnp.broadcast_to(mapping_ref[j: j + 1, :], c.shape)
        hit = jnp.take_along_axis(
            row, jnp.clip(local, 0, _LANES - 1), axis=1)
        acc = jnp.where((local >= 0) & (local < _LANES), hit, acc)
    out_ref[:] = acc


def remap_codes(codes, mapping, n: int):
    """``mapping[clip(codes, 0, cap-1)]`` for int code vectors.

    `mapping` is a runtime operand (pow2-padded to the dictionary cap);
    its VALUES never shape the program.  The kernel serves int32
    mappings up to REMAP_MAX_CAP entries; wider or 64-bit mappings, and
    the tier disabled, take the plain jnp gather."""
    cap = mapping.shape[0]
    codes = codes.reshape(n)
    if (not pallas_enabled() or mapping.dtype != jnp.int32
            or cap > REMAP_MAX_CAP):
        return mapping[jnp.clip(codes.astype(jnp.int32), 0, cap - 1)]
    block, rows = _row_blocks(-(-n // _LANES), 512)
    slices = -(-cap // _LANES)
    with jax.enable_x64(False):
        c2 = jnp.pad(codes.astype(jnp.int32), (0, rows * _LANES - n))
        m2 = jnp.pad(mapping, (0, slices * _LANES - cap))
        out = pl.pallas_call(
            partial(_remap_kernel, cap=cap),
            grid=(rows // block,),
            in_specs=[_rows_spec(block),
                      pl.BlockSpec((slices, _LANES),
                                   lambda i: (jnp.int32(0), jnp.int32(0)))],
            out_specs=_rows_spec(block),
            out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            interpret=_interpret(),
            name="remap_codes",
        )(c2.reshape(rows, _LANES), m2.reshape(slices, _LANES))
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# unpack_codes: the cold tier's bit-unpack
# ---------------------------------------------------------------------------


def _unpack_kernel(packed_ref, out_ref, *, bits: int, vpb: int):
    # Row r of the output takes the 128/vpb bytes of packed row r // vpb
    # that start at lane (r % vpb) * 128/vpb, each repeated vpb times
    # along the lanes; slot l % vpb of a byte is its bits [l%vpb * bits..).
    # The repeat is a one-hot (128, 128) matmul — bytes are exact in bf16.
    rows = packed_ref.shape[0]
    seg = _LANES // vpb
    p = packed_ref[:].astype(jnp.int32).astype(jnp.bfloat16)
    src = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    shift = (jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
             % vpb) * bits
    for k in range(vpb):
        onehot = (src == k * seg + dst // vpb).astype(jnp.bfloat16)
        spread = jnp.dot(p, onehot,
                         preferred_element_type=jnp.float32).astype(jnp.int32)
        out_ref[pl.ds(k, rows, stride=vpb), :] = (
            (spread >> shift) & ((1 << bits) - 1))


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
    nbytes = p.shape[0]
    block, rows = _row_blocks(-(-nbytes // _LANES), 256)
    with jax.enable_x64(False):
        p2 = jnp.pad(p, (0, rows * _LANES - nbytes))
        out = pl.pallas_call(
            partial(_unpack_kernel, bits=bits, vpb=vpb),
            grid=(rows // block,),
            in_specs=[_rows_spec(block)],
            out_specs=_rows_spec(block * vpb),
            out_shape=jax.ShapeDtypeStruct((rows * vpb, _LANES), jnp.int32),
            interpret=_interpret(),
            name="unpack_codes",
        )(p2.reshape(rows, _LANES))
    return out.reshape(-1)[:n].astype(jnp.uint8)


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

"""fp16 storage inside Pallas TPU kernels on chips without fp16 vectors.

The paper's policies keep scores, softmax statistics and accumulators in
fp16 (core/precision.py).  Mosaic on a TPU v5e has no fp16 vector type: an
fp16 block cannot be loaded, stored, converted to or reduced (the compiler
refuses each).  Compiled kernels therefore hold every fp16 quantity in an
f32 container whose value is rounded to the nearest fp16 number (round to
nearest even, overflow to inf, fp16 subnormals) wherever the kernel body
stores a policy dtype - the same storage points at which the fp16 kernel
rounds, so a score that overflows fp16 still becomes inf.  Arithmetic
between two such points runs in f32.

Interpret mode (the CPU route) keeps native fp16, so its results are those
of the fp16 kernel bit for bit.

:class:`KernelNumerics` is the one switch a kernel body sees: ``fit`` is a
plain ``astype`` natively and the rounding under emulation, ``storage``
names the container dtype of a block or scratch buffer, and ``precision``
keeps f32 contractions exact on the matrix unit (fp16 operands have 11
significant bits, more than one bf16 pass carries).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

_F16 = np.dtype(np.float16)
_F16_MAX_BITS = 0x477FE000        # 65504.0 as f32 bits
_F16_MIN_NORMAL = 2.0 ** -14


def round_f16(x):
    """f32 -> f32 holding ``x.astype(float16)`` exactly (IEEE rounding).

    Normal range: round the f32 mantissa to 10 bits, ties to even, by an
    integer add on the bit pattern (a carry into the exponent is the
    correct round-up); results above the fp16 maximum become inf.
    Subnormal range (|x| < 2**-14): round to a multiple of 2**-24.
    A scalar comes back as a (1, 1) array: Mosaic bitcasts vectors only.
    """
    x = jnp.asarray(x).astype(jnp.float32)
    if x.ndim == 0:
        x = x.reshape(1, 1)
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(x, u32)
    sign = bits & u32(0x80000000)
    mag = bits & u32(0x7FFFFFFF)
    lsb = (mag >> 13) & u32(1)
    norm = (mag + u32(0xFFF) + lsb) & u32(0xFFFFE000)
    norm = jnp.where(norm > u32(_F16_MAX_BITS), u32(0x7F800000), norm)
    norm = jnp.where(mag >= u32(0x7F800000), mag, norm)     # inf / nan
    ax = jax.lax.bitcast_convert_type(mag, jnp.float32)
    sub = jax.lax.bitcast_convert_type(
        jnp.round(ax * 2.0 ** 24) * 2.0 ** -24, u32
    )
    out = jnp.where(ax < _F16_MIN_NORMAL, sub, norm) | sign
    return jax.lax.bitcast_convert_type(out, jnp.float32)


@dataclasses.dataclass(frozen=True)
class KernelNumerics:
    """How a kernel body holds its policy dtypes (see module doc).

    ``emulate_f16`` is set for compiled (Mosaic) kernels and clear in
    interpret mode."""

    emulate_f16: bool = False

    def _emulated(self, dtype) -> bool:
        return self.emulate_f16 and np.dtype(dtype) == _F16

    def storage(self, dtype):
        """Dtype of a block, scratch buffer or output holding ``dtype``."""
        return jnp.float32 if self._emulated(dtype) else dtype

    def fit(self, x, dtype):
        """``x.astype(dtype)``, in the container ``storage(dtype)``."""
        return round_f16(x) if self._emulated(dtype) else x.astype(dtype)

    def const(self, value, dtype):
        """A scalar constant of ``dtype``, in its container."""
        if self._emulated(dtype):
            return jnp.asarray(np.float32(np.float16(value)))
        return jnp.asarray(value, dtype)

    @property
    def precision(self):
        return jax.lax.Precision.HIGHEST if self.emulate_f16 else None

    def to_storage(self, x):
        """Host-side (XLA) cast of a kernel operand to its container."""
        return x.astype(self.storage(x.dtype))

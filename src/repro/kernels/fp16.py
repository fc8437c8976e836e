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
_F16_MIN_NORMAL = 2.0 ** -14
_F16_OVERFLOW = 65520.0           # half an ulp past the fp16 maximum: inf
_SIGN = -(2**31)
_ABS = 0x7FFFFFFF
_EXP = 0x7F800000
_HALF = 0x3F000000                # 0.5 as f32 bits


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _f32(bits):
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _store(y, x_abs, sign):
    """``y`` rounded to fp16 precision, stored: inf where ``|x|`` is past
    the fp16 range, then ``x``'s sign bit (a zero keeps it too)."""
    y = jnp.where(x_abs >= _F16_OVERFLOW, jnp.inf, y)
    return _f32(_bits(y) | sign)


def round_f16(x):
    """f32 -> f32 holding ``x.astype(float16)`` exactly (IEEE rounding).

    Adding ``c`` = 2**13 fp16 ulps of |x|'s binade leaves the f32 sum an
    ulp of exactly that fp16 ulp, so the add rounds |x| to fp16 precision,
    ties to even, and subtracting ``c`` back is exact.  The binade is
    floored at fp16's smallest normal, so subnormals round to a multiple
    of 2**-24; past the fp16 range the result is inf whatever ``c`` made
    of it, and a nan stays nan.  Ten vector-unit ops.  A scalar comes
    back as a (1, 1) array: Mosaic bitcasts vectors only.
    """
    x = jnp.asarray(x).astype(jnp.float32)
    if x.ndim == 0:
        x = x.reshape(1, 1)
    bits = _bits(x)
    ax = _f32(bits & jnp.int32(_ABS))
    c = jnp.maximum(_f32(bits & jnp.int32(_EXP)), _F16_MIN_NORMAL) * 2.0**13
    return _store((ax + c) - c, ax, bits & jnp.int32(_SIGN))


def bf16_to_f16(x):
    """bf16 -> f32 holding ``x.astype(float16)`` exactly, cheaper than
    :func:`round_f16`.  A bf16 value carries 8 significant bits, so only
    fp16's range rounds it: adding ``c`` = 0.5 with x's sign (an f32 ulp
    of 2**-24, the fp16 subnormal step) and subtracting it back rounds the
    subnormal range and leaves every larger bf16 value as it is; past the
    fp16 maximum it is inf.  (``c`` is built from x's bits: a constant
    would let the compiler fold the add and subtract away.)"""
    x = x.astype(jnp.float32)
    sign = _bits(x) & jnp.int32(_SIGN)
    c = _f32(sign | jnp.int32(_HALF))
    return _store((x + c) - c, jnp.abs(x), sign)


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
        if not self._emulated(dtype):
            return x.astype(dtype)
        if x.dtype == jnp.bfloat16:
            return bf16_to_f16(x)
        return round_f16(x)

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

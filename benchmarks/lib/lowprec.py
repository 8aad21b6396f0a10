"""The arithmetic a plain reference multiplies with.

``F32`` is the reference proper: float32 operands, ``highest`` precision (on a
TPU a float32 product otherwise runs in fewer passes). ``FP8`` is the control:
the same reference with every product's operands, in the forward and in the
backward pass, rounded to float8 e4m3 under a per-tensor scale, the nearest
precision below the bfloat16 the configurations state. A comparison that lets
the control through would let a later PR serve or train in fp8 unseen."""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

E4M3_MAX = 448.0


def round_e4m3(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 (3 mantissa bits, least normal exponent
    -6, round to nearest even) under a per-tensor scale that puts its largest
    magnitude at 448. Worked in float32 arithmetic: bit for bit the result of
    ``astype(float8_e4m3fn)`` (a test holds it to that), but the TPU compiler
    takes a quarter of the time over it (130 s against 553 s for the ResNet
    reference; compile-only, PR 24)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / scale
    exponent = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(exponent - 3)
    return jnp.round(y / step) * step * scale


def _low_precision(fn: Callable) -> Callable:
    """``fn(a, b)`` bilinear; operands and cotangent rounded to e4m3."""

    @jax.custom_vjp
    def f(a, b):
        return fn(round_e4m3(a), round_e4m3(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(fn, round_e4m3(a), round_e4m3(b))
        return vjp(round_e4m3(g))

    f.defvjp(fwd, bwd)
    return f


def _einsum(spec: str) -> Callable:
    return lambda a, b: jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _conv(strides, padding) -> Callable:
    return lambda x, w: lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class Ops:
    name: str
    wrap: Callable[[Callable], Callable]

    def einsum(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        return self.wrap(_einsum(spec))(a, b)

    def conv(self, x: jax.Array, w: jax.Array, strides, padding) -> jax.Array:
        return self.wrap(_conv(tuple(strides), padding))(x, w)


F32 = Ops("f32_highest", lambda fn: fn)
FP8 = Ops("fp8_e4m3", _low_precision)
BY_NAME = {"f32": F32, "fp8": FP8}

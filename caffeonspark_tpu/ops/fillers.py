"""Weight fillers with Caffe semantics (caffe-public filler.hpp behaviors,
referenced by every `weight_filler`/`bias_filler` in data/*.prototxt).

Supported types: constant, uniform, gaussian, xavier, msra, positive_unitball,
bilinear, log_uniform (the log of a uniform draw: GatedDeltaNet's
A_log), log_arange (log(1..n) along the last axis, the same in every
row: Mamba's A_log) and inv_softplus_log_uniform (the inverse softplus
of a log-uniform draw in [min, max]: Mamba's dt bias).  `xavier`/`msra` honor `variance_norm` (FAN_IN default).
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..proto.caffe import FillerParameter, VarianceNorm


def _fans(shape: Sequence[int]) -> Tuple[float, float]:
    """Caffe: fan_in = count/num, fan_out = count/channels for 4D blobs;
    for 2D (IP) weight (N, K): fan_in = K, fan_out = N."""
    if len(shape) == 0:
        return 1.0, 1.0
    count = math.prod(shape)
    fan_in = count / shape[0]
    fan_out = count / shape[1] if len(shape) > 1 else float(shape[0])
    return fan_in, fan_out


def _n_for(filler: FillerParameter, shape) -> float:
    fan_in, fan_out = _fans(shape)
    vn = filler.variance_norm
    if vn == VarianceNorm.FAN_OUT:
        return fan_out
    if vn == VarianceNorm.AVERAGE:
        return (fan_in + fan_out) / 2.0
    return fan_in


def fill(key: jax.Array, filler: FillerParameter, shape: Sequence[int],
         dtype=jnp.float32) -> jax.Array:
    t = filler.type or "constant"
    shape = tuple(int(s) for s in shape)
    if t == "constant":
        return jnp.full(shape, filler.value, dtype)
    if t == "uniform":
        return jax.random.uniform(key, shape, dtype, filler.min, filler.max)
    if t == "log_uniform":
        return jnp.log(jax.random.uniform(key, shape, dtype, filler.min,
                                          filler.max))
    if t == "log_arange":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
            shape).astype(dtype)
    if t == "inv_softplus_log_uniform":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(filler.min),
                                        math.log(filler.max)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if t == "gaussian":
        return (filler.mean
                + filler.std * jax.random.normal(key, shape)).astype(dtype)
    if t == "xavier":
        scale = math.sqrt(3.0 / _n_for(filler, shape))
        return jax.random.uniform(key, shape, dtype, -scale, scale)
    if t == "msra":
        std = math.sqrt(2.0 / _n_for(filler, shape))
        return (std * jax.random.normal(key, shape)).astype(dtype)
    if t == "positive_unitball":
        x = jax.random.uniform(key, shape, dtype)
        flat = x.reshape(shape[0], -1)
        flat = flat / jnp.sum(flat, axis=1, keepdims=True)
        return flat.reshape(shape)
    if t == "bilinear":
        # upsampling kernel for Deconvolution (filler.hpp BilinearFiller)
        assert len(shape) == 4 and shape[2] == shape[3]
        k = shape[2]
        f = math.ceil(k / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        og = jnp.ogrid[:k, :k]
        w = (1 - jnp.abs(og[0] / f - c)) * (1 - jnp.abs(og[1] / f - c))
        return jnp.broadcast_to(w, shape).astype(dtype)
    raise ValueError(f"unknown filler type {t!r}")

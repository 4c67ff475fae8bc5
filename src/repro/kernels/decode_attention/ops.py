"""Public decode-attention API (inference-only; no vjp needed), dispatched
through repro.kernels.dispatch. k/v arrive in the kernel-native
(B, KVH, S, D) cache layout — zero copies on the decode hot path."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.decode_attention import kernel as K
from repro.kernels.decode_attention import ref
from repro.kernels.flash_attention.ops import fit


def decode_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                     bk: int | None = None, mode=None):
    """q: (B, KVH, G, D); k/v: (B, KVH, S, D); q_pos (B,); kv_pos (B, S)."""
    r = dispatch.resolve(mode)
    if not r.use_pallas:
        return ref.decode_ref(q, k, v, q_pos, kv_pos, window=window)
    return K.decode_attention_fwd(q, k, v, q_pos, kv_pos, window=window,
                                  bk=fit(k.shape[2], bk or K.DEFAULT_BK),
                                  interpret=r.interpret)


def _example(rng):
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, kv_ = jax.random.split(key, 3)
    b, kvh, g, s, d = 2, 2, 2, 512, 64
    q = jax.random.normal(kq, (b, kvh, g, d), jnp.float32)
    k = jax.random.normal(kk, (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(kv_, (b, kvh, s, d), jnp.float32)
    fill = int(0.75 * s)
    kv_pos = jnp.broadcast_to(
        jnp.where(jnp.arange(s) < fill, jnp.arange(s), 1 << 30)[None],
        (b, s))
    q_pos = jnp.full((b,), fill, jnp.int32)
    return (q, k, v, q_pos, kv_pos), {}


dispatch.register(
    "decode_attention", fn=decode_attention, ref=ref.decode_ref,
    example=_example)

"""Public flash attention API, dispatched through repro.kernels.dispatch.

- `flash5(q5, k, v, window)` — kernel-native layout, custom_vjp: the
  forward runs the Pallas kernel (bq/bk fit to the shape), the backward
  differentiates the jnp reference (correct gradients, kernel-speed
  forward).
- `flash_attention` (models layout) — adapter used by
  repro.models.attention when attn_impl == "flash": accepts the model's
  (B, S, KV, G, H) q and (B, T, KV, H) k/v with explicit positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ref


def fit(n: int, block: int) -> int:
    """Largest divisor of n that is <= block (block-shape validity)."""
    block = max(1, min(int(block), int(n)))
    while n % block:
        block -= 1
    return block


def _blocks(sq: int, skv: int) -> dict:
    return {"bq": fit(sq, K.DEFAULT_BQ), "bk": fit(skv, K.DEFAULT_BK)}


def _forward(q, k, v, window, mode):
    r = dispatch.resolve(mode)
    if not r.use_pallas:
        return ref.attention_ref(q, k, v, window=window)
    return K.flash_attention_fwd(q, k, v, window=window,
                                 interpret=r.interpret,
                                 **_blocks(q.shape[3], k.shape[2]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash5(q, k, v, window: int = 0, mode=None):
    return _forward(q, k, v, window, mode)


def _fwd(q, k, v, window, mode):
    return flash5(q, k, v, window, mode), (q, k, v)


def _bwd(window, mode, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.attention_ref(
        q_, k_, v_, window=window), q, k, v)
    return vjp(g)


flash5.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, q_pos, kv_pos, *, window: int = 0, mode=None):
    """Model-layout adapter: q (B,Sq,KV,G,H), k/v (B,Skv,KV,H)."""
    q5 = jnp.moveaxis(q, 1, 3)          # (B,KV,G,Sq,H)
    k4 = jnp.moveaxis(k, 1, 2)          # (B,KV,Skv,H)
    v4 = jnp.moveaxis(v, 1, 2)
    r = dispatch.resolve(mode)
    if not r.use_pallas:
        o5 = ref.attention_ref(q5, k4, v4, window=window)
    else:
        o5 = flash5(q5, k4, v4, window, mode)
    return jnp.moveaxis(o5, 3, 1)       # back to (B,Sq,KV,G,H)


def _example(rng):
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2, 2, 256, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(kv_, (1, 2, 256, 64), jnp.float32)
    return (q, k, v), {}


def _flash5_mode(q, k, v, *, mode=None):
    return _forward(q, k, v, 0, mode)


dispatch.register(
    "flash_attention", fn=_flash5_mode, ref=ref.attention_ref,
    example=_example)

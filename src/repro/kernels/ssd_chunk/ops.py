"""Public SSD-scan API: model-layout adapter over the chunk kernel,
dispatched through repro.kernels.dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.ssd_chunk import kernel as K
from repro.kernels.ssd_chunk import ref


def ssd(x, dt, a_log, b, c, chunk: int, mode=None):
    """Model layout: x (B, S, H, P); dt (B, S, H) fp32 post-softplus;
    a_log (H,); b/c (B, S, N) (groups=1, broadcast over heads).
    Returns (y (B, S, H, P), final_state (B, H, N, P))."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    la = dt * (-jnp.exp(a_log))                     # (B, S, H)

    def to_bh(t, feat):
        # (B, S, H?, F) -> (B*H, NC, Q, F)
        if t.ndim == 3 and t.shape[-1] == h:        # per-head scalar
            t = jnp.moveaxis(t, -1, 1)[..., None]   # (B, H, S, 1)
        elif t.ndim == 3:                            # shared (B, S, N)
            t = jnp.broadcast_to(t[:, None], (bsz, h, s, t.shape[-1]))
        else:                                        # (B, S, H, P)
            t = jnp.moveaxis(t, 2, 1)
        return t.reshape(bsz * h, nc, chunk, -1)

    r = dispatch.resolve(mode)
    if not r.use_pallas:
        ys, hs = [], []
        for bi in range(bsz):
            h_state = jnp.zeros((h, n, p), jnp.float32)
            y_rows = []
            for ci in range(nc):
                sl = slice(ci * chunk, (ci + 1) * chunk)
                y_c, h_state = ref.ssd_chunk_ref(
                    x[bi, sl], dt[bi, sl], la[bi, sl], b[bi, sl], c[bi, sl],
                    h_state)
                y_rows.append(y_c)
            ys.append(jnp.concatenate(y_rows, axis=0))
            hs.append(h_state)
        return jnp.stack(ys), jnp.stack(hs)

    y, hout = K.ssd_scan(to_bh(x, p), to_bh(dt, 1), to_bh(la, 1),
                         to_bh(b, n), to_bh(c, n),
                         interpret=r.interpret)
    y = y.reshape(bsz, h, s, p)
    return jnp.moveaxis(y, 1, 2), hout.reshape(bsz, h, n, p)


def _example(rng):
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    ks = jax.random.split(key, 5)
    bsz, s, h, p, n, chunk = 2, 64, 2, 16, 8, 16
    x = jax.random.normal(ks[0], (bsz, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h), jnp.float32))
    a_log = jax.random.normal(ks[2], (h,), jnp.float32) * 0.1
    b = jax.random.normal(ks[3], (bsz, s, n), jnp.float32)
    c = jax.random.normal(ks[4], (bsz, s, n), jnp.float32)
    return (x, dt, a_log, b, c, 16), {}


def _ssd_ref(x, dt, a_log, b, c, chunk, **kw):
    return ssd(x, dt, a_log, b, c, chunk, mode="xla_ref")


dispatch.register("ssd_chunk", fn=ssd, ref=_ssd_ref, example=_example)

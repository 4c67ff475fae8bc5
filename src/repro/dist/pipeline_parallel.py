"""GPipe-style pipeline parallelism over one mesh axis.

`gpipe` places stage s on device s of `axis` and streams M microbatches
through the ring with `ppermute`: at tick t device j runs its stage on
microbatch t-j, so the pipe drains in M + S - 1 ticks with the classic
bubble fraction (S-1)/(M+S-1) of idle device-ticks.

Composes with other axes (DP on "data" while PP on "pod"): specs mention
only `axis`, everything else is untouched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(microbatches: int, stages: int) -> float:
    """Idle fraction of the device-tick grid for a drained GPipe schedule."""
    return (stages - 1) / (microbatches + stages - 1)


def gpipe(stage, weights, xs, *, mesh, axis: str):
    """Run `stage(w_s, x)` for s = 0..S-1 composed in sequence, pipelined.

    weights: (S, ...) per-stage params, sharded over `axis` (one stage per
    device). xs: (M, ...) microbatches, replicated over `axis`. Output must
    have the same shape as a microbatch. Returns (M, ...) outputs,
    replicated.
    """
    s = int(mesh.shape[axis])
    m = int(xs.shape[0])
    if weights.shape[0] != s:
        raise ValueError(f"{weights.shape[0]} stages on a {s}-way "
                         f"'{axis}' axis")
    perm = [(i, (i + 1) % s) for i in range(s)]

    def body(w, xs):
        w = w[0]                                     # this device's stage
        idx = jax.lax.axis_index(axis)

        def tick(carry, t):
            out, recv = carry
            feed = xs[jnp.clip(t, 0, m - 1)]         # device-0 ingest
            x = jnp.where(idx == 0, feed, recv)
            y = stage(w, x)
            nxt = jax.lax.ppermute(y, axis, perm)
            done = t - (s - 1)                       # mb finishing this tick
            j = jnp.clip(done, 0, m - 1)
            keep = (idx == s - 1) & (done >= 0) & (done < m)
            out = out.at[j].set(jnp.where(keep, y, out[j]))
            return (out, nxt), None

        out0 = jnp.zeros(xs.shape, xs.dtype)
        (out, _), _ = jax.lax.scan(tick, (out0, jnp.zeros_like(xs[0])),
                                   jnp.arange(m + s - 1))
        # only the last device holds real outputs; broadcast to the ring
        return jax.lax.psum(jnp.where(idx == s - 1, out, 0.0), axis)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis), P()), out_specs=P(),
                         check_vma=False)(weights, xs)

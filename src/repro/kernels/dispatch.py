"""Unified kernel dispatch: one mode switch + registry for all families.

Every kernel family used to carry its own copy-pasted `_interpret()` probe
and `use_kernel` flag; this module centralizes that decision behind
`KernelMode` (the mamba-jax interface idiom) and keeps a registry of the
public ops so tests/tools can enumerate and parity-check every family
without knowing the packages:

- PALLAS:  always run the Pallas kernel (interpret mode off-TPU, compiled
           on TPU).
- XLA_REF: the pure-jnp oracle (ref.py) — differentiable, any backend.
- AUTO:    Pallas with autotuned block sizes (repro.kernels.tune); today
           resolves to PALLAS everywhere, and is the hook where future
           shape-based fallbacks live.

Ops accept `mode=` (str or KernelMode) plus the legacy `use_kernel=` bool
(False => XLA_REF) so existing call sites keep working.
"""
from __future__ import annotations

import enum
import importlib
from dataclasses import dataclass
from typing import Any, Callable, Mapping

import jax


class KernelMode(enum.Enum):
    PALLAS = "pallas"
    XLA_REF = "xla_ref"
    AUTO = "auto"


@dataclass(frozen=True)
class Resolved:
    """A concrete dispatch decision for one call."""
    use_pallas: bool
    interpret: bool
    tuned: bool        # consult the tune cache for block sizes


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(mode: KernelMode | str | None = None, *,
            use_kernel: bool = True) -> Resolved:
    """Collapse (mode, legacy use_kernel) into a Resolved decision."""
    if not use_kernel:
        mode = KernelMode.XLA_REF
    mode = KernelMode(mode) if mode is not None else KernelMode.AUTO
    if mode is KernelMode.XLA_REF:
        return Resolved(use_pallas=False, interpret=False, tuned=False)
    return Resolved(use_pallas=True, interpret=not on_tpu(),
                    tuned=mode is KernelMode.AUTO)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelOp:
    """One registered kernel family.

    fn/ref share the public signature; `fn` additionally accepts `mode=`.
    `example(rng)` returns (args, kwargs) exercising the op for parity and
    autotune sweeps. `tunables` maps block-size kwarg -> candidate values.
    """
    name: str
    fn: Callable
    ref: Callable
    tunables: Mapping[str, tuple]
    example: Callable[[Any], tuple]


_REGISTRY: dict[str, KernelOp] = {}

_OP_MODULES = ("scan_filter", "aggregate", "scan_aggregate",
               "scan_compressed", "group_aggregate", "mask_repack",
               "flash_attention", "decode_attention", "ssd_chunk")


def register(name: str, *, fn, ref, tunables=None, example=None) -> KernelOp:
    op = KernelOp(name=name, fn=fn, ref=ref,
                  tunables=dict(tunables or {}), example=example)
    _REGISTRY[name] = op
    return op


def ensure_registered() -> None:
    """Import every kernel family so module-level register() calls ran."""
    for mod in _OP_MODULES:
        importlib.import_module(f"repro.kernels.{mod}.ops")


def get(name: str) -> KernelOp:
    ensure_registered()
    return _REGISTRY[name]


def registered() -> dict[str, KernelOp]:
    ensure_registered()
    return dict(_REGISTRY)


# --------------------------------------------------------------------------
# launch accounting
# --------------------------------------------------------------------------
# Per-family call counters so tests can assert that batched execution
# really collapses N per-chunk launches into ~1 per (column, encoding)
# group. A "launch" is one call of a family's public op — Pallas kernel
# and XLA_REF oracle alike — made while the calling program is traced:
# inside jit the counter moves once per trace, not per execution, so a
# warm served query counts 0 (the device trace counts executions).
#
# The counters themselves live in repro.obs.metrics now: increments land
# in every active MetricsRegistry scope (an engine wrapping execution in
# its own scope sees only its own launches), and these four functions are
# backward-compatible shims over the always-active *default* scope — the
# exact semantics the old module-global dict had.

from repro.obs import metrics as _metrics  # noqa: E402  (import cycle:
#   obs.metrics is stdlib-only, safe below the jax import)


def count_launch(name: str, n: int = 1) -> None:
    """Record `n` dispatches for kernel family `name` (in every active
    metrics scope)."""
    _metrics.count_launch(name, n)


def record_batch(name: str, width: int, n_chunks: int) -> None:
    """Record one *batched* dispatch of family `name` covering `n_chunks`
    chunks at unified payload width `width` — the width-group detail the
    trace's launch spans carry. Does not add to launch_counts();
    count_launch still owns the dispatch count."""
    _metrics.record_batch(name, width, n_chunks)


def launch_counts() -> dict[str, int]:
    """Snapshot of per-family launch counts since the last reset (the
    default scope — process-global, as before)."""
    return _metrics.default_registry().launch_counts()


def total_launches() -> int:
    return _metrics.default_registry().total_launches()


def reset_launch_counts() -> None:
    """Reset the default scope's launch counters. Engine-scoped
    registries are unaffected — reset your own scope directly."""
    _metrics.default_registry().reset_launches()

"""Unified kernel dispatch: one mode switch + registry for all families.

Every kernel family takes its kernel-or-oracle decision here, behind
`KernelMode` (the mamba-jax interface idiom), and registers its public op
so tests/tools can enumerate and parity-check every family without
knowing the packages:

- PALLAS:  the Pallas kernel (interpret mode off-TPU, compiled on TPU);
           the default.
- XLA_REF: the pure-jnp oracle (ref.py) — differentiable, any backend.

Ops accept `mode=` as a str or KernelMode. Block sizes are fixed per
shape by each family's wrapper; nothing outside the call changes them.
"""
from __future__ import annotations

import enum
import importlib
from dataclasses import dataclass
from typing import Any, Callable

import jax


class KernelMode(enum.Enum):
    PALLAS = "pallas"
    XLA_REF = "xla_ref"


@dataclass(frozen=True)
class Resolved:
    """A concrete dispatch decision for one call."""
    use_pallas: bool
    interpret: bool


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(mode: KernelMode | str | None = None) -> Resolved:
    """Collapse `mode` (None means PALLAS) into a Resolved decision."""
    mode = KernelMode(mode) if mode is not None else KernelMode.PALLAS
    if mode is KernelMode.XLA_REF:
        return Resolved(use_pallas=False, interpret=False)
    return Resolved(use_pallas=True, interpret=not on_tpu())


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelOp:
    """One registered kernel family.

    fn/ref share the public signature; `fn` additionally accepts `mode=`.
    `example(rng)` returns (args, kwargs) exercising the op for parity.
    """
    name: str
    fn: Callable
    ref: Callable
    example: Callable[[Any], tuple]


_REGISTRY: dict[str, KernelOp] = {}

_OP_MODULES = ("scan_filter", "aggregate", "scan_aggregate",
               "scan_compressed", "group_aggregate", "mask_repack",
               "flash_attention", "decode_attention", "ssd_chunk")


def register(name: str, *, fn, ref, example=None) -> KernelOp:
    op = KernelOp(name=name, fn=fn, ref=ref, example=example)
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

"""Pallas TPU kernels (validated in interpret mode against ref.py oracles):

- scan_filter:       BitWeaving-H predicate scan (the paper's workload)
- aggregate:         fused masked aggregate (scan+aggregate query)
- mask_repack:       predicate mask from one code width's layout to
                     another's, bits moved across lanes on the MXU
- flash_attention:   blockwise online-softmax attention w/ causal skip
- decode_attention:  split-K one-token decode over the ring KV cache
                     (kernel-native (B, KVH, S, D) layout — the models'
                     cache is stored this way, so decode is zero-copy)
- ssd_chunk:         Mamba-2 SSD chunk scan with VMEM-carried state

Each package: kernel.py (pallas_call + BlockSpec), ops.py (public jit'd
wrapper), ref.py (pure-jnp oracle).

Dispatch architecture (dispatch.py): every ops.py routes through one
KernelMode switch — PALLAS (the kernel, interpret mode off-TPU; the
default) or XLA_REF (the oracle; differentiable) — and registers itself
in a KernelOp registry carrying its oracle and an example-input factory,
so tests and tools can enumerate and parity-check every family
generically. Each wrapper picks its block sizes from the shape alone
(its DEFAULT_* constants, clamped to the shape); no file or environment
variable changes them.
"""

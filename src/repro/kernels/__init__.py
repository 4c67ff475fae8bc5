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
KernelMode switch — PALLAS (the kernel, interpret mode off-TPU), XLA_REF
(the oracle; differentiable), AUTO (kernel + autotuned block sizes) — and
registers itself in a KernelOp registry carrying its oracle, tunable
block-size grid, and an example-input factory, so tests and tools can
enumerate and parity-check every family generically. The legacy
`use_kernel=False` flag maps to XLA_REF.

Autotuning (tune.py): ops consult a JSON on-disk cache (keyed by
op | backend | shape) for block sizes instead of hardcoding DEFAULT_*
constants; `tune.autotune` runs the timed sweep that populates it (wired
into benchmarks/kernels_bench.py, trajectory in BENCH_kernels.json).
"""

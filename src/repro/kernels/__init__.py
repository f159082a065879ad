"""Pallas TPU kernels for the compute hot-spots of the scale-out HDC system.

Each subpackage has kernel.py (pl.pallas_call + BlockSpec VMEM tiling), ops.py
(jit'd public wrapper with padding + backend dispatch) and ref.py (pure-jnp oracle
used by the allclose test sweeps).

* hamming/      packed XOR+popcount similarity search (memory-bound IMC path),
  incl. the fused top-1 `hamming_topk_banked` (a bipolar matmul on the MXU
  over bit planes unpacked in VMEM, class axis reduced in VMEM — the
  [G, B, C] distance tensor never reaches HBM; EXPERIMENTS.md §Perf)
* majority/     bit-wise majority bundling (the op the paper computes over-the-air)
* assoc_matmul/ bipolar MXU matmul (compute-bound IMC crossbar MVM analogue)
* flash_attention/ fused causal attention fwd (the fix for the dominant
  memory term of EXPERIMENTS.md §Roofline: block temporaries stay in VMEM)
"""

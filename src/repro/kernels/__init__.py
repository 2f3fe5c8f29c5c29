"""Pallas TPU kernels for the NonGEMM hot spots NonGEMM Bench identifies.

Layout (per assignment):
    <name>.py  pl.pallas_call + explicit BlockSpec VMEM tiling
    ops.py     jit'd wrappers with the interpret switch (nn backend);
               Mosaic by default, interpret mode only when asked for
    ref.py     pure-jnp oracles (the allclose ground truth)

Kernels: norms (rmsnorm / layernorm / fused add+rmsnorm / fused
add+layernorm / fused dequant+add+rmsnorm), rope (fused rotary
application), swiglu / geglu, flash_attention (causal / window / GQA),
softmax_xent (262k-vocab CE), nms (RoI Selection, TPU-adapted).

The ``fused_*`` / ``dequant_*`` entries back the operator-fusion subsystem
(``repro.core.fusion``): each is the single-launch implementation of a
NonGEMM chain the fusion pass rewrites.
"""

"""Pallas TPU kernels for the paper's compute hot-spots.

gram/        tiled gram-block  G = X Y^T       (MXU)
quant/       per-symbol encode/decode (§4.2)   (VPU threshold-count / one-hot)
qgram/       fused dequantize + gram           (decode in VMEM, no HBM roundtrip)
epilogue/    fused Nyström serve epilogue      (cached apply + fusion moments,
             one launch per query batch)

Each has <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd public wrapper,
padding + backend dispatch through runtime.choose) and ref.py (pure-jnp oracle
used by the shape/dtype-sweep allclose tests).  ``runtime`` is the shared
dispatch policy + registry + persistent autotune cache (docs/kernel_runtime.md).
"""
from . import runtime
from .gram import ops as gram_ops
from .quant import ops as quant_ops
from .qgram import ops as qgram_ops
from .epilogue import ops as epilogue_ops

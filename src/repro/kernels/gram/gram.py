"""Pallas TPU kernel: tiled gram-block computation  G = X @ Y^T.

This is the hot loop of the paper's distributed GP: every cross-machine block
G_ij of the gram matrix is an inner-product matrix between (reconstructed)
datasets.  Tiling: grid (n/bn, p/bp, d/bd); X and Y stream HBM->VMEM in
(bn, bd)/(bp, bd) tiles; the (bn, bp) fp32 accumulator tile lives in VMEM
across the k-steps (revisited output), hitting the MXU with 128-aligned dots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK = (128, 128, 128)  # (bn, bp, bd) — MXU-aligned


def _gram_kernel(x_ref, y_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...],
        y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),  # X @ Y^T
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gram_pallas(x, y, *, block=DEFAULT_BLOCK, interpret=False):
    """x: (n, d), y: (p, d) -> (n, p) fp32.  Shapes must be block-multiples
    (ops.py pads)."""
    n, d = x.shape
    p, _ = y.shape
    bn, bp, bd = block
    grid = (n // bn, p // bp, d // bd)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bp, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, p), jnp.float32),
        interpret=interpret,
    )(x, y)

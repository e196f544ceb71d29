"""Pallas TPU kernel: FUSED unpack + dequantize + gram from PACKED words.

The wire/at-rest representation of quantized data is the packed code plane
(``repro.core.jax_scheme.pack_codes``): each row's d codes concatenated at
their per-dimension widths into W = ceil(R/32) uint32 words.  This kernel
consumes that plane DIRECTLY — the (bn, W) word tile is unpacked with
shift/mask ops inside the block, decoded against the scaled centroid tables
by a chunked one-hot matmul, and fed to the MXU — so neither the int codes
nor the fp32 reconstruction ever exists in HBM.

Grid (n/bn, p/bp); d and W are NOT tiled (W is 1-2 words for paper rates,
d <= a few hundred), so each (i, j) program writes its output tile once —
no cross-step accumulator.  The decode depends on the row tile alone: the
first column step of each row (j == 0) decodes it into a (bn, d) VMEM
scratch and every step of the row multiplies that scratch against its y
tile, so a call decodes n/bn row tiles, not n/bn x p/bp.  The column axis is
the grid's last and "arbitrary" (its steps run in order on one core); the
row axis stays "parallel".  The per-dimension bit layout arrives as a small
``meta`` operand (word index / bit offset / width per dimension, possibly
traced); word selection is a static W-step select loop, not a dynamic
gather, so the kernel lowers on TPU as well as in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_PACKED = (128, 128)  # (bn, bp)
DEFAULT_ECHUNK = 128
_WORD = 32


def _decode_rows(words_ref, meta_ref, cents_ref, mask_ref, *, echunk: int):
    """The row tile's (bn, d) reconstruction: unpack, one-hot decode, mask."""
    words = words_ref[...]  # (bn, W) uint32
    W = words.shape[1]
    word_idx = meta_ref[0, :]  # (d,) int32
    bit_i = meta_ref[1, :]
    width_i = meta_ref[2, :]
    # shift amounts are clamped in int32 and only then cast: Mosaic has no
    # unsigned max/min (arith.maxui / arith.minui do not legalize on TPU)
    bit = bit_i.astype(jnp.uint32)
    hi_shift = (_WORD - jnp.maximum(bit_i, 1)).astype(jnp.uint32)
    lo_width = jnp.minimum(width_i, _WORD - 1).astype(jnp.uint32)

    # select each dimension's source word(s) with a static W-step select loop
    # (TPU-safe: no dynamic gather on the lane axis)
    lo_src = jnp.zeros((words.shape[0], word_idx.shape[0]), jnp.uint32)
    hi_src = jnp.zeros_like(lo_src)
    for k in range(W):
        col = words[:, k][:, None]  # (bn, 1)
        lo_src = jnp.where(word_idx[None, :] == k, col, lo_src)
        hi_src = jnp.where(word_idx[None, :] + 1 == k, col, hi_src)

    lo = lo_src >> bit[None, :]
    hi = jnp.where(
        bit_i[None, :] > 0, hi_src << hi_shift[None, :], jnp.uint32(0)
    )
    full = jnp.uint32(0xFFFFFFFF)
    wmask = jnp.where(
        width_i >= _WORD, full, (jnp.uint32(1) << lo_width) - jnp.uint32(1)
    )
    codes = ((lo | hi) & wmask[None, :]).astype(jnp.int32)  # (bn, d) in VMEM

    # dequantize: chunked one-hot matmul against the scaled centroid tables
    n_chunks = cents_ref.shape[1] // echunk

    def body(c, acc):
        cents = cents_ref[:, pl.dslice(c * echunk, echunk)]  # (d, echunk)
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, echunk), 2) + c * echunk
        onehot = (codes[:, :, None] == idx).astype(cents.dtype)
        return acc + jnp.sum(onehot * cents[None, :, :], axis=-1)

    xhat = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros(codes.shape, dtype=jnp.float32)
    )  # (bn, d) decoded in VMEM — codes and x̂ never touch HBM
    return xhat * mask_ref[...]  # (bn, 1): masked rows contribute zero rows


def _qgram_packed_kernel(
    words_ref, meta_ref, cents_ref, y_ref, mask_ref, o_ref, xhat_ref, *,
    echunk: int,
):
    @pl.when(pl.program_id(1) == 0)
    def _decode():  # once per row tile, kept for the row's column steps
        xhat_ref[...] = _decode_rows(
            words_ref, meta_ref, cents_ref, mask_ref, echunk=echunk
        )

    o_ref[...] = jax.lax.dot_general(
        xhat_ref[...],
        y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("block", "echunk", "interpret"))
def qgram_packed_pallas(
    words, meta, scaled_cents, y, mask, *, block=DEFAULT_BLOCK_PACKED,
    echunk=DEFAULT_ECHUNK, interpret=False,
):
    """words: (n, W) uint32 packed rows; meta: (3, d) int32 [word, bit, width]
    per dimension; scaled_cents: (d, C); y: (p, d); mask: (n, 1) row validity
    -> (n, p) fp32.  All shapes pre-padded to block multiples by the caller."""
    n, _ = words.shape
    p, _ = y.shape
    bn, bp = block
    grid = (n // bn, p // bp)
    return pl.pallas_call(
        functools.partial(_qgram_packed_kernel, echunk=echunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, words.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec(meta.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(scaled_cents.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((bp, y.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bp), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, y.shape[1]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(words, meta, scaled_cents, y, mask)

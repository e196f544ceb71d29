"""Pallas TPU kernel: fused Nyström serve epilogue (decode-attn pattern).

Grid (m,): one step per expert, streaming that expert's (t, K) cross-gram
tile and its K x K cached operands HBM->VMEM; the (ROWS, t) fp32 moment
accumulator lives in the revisited output tile across steps (the same
output-accumulator-only shape as the gram and decode_attn kernels).  Each
step runs the expert's cached apply — two MXU matmuls against ``Ainv`` and
the woodbury projector ``P`` — and folds the resulting predictive straight
into the fusion's moment rows, so the whole serve tail between the
cross-gram and ``finalize`` is ONE kernel launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fp32 sublane tile: the (3, t) moment rows ride in an 8-row output block
ROWS = 8
LANE = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = 4
# the scoped-VMEM floor of the TPU generations this repo targets (v5e: 16 MiB)
_VMEM_FLOOR = 16 << 20


def _compiler_params(bt: int, K: int):
    """Scoped-VMEM limit sized to one grid step's working set.

    Every step double-buffers its expert's (bt, K) cross-gram tile and the
    two (K, K) blocks ``Ainv`` and ``P``, and holds the (bt, K) temporaries
    ``Bt`` and ``Q``.  At K=1152 (one machine's shard of SARCOS at m=40)
    the compiler asks for 21.4 MiB, past the 16 MiB v5e grants a kernel by
    default but far inside its 128 MiB of VMEM, so the limit follows the
    shapes instead of tiling K (which would split the quad form
    ``Bt P Bt^T`` over two passes with a VMEM scratch for ``Bt``)."""
    step_in = bt * K + 2 * K * K + K + 3 * bt
    work = 2 * step_in + 2 * ROWS * bt + 4 * bt * K
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(_VMEM_FLOOR, 2 * work * _F32)
    )


def _epilogue_kernel(g_ref, a_ref, p_ref, wa_ref, gss_ref, prior_ref, w_ref,
                     o_ref, *, fuse):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    G = g_ref[0]        # (t, K)
    A = a_ref[0]        # (K, K)  Ainv
    P = p_ref[0]        # (K, K)
    wa = wa_ref[0]      # (1, K)
    gss = gss_ref[...]  # (1, t)
    prior = prior_ref[...]
    w = w_ref[0]        # (1, t) — expert weight broadcast over test points

    # B^T = G Ainv^T : the triangular solve of nystrom_apply, cached as a matmul
    Bt = jax.lax.dot_general(
        G, A, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (t, K)
    mu = jnp.sum(Bt * wa, axis=1, keepdims=True).T  # (1, t)
    Q = jax.lax.dot_general(
        Bt, P, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (t, K) = B^T P  (P symmetric)
    quad = jnp.sum(Bt * Q, axis=1, keepdims=True).T
    s2 = jnp.maximum(gss - quad, 1e-12)  # expert predictive variance

    # fusion moment rows — MUST mirror FusionSpec.moments term for term
    if fuse == "none":
        r0, r1, r2 = mu, s2, w
    elif fuse == "kl":
        r0, r1, r2 = w * mu, w * (s2 + mu * mu), w
    elif fuse == "rbcm":
        beta = 0.5 * (jnp.log(prior) - jnp.log(s2)) * w
        r0, r1, r2 = beta / s2, beta * mu / s2, beta
    else:  # poe / gpoe / bcm share precision rows
        r0, r1, r2 = w / s2, w * mu / s2, w

    pad = jnp.zeros((ROWS - 3, mu.shape[1]), jnp.float32)
    o_ref[...] += jnp.concatenate([r0, r1, r2, pad], axis=0)


def _epilogue_fleet_kernel(g_ref, a_ref, p_ref, wa_ref, gss_ref, prior_ref,
                           w_ref, o_ref, *, fuse):
    # grid (T, t-tiles, m): expert axis innermost, so each tenant's output
    # tile is revisited across its m experts with the accumulator init at
    # the first expert — tenants NEVER share an accumulator row (summing
    # all T*m experts into one tile would fuse tenants together)
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    G = g_ref[0, 0]        # (bt, K)
    A = a_ref[0, 0]        # (K, K)  Ainv
    P = p_ref[0, 0]        # (K, K)
    wa = wa_ref[0, 0]      # (1, K)
    gss = gss_ref[0]       # (1, bt)
    prior = prior_ref[0]
    w = w_ref[0, 0]        # (1, bt)

    Bt = jax.lax.dot_general(
        G, A, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (bt, K)
    mu = jnp.sum(Bt * wa, axis=1, keepdims=True).T  # (1, bt)
    Q = jax.lax.dot_general(
        Bt, P, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )
    quad = jnp.sum(Bt * Q, axis=1, keepdims=True).T
    s2 = jnp.maximum(gss - quad, 1e-12)

    # fusion moment rows — MUST mirror FusionSpec.moments term for term
    if fuse == "none":
        r0, r1, r2 = mu, s2, w
    elif fuse == "kl":
        r0, r1, r2 = w * mu, w * (s2 + mu * mu), w
    elif fuse == "rbcm":
        beta = 0.5 * (jnp.log(prior) - jnp.log(s2)) * w
        r0, r1, r2 = beta / s2, beta * mu / s2, beta
    else:  # poe / gpoe / bcm share precision rows
        r0, r1, r2 = w / s2, w * mu / s2, w

    pad = jnp.zeros((ROWS - 3, mu.shape[1]), jnp.float32)
    o_ref[0] += jnp.concatenate([r0, r1, r2, pad], axis=0)


@functools.partial(jax.jit, static_argnames=("fuse", "block", "interpret"))
def epilogue_fleet_pallas(G, Ainv, P, walpha, gss, prior, w, *, fuse,
                          block=None, interpret=False):
    """Tenant-batched fused serve epilogue: G (T, m, t, K); Ainv/P
    (T, m, K, K); walpha (T, m, 1, K); gss/prior (T, 1, t); w (T, m, 1, t).
    t and K must be LANE-multiples (ops.py pads); ``block`` is the tuned
    t-tile (None = full t, must divide t).  Returns the (T, ROWS, t)
    accumulator; rows [:, :3] are each tenant's summed fusion moments."""
    T, m, t, K = G.shape
    bt = t if block is None else int(block)
    grid = (T, t // bt, m)
    return pl.pallas_call(
        functools.partial(_epilogue_fleet_kernel, fuse=fuse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bt, K), lambda i, s, j: (i, j, s, 0)),
            pl.BlockSpec((1, 1, K, K), lambda i, s, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, K, K), lambda i, s, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, K), lambda i, s, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, bt), lambda i, s, j: (i, 0, s)),
            pl.BlockSpec((1, 1, bt), lambda i, s, j: (i, 0, s)),
            pl.BlockSpec((1, 1, 1, bt), lambda i, s, j: (i, j, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, ROWS, bt), lambda i, s, j: (i, 0, s)),
        out_shape=jax.ShapeDtypeStruct((T, ROWS, t), jnp.float32),
        compiler_params=_compiler_params(bt, K),
        interpret=interpret,
    )(G, Ainv, P, walpha, gss, prior, w)


@functools.partial(jax.jit, static_argnames=("fuse", "interpret"))
def epilogue_pallas(G, Ainv, P, walpha, gss, prior, w, *, fuse,
                    interpret=False):
    """G: (m, t, K); Ainv/P: (m, K, K); walpha: (m, 1, K); gss/prior: (1, t);
    w: (m, 1, t).  t and K must be LANE-multiples (ops.py pads).  Returns the
    (ROWS, t) accumulator; rows 0..2 are the summed fusion moments S."""
    m, t, K = G.shape
    return pl.pallas_call(
        functools.partial(_epilogue_kernel, fuse=fuse),
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, t, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, t), lambda i: (0, 0)),
            pl.BlockSpec((1, t), lambda i: (0, 0)),
            pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, t), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ROWS, t), jnp.float32),
        compiler_params=_compiler_params(t, K),
        interpret=interpret,
    )(G, Ainv, P, walpha, gss, prior, w)

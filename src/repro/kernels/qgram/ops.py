"""Public wrappers for the fused dequantize+gram kernels.

Two entry points:

* :func:`qgram_packed` / :func:`qgram_packed_batched` — the PRIMARY path:
  consume the packed code plane (``jax_scheme.pack_codes`` uint32 words, the
  same buffer the collectives move and the checkpoints store) and fuse
  unpack + dequantize + gram in one tiled Pallas kernel (:mod:`.packed`).
* :func:`qgram` / :func:`qgram_batched` — the legacy unpacked-int-code API,
  kept for callers holding raw (n, d) int32 codes.

Backend selection is the unified runtime policy
(:func:`repro.kernels.runtime.choose`): compiled Pallas on TPU, the
equivalent single-jit XLA program elsewhere, ``interpret=True`` /
``REPRO_FORCE_PALLAS=1`` to force the kernel path for debugging.  On the
compiled path, block sizes are autotuned per (shape, dtype, bits, backend)
through the runtime's PERSISTENT cache (:func:`repro.kernels.runtime
.autotune`): the sweep runs once per key per cache file, warm processes pad
only to the cached winner instead of the largest tune candidate.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from ...core import jax_scheme
from .. import runtime
from .qgram import qgram_pallas, DEFAULT_BLOCK, DEFAULT_ECHUNK
from .packed import qgram_packed_pallas, DEFAULT_BLOCK_PACKED
from .ref import qgram_ref, qgram_packed_ref


def _pad_axis(a, mult, axis, value=0):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


# --------------------------------------------------------------------------
# the packed plane: words straight from the wire/checkpoint
# --------------------------------------------------------------------------


import functools


@functools.partial(jax.jit, static_argnames=("total_bits", "has_mask"))
def _qgram_packed_xla(words, rates, scaled_cents, y, mask, total_bits, has_mask):
    """XLA fallback: the same unpack -> decode -> matmul as ONE jitted
    program (no intermediate dispatch, no HBM round-trip between stages)."""
    codes = jax_scheme.unpack_codes(words, rates, total_bits=total_bits)
    d = scaled_cents.shape[0]
    xhat = scaled_cents[jnp.arange(d), codes]  # (n, d)
    if has_mask:
        xhat = xhat * mask[:, None]
    return xhat @ jnp.asarray(y, jnp.float32).T


# candidate menu lives in the runtime's central registry (satellite of the
# fleet-epilogue work: every family's sweep table is declared next to its
# KernelImpl and enumerable from one place)
_TUNE_CANDIDATES = runtime.register_tune_candidates(
    "qgram_packed", ((128, 128), (256, 128), (128, 256), (256, 256))
)

# kept as a name (tests/benchmarks import it); the policy is runtime's
_interpret_autotune = runtime.interpret_autotune


def _padded_inputs(words, rates, scaled_cents, y, mask, echunk, bn, bp):
    """Pad every operand to the given block (rows masked to zero)."""
    n = words.shape[0]
    mask_col = (
        jnp.ones((n, 1), jnp.float32) if mask is None
        else jnp.asarray(mask, jnp.float32)[:, None]
    )
    wpad = _pad_axis(words, bn, 0)
    mpad = _pad_axis(mask_col, bn, 0)
    tpad = _pad_axis(_pad_axis(jnp.asarray(scaled_cents), 8, 0), echunk, 1)
    d_pad = tpad.shape[0]
    ypad = _pad_axis(_pad_axis(jnp.asarray(y, jnp.float32), bp, 0), d_pad, 1)
    meta = _pack_meta(rates, d_pad)
    return wpad, meta, tpad, ypad, mpad


def _autotune_block(words, rates, scaled_cents, y, mask, echunk, total_bits,
                    interpret):
    """Resolve the (bn, bp) block for this logical shape via the runtime's
    persistent cache: a warm hit (this process or any earlier one that wrote
    the cache file) returns immediately with ZERO sweeps; a miss times one
    compiled run of each candidate on max-candidate-padded inputs, persists
    the winner, and returns it."""
    key = runtime.cache_key(
        "qgram_packed",
        shapes=(words.shape, scaled_cents.shape, y.shape),
        dtype=words.dtype,
        bits=total_bits,
        extra=(f"echunk={echunk}",),
    )
    cands = runtime.tune_candidates("qgram_packed")
    max_bn = max(c[0] for c in cands)
    max_bp = max(c[1] for c in cands)
    padded = None  # built lazily: only a cache MISS pays the max-pad

    def measure(cand):
        nonlocal padded
        if padded is None:
            padded = _padded_inputs(
                words, rates, scaled_cents, y, mask, echunk, max_bn, max_bp
            )
        wpad, meta, tpad, ypad, mpad = padded
        bn, bp = cand
        if wpad.shape[0] % bn or ypad.shape[0] % bp:
            return None
        fn = lambda: qgram_packed_pallas(
            wpad, meta, tpad, ypad, mpad, block=(bn, bp), echunk=echunk,
            interpret=interpret,
        )
        jax.block_until_ready(fn())  # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    return runtime.autotune(key, cands, measure, DEFAULT_BLOCK_PACKED)


def _pack_meta(rates, d_pad):
    """(3, d_pad) int32 [word index, bit offset, width] rows for the kernel;
    padded dimensions get width 0 (they unpack to code 0 and decode to the
    zero-padded centroid rows)."""
    w = jnp.asarray(rates, jnp.int32)
    w = jnp.concatenate([w, jnp.zeros((d_pad - w.shape[0],), jnp.int32)])
    offs = jnp.cumsum(w) - w
    return jnp.stack([offs // 32, offs % 32, w])


def _qgram_packed_kernel_path(
    words, rates, scaled_cents, y, *, total_bits, interpret,
    mask=None, block=None, echunk=DEFAULT_ECHUNK,
):
    words = jnp.asarray(words)
    n, p = words.shape[0], y.shape[0]
    traced = any(
        isinstance(a, jax.core.Tracer)
        for a in (words, rates, scaled_cents, y)
        + (() if mask is None else (mask,))
    )
    autotune = (
        block is None and not traced and (not interpret or _interpret_autotune())
    )
    if autotune:
        bn, bp = _autotune_block(
            words, rates, scaled_cents, y, mask, echunk, total_bits, interpret
        )
    else:
        bn, bp = DEFAULT_BLOCK_PACKED if block is None else block
    # pad to the CHOSEN block only — the old path padded every autotuned call
    # to the largest tune candidate even when the cached winner was small
    wpad, meta, tpad, ypad, mpad = _padded_inputs(
        words, rates, scaled_cents, y, mask, echunk, bn, bp
    )
    out = qgram_packed_pallas(
        wpad, meta, tpad, ypad, mpad, block=(bn, bp), echunk=echunk,
        interpret=interpret,
    )
    return out[:n, :p]


def qgram_packed(
    words, rates, scaled_cents, y, *, total_bits: int, mask=None,
    block=None, echunk=DEFAULT_ECHUNK, interpret=None,
):
    """G = decode(unpack(words)) @ y^T straight from the packed code plane.

    words: (n, W) uint32 packed rows (``jax_scheme.pack_codes`` layout, W =
    ceil(total_bits/32)); rates: (d,) per-dimension widths (may be traced);
    scaled_cents: (d, C) from ``jax_scheme.scaled_centroids``; y: (p, d);
    mask: optional (n,) row validity — masked rows produce zero output rows
    (the packed twin of the old -1-sentinel behavior); total_bits: the static
    row bit budget the words were packed under."""
    words = jnp.asarray(words)
    d = runtime.choose(interpret)
    if words.shape[-1] == 0 or d.kind == "xla":
        # zero-rate rows have no words at all — nothing for a kernel block to
        # load; the XLA program handles the degenerate layout
        m = None if mask is None else jnp.asarray(mask, jnp.float32)
        return _qgram_packed_xla(
            words, rates, scaled_cents, y, m, total_bits, mask is not None
        )
    return _qgram_packed_kernel_path(
        words, rates, scaled_cents, y, total_bits=total_bits,
        interpret=d.interpret, mask=mask, block=block, echunk=echunk,
    )


def qgram_packed_decodes(words_shape) -> int:
    """Row-tile decodes one traced :func:`qgram_packed` call on words of
    shape ``(n, W)`` makes: one per row tile of the default block's grid,
    however many column tiles it has; 0 where the call runs the XLA program
    instead."""
    n, n_words = words_shape[-2:]
    if n_words == 0 or runtime.choose().kind == "xla":
        return 0
    return -(-n // DEFAULT_BLOCK_PACKED[0])


def qgram_packed_batched(words, rates, scaled_cents, y, *, total_bits, mask=None, **kw):
    """vmapped :func:`qgram_packed` over a leading machine axis.

    words: (m, n, W); rates: (m, d); scaled_cents: (m, d, C); y: (p, d)
    shared or (m, p, d) per-machine; mask: optional (m, n).  Returns
    (m, n, p)."""
    run = lambda w, r, t, yy, mk: qgram_packed(
        w, r, t, yy, total_bits=total_bits, mask=mk, **kw
    )
    in_axes = (0, 0, 0, 0 if y.ndim == 3 else None, None if mask is None else 0)
    return jax.vmap(run, in_axes=in_axes)(words, rates, scaled_cents, y, mask)


# --------------------------------------------------------------------------
# legacy unpacked-int-code API
# --------------------------------------------------------------------------


@jax.jit
def _qgram_xla(codes, scaled_cents, y):
    d = scaled_cents.shape[0]
    xhat = jnp.where(
        codes >= 0, scaled_cents[jnp.arange(d), jnp.maximum(codes, 0)], 0.0
    )
    return xhat @ jnp.asarray(y, jnp.float32).T


def _qgram_kernel_path(codes, scaled_cents, y, *, interpret,
                       block=DEFAULT_BLOCK, echunk=DEFAULT_ECHUNK):
    n, d = codes.shape
    p = y.shape[0]
    bn, bp, bd = block
    # pad codes with an out-of-range code so padded dims decode to 0
    cpad = _pad_axis(_pad_axis(jnp.asarray(codes), bn, 0), bd, 1, value=-1)
    tpad = _pad_axis(_pad_axis(jnp.asarray(scaled_cents), bd, 0), echunk, 1)
    ypad = _pad_axis(_pad_axis(jnp.asarray(y, jnp.float32), bp, 0), bd, 1)
    out = qgram_pallas(cpad, tpad, ypad, block=block, echunk=echunk, interpret=interpret)
    return out[:n, :p]


def qgram(codes, scaled_cents, y, *, block=DEFAULT_BLOCK, echunk=DEFAULT_ECHUNK, interpret=None):
    """G = decode(codes) @ y^T without materializing the reconstruction.

    codes: (n, d) int32 per-symbol codes (-1 decodes to 0); scaled_cents:
    (d, C); y: (p, d).  Prefer :func:`qgram_packed` — it eats the wire's
    packed words directly."""
    d = runtime.choose(interpret)
    if d.kind == "xla":
        return _qgram_xla(jnp.asarray(codes), scaled_cents, y)
    return _qgram_kernel_path(
        codes, scaled_cents, y, interpret=d.interpret, block=block, echunk=echunk
    )


def qgram_batched(codes, scaled_cents, y, **kw):
    """vmapped fused dequantize+gram over a leading machine axis.

    codes: (m, n, d) int32 (pad rows with -1 so they decode to 0);
    scaled_cents: (m, d, C) per-machine tables; y: (p, d) shared or (m, p, d)
    per-machine.  Returns (m, n, p)."""
    if y.ndim == 2:
        return jax.vmap(lambda c, t: qgram(c, t, y, **kw))(codes, scaled_cents)
    return jax.vmap(lambda c, t, yy: qgram(c, t, yy, **kw))(codes, scaled_cents, y)


runtime.register_kernel_op(runtime.KernelImpl(
    name="qgram",
    pallas=_qgram_kernel_path,
    xla=lambda c, t, y, block=None, echunk=None: _qgram_xla(jnp.asarray(c), t, y),
    ref=qgram_ref,
))
runtime.register_kernel_op(runtime.KernelImpl(
    name="qgram_packed",
    pallas=_qgram_packed_kernel_path,
    xla=lambda w, r, t, y, *, total_bits, mask=None, block=None, echunk=None:
        _qgram_packed_xla(
            jnp.asarray(w), r, t, y,
            None if mask is None else jnp.asarray(mask, jnp.float32),
            total_bits, mask is not None,
        ),
    ref=qgram_packed_ref,
))

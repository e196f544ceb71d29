"""Roofline share of the ``qgram_packed`` kernel in a broadcast fit (layer:
kernels): the least time of the fits' inner products over the kernel's time.

Per fit, every machine i needs <x_hat_j, x_i> for each of the m - 1 other
machines j: the inner products of j's decoded points (unpacked and
dequantized from its packed words inside the kernel) with i's exact points
in j's decorrelated basis.  The work counts those m (m - 1) blocks, the
algorithm's own; the diagonal blocks a program computes and discards do not
count.
"""
import math

# the kernel's jitted wrapper, as HLO names its custom call (also inside a
# vmap: ``vmap_jit_qgram_packed_pallas__``)
PATTERN = r"qgram_packed_pallas"
WORD_BITS = 32


def work(cfg: dict) -> tuple:
    """(flops, bytes) of the inner products one broadcast fit needs."""
    m, d = cfg["m"], cfg["d"]
    n = math.ceil(cfg["n_train"] / m)
    pairs = m * (m - 1)
    row_bits = min(cfg["bits_per_sample"], d * cfg["max_bits"])
    words = math.ceil(row_bits / WORD_BITS)
    levels = 2 ** min(cfg["max_bits"], cfg["bits_per_sample"])
    flops = pairs * 2 * n * n * d
    nbytes = 4 * (m * n * words           # each machine's packed words
                  + m * d * levels        # its scaled centroid tables
                  + pairs * n * d         # receivers' points in its basis
                  + m * n                 # row validity
                  + pairs * n * n)        # the inner-product blocks out
    return flops, nbytes


def read(ctx):
    fits = ctx.counters.get("fits")
    if not fits:
        return None
    flops, nbytes = work(ctx.cfg)
    return ctx.roofline(PATTERN, fits * flops, fits * nbytes)

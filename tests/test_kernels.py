"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import quantizers as Q
from repro.kernels.gram.ops import gram
from repro.kernels.gram.ref import gram_ref
from repro.kernels.quant.ops import encode, decode, build_scaled_tables
from repro.kernels.quant.ref import encode_ref, decode_ref
from repro.kernels.qgram.ops import qgram
from repro.kernels.qgram.ref import qgram_ref


GRAM_SHAPES = [
    (8, 4, 8),        # tiny, all padding
    (128, 128, 128),  # exact single tile
    (130, 20, 50),    # ragged every axis
    (256, 384, 128),  # multi-tile
    (1, 1, 1),        # degenerate
]


@pytest.mark.parametrize("n,d,p", GRAM_SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_gram_matches_ref(n, d, p, dtype):
    rng = np.random.default_rng(n * 1000 + d)
    x = rng.normal(size=(n, d)).astype(dtype)
    y = rng.normal(size=(p, d)).astype(dtype)
    out = np.asarray(gram(x, y, interpret=True))
    ref = np.asarray(gram_ref(x, y))
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
    assert out.shape == (n, p) and out.dtype == np.float32


@pytest.mark.parametrize("block", [(128, 128, 128), (256, 128, 128)])
def test_gram_block_shapes(block):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(100, 40)).astype(np.float32)
    y = rng.normal(size=(60, 40)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(gram(x, y, block=block, interpret=True)),
        np.asarray(gram_ref(x, y)), rtol=1e-5, atol=1e-4,
    )


def _tables(rng, d, total_bits, max_bits=8):
    var = rng.uniform(0.05, 4.0, size=d)
    rates = Q.allocate_bits_greedy(var, total_bits, max_bits)
    sigma = np.sqrt(var).astype(np.float32)
    return sigma, rates, build_scaled_tables(sigma, rates)


@pytest.mark.parametrize("n,d,bits", [(64, 8, 24), (200, 20, 60), (128, 128, 200), (3, 5, 0)])
def test_quant_encode_decode_match_ref(n, d, bits):
    rng = np.random.default_rng(d)
    sigma, rates, (edges, cents) = _tables(rng, d, bits)
    x = (rng.normal(size=(n, d)) * sigma).astype(np.float32)
    ce = np.asarray(encode(x, edges, interpret=True))
    cr = np.asarray(encode_ref(jnp.asarray(x), edges))
    np.testing.assert_array_equal(ce, cr)
    xe = np.asarray(decode(jnp.asarray(ce), cents, interpret=True))
    xr = np.asarray(decode_ref(jnp.asarray(cr), cents))
    np.testing.assert_allclose(xe, xr, rtol=1e-6)


def test_quant_kernel_agrees_with_core_quantizers():
    rng = np.random.default_rng(7)
    d = 16
    sigma, rates, (edges, cents) = _tables(rng, d, 48)
    x = (rng.normal(size=(100, d)) * sigma).astype(np.float32)
    et, ct = Q.build_codebook_tables(int(max(rates.max(), 1)))
    c_core = Q.quantize(jnp.asarray(x), jnp.asarray(sigma), jnp.asarray(rates), et)
    c_kern = encode(x, edges, interpret=True)
    np.testing.assert_array_equal(np.asarray(c_core), np.asarray(c_kern))
    x_core = Q.dequantize(c_core, jnp.asarray(sigma), jnp.asarray(rates), ct)
    x_kern = decode(c_kern, cents, interpret=True)
    np.testing.assert_allclose(np.asarray(x_core), np.asarray(x_kern), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,d,p,bits", [(64, 8, 32, 24), (130, 20, 33, 60), (128, 128, 128, 256)])
def test_qgram_fused_matches_ref(n, d, p, bits):
    rng = np.random.default_rng(n + d)
    sigma, rates, (edges, cents) = _tables(rng, d, bits)
    x = (rng.normal(size=(n, d)) * sigma).astype(np.float32)
    y = rng.normal(size=(p, d)).astype(np.float32)
    codes = encode(x, edges, interpret=True)
    out = np.asarray(qgram(codes, cents, y, interpret=True))
    ref = np.asarray(qgram_ref(jnp.asarray(codes), cents, jnp.asarray(y)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,d,p,bits", [
    (64, 8, 32, 24), (130, 20, 33, 60), (50, 6, 20, 0),
    # 3 row tiles x 3 column tiles of the default (128, 128) block: each row
    # tile is decoded at its first column step and reused by the next two
    (260, 21, 300, 64), (300, 8, 260, 25),
])
def test_qgram_packed_matches_ref(n, d, p, bits):
    """The packed-word kernel (unpack in-block, shift/mask, one-hot decode)
    against the three-step oracle — Pallas interpret AND the XLA fallback."""
    from repro.core import jax_scheme as js
    from repro.kernels.qgram.ops import qgram_packed
    from repro.kernels.qgram.ref import qgram_packed_ref

    rng = np.random.default_rng(n + d)
    sigma, rates, (edges, cents) = _tables(rng, d, bits)
    x = (rng.normal(size=(n, d)) * sigma).astype(np.float32)
    y = rng.normal(size=(p, d)).astype(np.float32)
    codes = encode(x, edges, interpret=True)
    mask = (np.arange(n) < n - 5).astype(np.float32)
    words = js.pack_codes(codes, jnp.asarray(rates), total_bits=bits,
                          mask=jnp.asarray(mask))
    kw = dict(total_bits=bits, mask=jnp.asarray(mask))
    ref = np.asarray(qgram_packed_ref(words, jnp.asarray(rates), cents, y, **kw))
    out_xla = np.asarray(qgram_packed(words, jnp.asarray(rates), cents, y, **kw))
    np.testing.assert_allclose(out_xla, ref, rtol=1e-5, atol=1e-5)
    if bits > 0:  # zero-rate rows have no words for a kernel block to load
        out_pal = np.asarray(
            qgram_packed(words, jnp.asarray(rates), cents, y, interpret=True, **kw)
        )
        np.testing.assert_allclose(out_pal, ref, rtol=1e-4, atol=1e-3)


def test_receiver_products_kernel_matches_the_decoded_wire(monkeypatch):
    """The broadcast wire's inner products (``_receiver_products``) through
    the packed kernel under its two vmaps, senders x receivers, against the
    XLA branch's product of the decoded wire: 3 senders, 2 receivers, shards
    of 139-140 rows (two row tiles), d=21 at R=64 (two-word rows)."""
    from repro.core import split_machines
    from repro.core.distributed_gp import _run_wire_protocol, pad_parts
    from repro.core.protocols import broadcast

    rng = np.random.default_rng(5)
    X = rng.normal(size=(419, 21)).astype(np.float32)
    y = np.sin(X.sum(1)).astype(np.float32)
    shards = pad_parts(split_machines(X, y, 3, jax.random.PRNGKey(5)))
    assert shards.X.shape[1] > 128
    wire = _run_wire_protocol(shards.X, shards.mask, 64, 12, "broadcast", 0)
    assert wire.codes.shape[-1] == 2
    recv = shards.X[1:]
    want = np.asarray(broadcast._receiver_products(recv, shards.mask, wire, "xla"))
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    got = np.asarray(broadcast._receiver_products(
        recv, shards.mask, wire, "pallas", pack_bits=64))
    assert got.shape == want.shape == (3, 2) + (shards.X.shape[1],) * 2
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    # one decode per sender, receiver and row tile
    assert broadcast._receiver_decodes(wire, 2, "pallas") == 3 * 2 * 2
    assert broadcast._receiver_decodes(wire, 2, "xla") == 0


def test_qgram_packed_decodes_at_the_cells_shapes(monkeypatch):
    """Row-tile decodes a call makes: n_pad/bn, whatever the column count.
    sarcos-refit's blocks (1,113 rows of two words) take 9, broadcast-refit's
    (250 rows of one word) 2; an XLA call and zero-rate rows take none."""
    from repro.kernels.qgram.ops import qgram_packed_decodes

    on_chip = jax.default_backend() == "tpu"
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    assert qgram_packed_decodes((1113, 2)) == (9 if on_chip else 0)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert qgram_packed_decodes((1113, 2)) == 9
    assert qgram_packed_decodes((250, 1)) == 2
    assert qgram_packed_decodes((250, 0)) == 0


def test_qgram_packed_equals_unpacked_qgram():
    """The packed kernel and the legacy int-code kernel are the same math:
    identical grams from the same scheme output."""
    from repro.core import jax_scheme as js
    from repro.kernels.qgram.ops import qgram_packed

    rng = np.random.default_rng(17)
    n, d, p, bits = 70, 12, 40, 36
    sigma, rates, (edges, cents) = _tables(rng, d, bits)
    x = (rng.normal(size=(n, d)) * sigma).astype(np.float32)
    y = rng.normal(size=(p, d)).astype(np.float32)
    codes = encode(x, edges, interpret=True)
    words = js.pack_codes(codes, jnp.asarray(rates), total_bits=bits)
    packed = np.asarray(
        qgram_packed(words, jnp.asarray(rates), cents, y, total_bits=bits,
                     interpret=True)
    )
    unpacked = np.asarray(qgram(codes, cents, y, interpret=True))
    np.testing.assert_allclose(packed, unpacked, rtol=1e-4, atol=1e-3)


def test_qgram_equals_decode_then_gram():
    """The fusion must be exactly decode∘gram."""
    rng = np.random.default_rng(9)
    d = 12
    sigma, rates, (edges, cents) = _tables(rng, d, 36)
    x = (rng.normal(size=(70, d)) * sigma).astype(np.float32)
    y = rng.normal(size=(40, d)).astype(np.float32)
    codes = encode(x, edges, interpret=True)
    xhat = decode(codes, cents, interpret=True)
    fused = np.asarray(qgram(codes, cents, y, interpret=True))
    twostep = np.asarray(gram(xhat, y, interpret=True))
    np.testing.assert_allclose(fused, twostep, rtol=1e-4, atol=1e-3)

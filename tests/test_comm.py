"""Quantized collectives on an 8-device host mesh (subprocess so the main
pytest process keeps 1 device, per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import q_all_gather, q_psum
from jax import shard_map
from repro.compat import make_mesh

mesh = make_mesh((8,), ("m",))
rng = np.random.default_rng(0)
d, n_loc = 12, 64
X = (rng.normal(size=(8 * n_loc, d)) @ (rng.normal(size=(d, d)) / np.sqrt(d))).astype(np.float32)

f = shard_map(lambda x: q_all_gather(x, "m", 36), mesh=mesh,
                  in_specs=P("m", None), out_specs=P("m", None))
out = np.asarray(jax.jit(f)(X))
view0 = out[:8]
own_exact = float(np.abs(view0[0] - X[:n_loc]).max())
others = float(np.mean((view0[1:].reshape(-1, d) - X[n_loc:8 * n_loc]) ** 2))
raw_var = float(np.mean(X ** 2))

errs = {}
g = rng.normal(size=(4096,)).astype(np.float32)
G = np.stack([g * (i + 1) for i in range(8)])
for bits in (4, 8):
    f2 = shard_map(lambda x, b=bits: q_psum(x[0], "m", b), mesh=mesh,
                       in_specs=P("m", None), out_specs=P(), check_vma=False)
    s = np.asarray(jax.jit(f2)(G))
    true = G.sum(0)
    errs[bits] = float(np.linalg.norm(s - true) / np.linalg.norm(true))

print(json.dumps({"own_exact": own_exact, "others_mse": others,
                  "raw_var": raw_var, "psum_err": errs}))
"""


@pytest.fixture(scope="module")
def comm_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_q_all_gather_own_block_exact(comm_results):
    assert comm_results["own_exact"] < 1e-5


def test_q_all_gather_peers_within_rate_distortion(comm_results):
    # 36 bits over 12 dims = 3 bits/dim: distortion well below signal power
    assert comm_results["others_mse"] < 0.5 * comm_results["raw_var"]
    assert comm_results["others_mse"] > 0  # actually quantized, not copied


def test_q_psum_error_decreases_with_bits(comm_results):
    errs = comm_results["psum_err"]
    assert errs["8"] < errs["4"] < 0.5
    assert errs["8"] < 0.1


# --------------------------------------------------------------------------
# in-process coverage (conftest's 8 forced host devices): bits edge cases,
# shard counts, ledger accounting, gradients
# --------------------------------------------------------------------------


def _mesh(m):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:m]), ("m",))


def _run_q_all_gather(m, n_loc, d, bits, seed=0):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.comm import q_all_gather
    from jax import shard_map

    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(m * n_loc, d))
         @ (rng.normal(size=(d, d)) / np.sqrt(d))).astype(np.float32)
    fn = shard_map(lambda x: q_all_gather(x, "m", bits), mesh=_mesh(m),
                   in_specs=P("m", None), out_specs=P("m", None),
                   check_vma=False)
    return X, np.asarray(jax.jit(fn)(X)).reshape(m, m, n_loc, d)


@pytest.mark.parametrize("m", [2, 4, 8])
def test_q_all_gather_shard_counts(m):
    """Own block exact and peers genuinely quantized for 2/4/8 shards."""
    import numpy as np

    n_loc, d = 16, 6
    X, out = _run_q_all_gather(m, n_loc, d, bits=18)
    blocks = X.reshape(m, n_loc, d)
    for i in range(m):
        np.testing.assert_array_equal(out[i, i], blocks[i])  # own block exact
    if m > 1:
        peer_mse = np.mean((out[0, 1:] - blocks[1:]) ** 2)
        assert 0 < peer_mse < np.mean(X**2)


@pytest.mark.parametrize("bits", [1, 8, 32])
def test_q_all_gather_bits_edges(bits):
    """1 bit/sample (minimum rate), 8, and a 32-bit budget all decode to
    finite blocks whose distortion decreases with rate."""
    import numpy as np

    X, out = _run_q_all_gather(4, 16, 6, bits=bits)
    assert np.all(np.isfinite(out))
    blocks = X.reshape(4, 16, 6)
    mse = np.mean((out[0, 1:] - blocks[1:]) ** 2)
    if bits == 1:
        assert mse > 0
    if bits == 32:
        assert mse < 0.5 * np.mean(X**2)


def test_q_all_gather_state_ledger_matches_formula():
    """The return_state ledgers: ``wire_bits`` equals rates.sum() * n_valid +
    side_info_bits(d) per transmitting shard, ``payload_bits`` — measured
    from the packed word buffer the collective moved — equals the shared
    payload formula EXACTLY (whole uint32 words per valid row), and masked
    rows pack to all-zero words, unpack to -1 sentinels, and are neither
    decoded nor charged."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.comm import q_all_gather
    from repro.comm.accounting import payload_bits_formula, side_info_bits
    from jax import shard_map
    from repro.core import jax_scheme

    m, n_loc, d = 4, 12, 5
    bits = 15
    rng = np.random.default_rng(1)
    X = rng.normal(size=(m * n_loc, d)).astype(np.float32)
    mask = np.ones((m, n_loc), np.float32)
    mask[1, 9:] = 0.0  # machine 1 is ragged: 9 valid rows
    mask[3, 6:] = 0.0

    fn = shard_map(
        lambda x, mk: q_all_gather(x, "m", bits, mask=mk[0], return_state=True)[1],
        mesh=_mesh(m), in_specs=(P("m", None), P("m", None)), out_specs=P(),
        check_vma=False,
    )
    st = jax.jit(fn)(X, mask)
    rates = np.asarray(st["rates"])
    n_valid = mask.sum(axis=1).astype(int)
    expect = sum(int(rates[j].sum()) * int(n_valid[j]) + side_info_bits(d)
                 for j in range(m))
    assert int(st["wire_bits"]) == expect
    # physical payload: measured == formula, and == ledger + per-word padding
    lengths = [int(v) for v in n_valid]
    assert int(st["payload_bits"]) == payload_bits_formula(lengths, d, bits, 8)
    words = np.asarray(st["codes"])
    W = words.shape[-1]
    pad = sum((32 * W - int(rates[j].sum())) * lengths[j] for j in range(m))
    assert int(st["payload_bits"]) == int(st["wire_bits"]) + pad
    # the wire is packed uint32 words; masked rows are all-zero words that
    # unpack to -1 sentinels and decode to zero
    assert words.dtype == np.uint32 and W == (bits + 31) // 32
    dec = np.asarray(st["decoded"])
    assert np.all(words[1, 9:] == 0) and np.all(dec[1, 9:] == 0.0)
    assert np.all(words[3, 6:] == 0) and np.all(dec[3, 6:] == 0.0)
    codes = np.asarray(jax.vmap(
        lambda w, r, mk: jax_scheme.unpack_codes(w, r, total_bits=bits, mask=mk)
    )(st["codes"], st["rates"], st["mask"]))
    assert np.all(codes[1, 9:] == -1) and np.all(codes[3, 6:] == -1)
    assert np.all(codes[:, :6] >= 0)  # valid rows carry real codes


def test_wire_bits_all_gather_accounting():
    """Both comm ledger call sites charge the ONE shared side-info formula."""
    from repro.comm import wire_bits_all_gather
    from repro.comm.accounting import side_info_bits

    q, base = wire_bits_all_gather(n_per_shard=100, d=8, bits=24, n_shards=4)
    assert q == 100 * 24 + side_info_bits(8)
    assert q == 100 * 24 + 2 * 8 * 8 * 32  # the paper's O(2 d^2) exchange
    assert base == 100 * 8 * 32
    assert q < base  # the point of the paper


def test_ledger_call_sites_integer_equal():
    """The q_all_gather return_state ledger and the wire_bits_all_gather
    formula are the same accounting: summed over shards they agree exactly
    (uniform shards, no mask)."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.comm import q_all_gather, wire_bits_all_gather
    from jax import shard_map

    m, n_loc, d, bits = 4, 16, 6, 21
    rng = np.random.default_rng(3)
    X = rng.normal(size=(m * n_loc, d)).astype(np.float32)
    fn = shard_map(
        lambda x: q_all_gather(x, "m", bits, return_state=True)[1],
        mesh=_mesh(m), in_specs=P("m", None), out_specs=P(), check_vma=False,
    )
    st = jax.jit(fn)(X)
    # wire_bits_all_gather charges bits/sample * n + side info per shard; the
    # collective's ledger is that same number summed over all m shards
    # (greedy allocation hands out exactly `bits` per sample here, and
    # wire_bits_all_gather's n_per_shard counts samples * bits-per-sample as
    # its per-shard code payload via n * bits)
    rates = np.asarray(st["rates"])
    assert (rates.sum(axis=1) == bits).all()
    per_shard, _ = wire_bits_all_gather(n_per_shard=n_loc, d=d, bits=bits,
                                        n_shards=m)
    assert int(st["wire_bits"]) == m * per_shard


def test_q_psum_fp_fallback_is_exact():
    """bits >= 32 is the fp fallback: an exact lax.psum."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.comm import q_psum
    from jax import shard_map

    m = 4
    G = np.stack([np.linspace(-1, 1, 128).astype(np.float32) * (i + 1)
                  for i in range(m)])
    fn = shard_map(lambda x: q_psum(x[0], "m", 32), mesh=_mesh(m),
                   in_specs=P("m", None), out_specs=P(), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(jnp.asarray(G))),
                               G.sum(0), rtol=1e-6)


@pytest.mark.parametrize("m", [2, 4, 8])
def test_q_psum_gradient_straight_through(m):
    """jax.grad flows through q_psum: at bits=32 (exact fallback) gradients
    match the exact-psum gradients; at bits=8 the straight-through VJP gives
    finite gradients aligned with the exact ones (the quantizer's
    zero-derivative staircase must not zero them out)."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.comm import q_psum
    from jax import shard_map

    rng = np.random.default_rng(m)
    G = jnp.asarray(rng.normal(size=(m, 256)).astype(np.float32))

    def loss(bits):
        body = lambda x: jnp.sum(q_psum(x[0], "m", bits) ** 2)[None]
        fn = shard_map(body, mesh=_mesh(m), in_specs=P("m", None),
                       out_specs=P("m"), check_vma=False)
        return lambda x: jnp.sum(fn(x)) / m

    g_exact = jax.grad(lambda x: jnp.sum(jnp.sum(x, 0) ** 2))(G)
    g32 = jax.grad(jax.jit(loss(32)))(G)
    np.testing.assert_allclose(np.asarray(g32), np.asarray(g_exact),
                               rtol=1e-4, atol=1e-4)
    g8 = jax.grad(jax.jit(loss(8)))(G)
    g8, ge = np.asarray(g8), np.asarray(g_exact)
    assert np.all(np.isfinite(g8)) and np.linalg.norm(g8) > 0
    cos = float((g8 * ge).sum() / (np.linalg.norm(g8) * np.linalg.norm(ge)))
    assert cos > 0.95
    # and the MAGNITUDE matches too — the bwd must psum the cotangent, else
    # gradients come out 1/m of the exact reduce (scale-blind cosine passes)
    ratio = float(np.linalg.norm(g8) / np.linalg.norm(ge))
    assert 0.8 < ratio < 1.2

"""The unified kernel runtime: one dispatch policy, the kernel-op registry,
the persistent autotune cache, and the fused serve epilogue.

Covers the PR's acceptance contract: off-TPU ``interpret=None`` routes to the
XLA fallback for EVERY family; forced-Pallas interpret mode agrees with each
family's ``ref.py`` oracle; the autotune cache is demonstrably persistent
across processes (second process performs ZERO sweeps) and tolerates corrupt
files; and the fused serve epilogue is numerically equal to the unfused path
for every registered fusion method, with the warm-serve invariants (0
cholesky / 0 eigh / 0 retraces) intact.
"""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import runtime

FAMILIES = (
    "gram", "quant_encode", "quant_decode", "qgram", "qgram_packed",
    "decode_attn", "epilogue",
)


# --------------------------------------------------------------------------
# the one fallback policy
# --------------------------------------------------------------------------


def test_choose_policy_off_tpu(monkeypatch):
    assert jax.default_backend() != "tpu"  # CI/dev hosts
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    assert runtime.choose(None) == runtime.Decision("xla")
    assert runtime.choose(True) == runtime.Decision("pallas", True)
    assert runtime.choose(False) == runtime.Decision("pallas", False)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert runtime.choose(None) == runtime.Decision("pallas", True)
    # explicit interpret always wins over the env override
    assert runtime.choose(False) == runtime.Decision("pallas", False)


def test_registry_has_every_family():
    for name in FAMILIES:
        spec = runtime.kernel_op(name)
        assert spec.name == name
        assert callable(spec.pallas) and callable(spec.xla)
        assert spec.ref is not None


def test_registry_unknown_op_lists_menu():
    with pytest.raises(ValueError, match="known kernel ops are .*gram"):
        runtime.kernel_op("no_such_kernel")


def test_dispatch_binds_backend(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    spec = runtime.kernel_op("gram")
    d, fn = runtime.dispatch("gram")
    assert d.kind == "xla" and fn is spec.xla
    d, fn = runtime.dispatch("gram", interpret=True)
    assert d == runtime.Decision("pallas", True)


# --------------------------------------------------------------------------
# dispatch-table parity: pallas(interpret) vs ref, xla vs ref, per family
# --------------------------------------------------------------------------


def _family_args(name, rng):
    """(args, kwargs) over each op's public unpadded signature."""
    from repro.core import quantizers as Q
    from repro.core import jax_scheme as js
    from repro.kernels.quant.ops import build_scaled_tables, encode

    if name == "gram":
        return (rng.normal(size=(33, 7)).astype(np.float32),
                rng.normal(size=(20, 7)).astype(np.float32)), {}
    d, bits = 10, 30
    var = rng.uniform(0.05, 4.0, size=d)
    rates = Q.allocate_bits_greedy(var, bits, 8)
    sigma = np.sqrt(var).astype(np.float32)
    edges, cents = build_scaled_tables(sigma, rates)
    x = (rng.normal(size=(40, d)) * sigma).astype(np.float32)
    if name == "quant_encode":
        return (x, edges), {}
    codes = encode(x, edges, interpret=True)
    if name == "quant_decode":
        return (codes, cents), {}
    y = rng.normal(size=(22, d)).astype(np.float32)
    if name == "qgram":
        return (codes, cents, y), {}
    if name == "qgram_packed":
        words = js.pack_codes(codes, jnp.asarray(rates), total_bits=bits)
        return (words, jnp.asarray(rates), cents, y), {"total_bits": bits}
    if name == "decode_attn":
        B, S, KV, G, hd = 2, 24, 2, 2, 16
        q = rng.normal(size=(B, KV, G, hd)).astype(np.float32)
        K = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
        V = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
        kpos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
        return (q, K, V, kpos, S - 1), {}
    if name == "epilogue":
        m, t, K = 3, 17, 11
        G = rng.normal(size=(m, t, K)).astype(np.float32)
        Ainv = np.stack([
            np.linalg.inv(np.tril(rng.normal(size=(K, K))) * 0.1 + np.eye(K))
            for _ in range(m)
        ]).astype(np.float32)
        P = np.stack([0.01 * A @ A.T for A in Ainv]).astype(np.float32)
        walpha = rng.normal(size=(m, K)).astype(np.float32)
        gss = rng.uniform(1.0, 2.0, size=(t,)).astype(np.float32)
        w = np.ones((m,), np.float32)
        return (G, Ainv, P, walpha, gss, gss + 0.1, w), {"fuse": "kl"}
    raise AssertionError(name)


@pytest.mark.parametrize("name", FAMILIES)
def test_family_backends_match_ref(name):
    """Forced-Pallas interpret mode AND the XLA fallback against the family's
    pure-jnp oracle, through the registry's uniform public signature."""
    rng = np.random.default_rng(hash(name) % 2**31)
    spec = runtime.kernel_op(name)
    args, kw = _family_args(name, rng)
    ref = spec.ref(*args, **kw)
    pal = spec.pallas(*args, interpret=True, **kw)
    xla = spec.xla(*args, **kw)
    for got in (pal, xla):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-3
            ),
            got, ref,
        )


def test_decode_attn_xla_fallback_serves_off_tpu(monkeypatch):
    """decode_attn historically had NO fallback: interpret=None off-TPU now
    runs the jitted reference instead of raising/interpreting."""
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    from repro.kernels.decode_attn.ops import decode_attn

    rng = np.random.default_rng(3)
    (q, K, V, kpos, pos), _ = _family_args("decode_attn", rng)
    out = decode_attn(q, K, V, kpos, pos)  # interpret=None -> xla
    ref = runtime.kernel_op("decode_attn").ref(q, K, V, kpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# persistent autotune cache
# --------------------------------------------------------------------------


def _with_cache(monkeypatch, tmp_path):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    runtime.clear_cache_memory()
    return path


def test_autotune_sweeps_once_then_warm_hits(monkeypatch, tmp_path):
    path = _with_cache(monkeypatch, tmp_path)
    key = runtime.cache_key("op", [(8, 8)], "float32", bits=4)
    seen = []
    measure = lambda c: (seen.append(c), float(c[0]))[1]
    before = runtime.sweep_count()
    win = runtime.autotune(key, [(2, 2), (1, 1)], measure, (2, 2))
    assert win == (1, 1) and runtime.sweep_count() == before + 1
    assert seen == [(2, 2), (1, 1)]
    # warm hit: straight from disk image, zero sweeps, measure never called
    runtime.clear_cache_memory()
    win2 = runtime.autotune(key, [(2, 2), (1, 1)], lambda c: 1 / 0, (2, 2))
    assert win2 == (1, 1) and runtime.sweep_count() == before + 1
    blob = json.load(open(path))
    assert blob["version"] == runtime.CACHE_VERSION
    assert blob["entries"][key] == [1, 1]


def test_autotune_infeasible_and_failing_candidates(monkeypatch, tmp_path,
                                                    capsys):
    _with_cache(monkeypatch, tmp_path)
    key = runtime.cache_key("op2", [(4,)], "int8")

    def measure(c):
        if c == (1,):
            return None  # infeasible for this shape
        if c == (2,):
            raise RuntimeError("compile blew up")
        return 5.0

    assert runtime.autotune(key, [(1,), (2,), (3,)], measure, (1,)) == (3,)
    # the failing candidate is reported, not swallowed
    assert "candidate (2,) failed: RuntimeError: compile blew up" in \
        capsys.readouterr().err


def test_autotune_raises_when_every_candidate_fails(monkeypatch, tmp_path):
    path = _with_cache(monkeypatch, tmp_path)
    key = runtime.cache_key("op5", [(4,)], "float32")

    def measure(c):
        raise RuntimeError(f"no lowering for {c}")

    with pytest.raises(RuntimeError, match="no candidate ran"):
        runtime.autotune(key, [(1,), (2,)], measure, (1,))
    assert not os.path.exists(path)  # nothing untested was persisted


def test_cache_key_names_the_device_kind():
    key = runtime.cache_key("op6", [(2, 3)], "float32", bits=4)
    assert key.split("|")[1] == jax.devices()[0].device_kind


def test_corrupt_or_stale_cache_falls_back(monkeypatch, tmp_path):
    path = _with_cache(monkeypatch, tmp_path)
    key = runtime.cache_key("op3", [(2, 2)], "float32")
    for garbage in ("{not json", json.dumps({"version": 99, "entries": {key: [9]}}),
                    json.dumps([1, 2, 3])):
        with open(path, "w") as f:
            f.write(garbage)
        runtime.clear_cache_memory()
        before = runtime.sweep_count()
        win = runtime.autotune(key, [(7,)], lambda c: 1.0, (7,))
        assert win == (7,) and runtime.sweep_count() == before + 1
        runtime.clear_cache_memory()  # the sweep rewrote a valid file


def test_stale_winner_not_in_candidates_resweeps(monkeypatch, tmp_path):
    path = _with_cache(monkeypatch, tmp_path)
    key = runtime.cache_key("op4", [(2,)], "float32")
    with open(path, "w") as f:
        json.dump({"version": runtime.CACHE_VERSION,
                   "entries": {key: [999, 999]}}, f)
    runtime.clear_cache_memory()
    before = runtime.sweep_count()
    win = runtime.autotune(key, [(4, 4)], lambda c: 1.0, (4, 4))
    assert win == (4, 4) and runtime.sweep_count() == before + 1


_SUBPROC = r"""
import os, sys
import numpy as np, jax.numpy as jnp
sys.path.insert(0, {src!r})
from repro.core import quantizers as Q, jax_scheme as js
from repro.kernels import runtime
from repro.kernels.quant.ops import build_scaled_tables, encode
from repro.kernels.qgram.ops import qgram_packed

rng = np.random.default_rng(0)
d, bits = 10, 30
var = rng.uniform(0.05, 4.0, size=d)
rates = Q.allocate_bits_greedy(var, bits, 8)
sigma = np.sqrt(var).astype(np.float32)
edges, cents = build_scaled_tables(sigma, rates)
x = (rng.normal(size=(40, d)) * sigma).astype(np.float32)
y = rng.normal(size=(22, d)).astype(np.float32)
codes = encode(x, edges, interpret=True)
words = js.pack_codes(codes, jnp.asarray(rates), total_bits=bits)
out = qgram_packed(words, jnp.asarray(rates), cents, y, total_bits=bits,
                   interpret=True)
np.asarray(out)
print("SWEEPS", runtime.sweep_count())
"""


def test_cache_persists_across_processes(tmp_path):
    """The acceptance criterion verbatim: a second process serving the same
    shapes performs ZERO autotune sweeps (warm disk hit)."""
    env = dict(
        os.environ,
        REPRO_TUNE_CACHE=str(tmp_path / "autotune.json"),
        REPRO_AUTOTUNE_INTERPRET="1",  # let the interpret path tune on CPU
        JAX_PLATFORMS="cpu",
    )
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SUBPROC.format(src=os.path.abspath(src))

    def run():
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr
        return int(r.stdout.strip().split()[-1])

    assert run() >= 1  # cold: at least one sweep, winner persisted
    assert run() == 0  # warm: second process sweeps ZERO times


# --------------------------------------------------------------------------
# fused serve epilogue: equality with the unfused path, serve invariants
# --------------------------------------------------------------------------


def _parts(rng, m=3, n=20, d=3):
    return [(rng.normal(size=(n, d)).astype(np.float32),
             rng.normal(size=(n,)).astype(np.float32)) for _ in range(m)]


@pytest.mark.parametrize("fuse", ["kl", "poe", "gpoe", "bcm", "rbcm"])
def test_fused_epilogue_equals_unfused_all_fusions(fuse):
    import dataclasses
    from repro.core.api import DistributedGP
    from repro.core.config import DGPConfig

    rng = np.random.default_rng(7)
    parts = _parts(rng)
    Xst = rng.normal(size=(12, 3)).astype(np.float32)
    cfg = DGPConfig(protocol="broadcast", fusion=fuse, steps=4,
                    bits_per_sample=8, serve_epilogue="fused")
    art_f = DistributedGP(cfg).fit(parts=parts)
    assert "Ainv" in art_f.factors and "walpha" in art_f.factors
    assert "U" not in art_f.factors  # the projector needs L_M alone
    cfg_u = dataclasses.replace(cfg, serve_epilogue="unfused")
    art_u = DistributedGP(cfg_u).fit(parts=parts)
    assert "Ainv" not in art_u.factors
    mu_f, s2_f = DistributedGP(cfg).predict(art_f, Xst)
    mu_u, s2_u = DistributedGP(cfg_u).predict(art_u, Xst)
    np.testing.assert_allclose(np.asarray(mu_f), np.asarray(mu_u), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2_f), np.asarray(s2_u), atol=2e-4)
    # degraded serving goes through the same fused moments
    avail = np.array([1.0, 0.0, 1.0], np.float32)
    mu_f, s2_f = DistributedGP(cfg).predict(art_f, Xst, available=avail)
    mu_u, s2_u = DistributedGP(cfg_u).predict(art_u, Xst, available=avail)
    np.testing.assert_allclose(np.asarray(mu_f), np.asarray(mu_u), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2_f), np.asarray(s2_u), atol=2e-4)


def test_fused_pallas_backend_matches_xla_backend():
    """gram_backend="pallas" + fused cache routes the whole epilogue through
    the one-launch kernels.epilogue op — same answer as the xla route."""
    from repro.core.protocols import base

    rng = np.random.default_rng(11)
    parts = _parts(rng)
    Xst = rng.normal(size=(10, 3)).astype(np.float32)
    kw = dict(protocol="broadcast", kernel="se", steps=4, fuse="kl")
    art_x = base.fit(parts, 8, gram_backend="xla", **kw)
    art_p = base.fit(parts, 8, gram_backend="pallas", **kw)
    assert "Ainv" in art_p.factors
    mu_x, s2_x = base.predict(art_x, Xst)
    mu_p, s2_p = base.predict(art_p, Xst)
    np.testing.assert_allclose(np.asarray(mu_p), np.asarray(mu_x), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2_p), np.asarray(s2_x), atol=1e-4)


def test_fused_update_maintains_cache():
    """Streaming update keeps the serve-cache keys consistent: an updated
    fused artifact predicts the same as an updated unfused one."""
    import dataclasses
    from repro.core.api import DistributedGP
    from repro.core.config import DGPConfig

    rng = np.random.default_rng(13)
    parts = _parts(rng)
    Xst = rng.normal(size=(10, 3)).astype(np.float32)
    Xn = rng.normal(size=(4, 3)).astype(np.float32)
    yn = rng.normal(size=(4,)).astype(np.float32)
    cfg = DGPConfig(protocol="broadcast", fusion="kl", steps=4,
                    bits_per_sample=8, serve_epilogue="fused")
    cfg_u = dataclasses.replace(cfg, serve_epilogue="unfused")
    from repro.core.protocols import base

    art_f = base.update(DistributedGP(cfg).fit(parts=parts), Xn, yn, machine=1)
    art_u = base.update(DistributedGP(cfg_u).fit(parts=parts), Xn, yn, machine=1)
    assert "Ainv" in art_f.factors and "walpha" in art_f.factors
    assert "U" not in art_f.factors
    mu_f, s2_f = base.predict(art_f, Xst)
    mu_u, s2_u = base.predict(art_u, Xst)
    np.testing.assert_allclose(np.asarray(mu_f), np.asarray(mu_u), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2_f), np.asarray(s2_u), atol=2e-4)


def test_fused_serve_keeps_warm_invariants():
    """The fused predict program still contains ZERO fit-time factorizations,
    and repeated serving does not retrace."""
    from repro.core.protocols import base

    rng = np.random.default_rng(17)
    parts = _parts(rng)
    Xst = rng.normal(size=(8, 3)).astype(np.float32)
    for protocol in ("center", "broadcast"):
        art = base.fit(parts, 8, protocol=protocol, steps=4)
        assert "Ainv" in art.factors
        counts = base.predict_op_counts(art, Xst)
        assert counts["cholesky"] == 0 and counts["eigh"] == 0
        base.predict(art, Xst)
        traces = dict(base._SERVE_TRACES)
        for _ in range(3):
            base.predict(art, Xst)
        assert dict(base._SERVE_TRACES) == traces  # warm: zero retraces

"""The contract checker (repro.analysis): every detector demonstrated firing
on a known-bad fixture, every shipped contract passing on the real artifacts,
and the source tree lint-clean.

Structure:
  * jaxpr plane — walk/count primitives through nested pjit/scan/cond/
    shard_map bodies; the PrimitiveBudget / NoHostCallbacks /
    CollectiveBudget rules each fire on a bad program and stay silent on a
    good one;
  * sharding plane — find_sharding_leaks and the PR-8 regression: an
    artifact whose leaves are committed-REPLICATED over the mesh (the exact
    shard_map ``out_specs=P()`` escape) is caught by check_contracts;
  * ledger plane — LedgerAccounting vs a doctored wire ledger;
  * trace plane — check_contracts is trace-neutral; retrace_budget raises on
    an over-budget block;
  * source plane — each lint rule on a synthetic source, and the real tree
    clean.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from jax import shard_map
from repro.compat import make_mesh
from repro.core import split_machines, fit, predict
from repro.core.protocols import serve_trace_count
from repro.analysis import (
    COLLECTIVE_PRIMITIVES,
    FACTORIZATION_PRIMITIVES,
    CollectiveBudget,
    ContractViolation,
    NoHostCallbacks,
    NoShardingLeak,
    check_contracts,
    collective_stats,
    contract_for,
    find_sharding_leaks,
    forbid_primitives,
    primitive_counts,
    register_contract,
    retrace_budget,
    walk_jaxpr,
)
from repro.analysis.contracts import Contract, LedgerAccounting, _CheckContext
from repro.analysis.lint import RULES, lint_paths, lint_source

P = jax.sharding.PartitionSpec


# --------------------------------------------------------------------------
# fixtures
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def art_center():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    y = rng.normal(size=64).astype(np.float32)
    parts = split_machines(X, y, 4, jax.random.PRNGKey(0))
    return fit(parts, 16, "center", steps=1)


@pytest.fixture(scope="module")
def Xq():
    return np.random.default_rng(1).normal(size=(8, 3)).astype(np.float32)


# --------------------------------------------------------------------------
# jaxpr plane: recursive walk
# --------------------------------------------------------------------------


def test_walk_descends_into_scan_and_cond():
    def body(c, _):
        L = jnp.linalg.cholesky(c)
        return L @ L.T, None

    def prog(M, flag):
        M, _ = jax.lax.scan(body, M, None, length=2)
        return jax.lax.cond(flag, jnp.linalg.cholesky, lambda x: x, M)

    cj = jax.make_jaxpr(prog)(jnp.eye(3), True)
    counts = primitive_counts(cj, names=FACTORIZATION_PRIMITIVES)
    # one cholesky inside the scan body + one inside a cond branch
    assert counts["cholesky"] == 2


def test_walk_descends_into_pjit():
    inner = jax.jit(lambda M: jnp.linalg.cholesky(M))
    cj = jax.make_jaxpr(lambda M: inner(M) @ inner(M).T)(jnp.eye(3))
    assert primitive_counts(cj, names=("cholesky",))["cholesky"] >= 1


def test_walk_descends_into_shard_map():
    devs = jax.devices()
    mesh = make_mesh((len(devs),), ("m",))
    f = shard_map(lambda x: jax.lax.psum(x, "m"),
                  mesh=mesh, in_specs=P("m"), out_specs=P())
    cj = jax.make_jaxpr(f)(jnp.ones(len(devs)))
    stats = collective_stats(cj)
    # shard_map rewrites the reduction as psum2 (check_rep) or
    # psum_invariant (check_vma); every spelling counts
    (name,) = stats.keys()
    assert name in ("psum", "psum2", "psum_invariant")
    assert stats[name]["count"] == 1
    assert stats[name]["bytes"] == 4  # one f32 scalar per participant


# --------------------------------------------------------------------------
# jaxpr plane: detectors firing on known-bad programs
# --------------------------------------------------------------------------


def _ctx(fn, *args):
    return _CheckContext(jaxpr=jax.make_jaxpr(fn)(*args))


def test_primitive_budget_fires_on_unbudgeted_cholesky():
    ctx = _ctx(lambda M: jnp.linalg.cholesky(M @ M.T + jnp.eye(4)),
               jnp.ones((4, 4)))
    assert forbid_primitives("cholesky").check(ctx)
    # a triangular solve against a cached factor is NOT a factorization
    ok = _ctx(lambda L, b: jax.scipy.linalg.solve_triangular(L, b, lower=True),
              jnp.eye(4), jnp.ones(4))
    assert not forbid_primitives().check(ok)


def test_no_host_callbacks_fires_on_pure_callback():
    def bad(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    ctx = _ctx(bad, jnp.ones(3))
    findings = NoHostCallbacks().check(ctx)
    assert findings and "pure_callback" in findings[0]
    assert not NoHostCallbacks(allow=("pure_callback",)).check(ctx)


def test_collective_budget_fires_on_unaccounted_psum():
    devs = jax.devices()
    mesh = make_mesh((len(devs),), ("m",))
    bad = shard_map(lambda x: jax.lax.psum(x, "m") + jax.lax.pmax(x, "m"),
                    mesh=mesh, in_specs=P("m"), out_specs=P("m"))
    ctx = _ctx(bad, jnp.ones(len(devs)))
    # psum + pmax (+ any rewrite-inserted pbroadcast) against a budget of
    # one: the unaccounted channel fires, naming every collective
    findings = CollectiveBudget(max_count=1).check(ctx)
    assert findings and "> budget 1" in findings[0]
    n_coll = sum(v["count"] for v in collective_stats(ctx.jaxpr).values())
    assert n_coll >= 2
    # a byte ceiling catches a payload regression even under the count budget
    assert CollectiveBudget(max_count=n_coll, max_bytes=1).check(ctx)
    assert not CollectiveBudget(max_count=n_coll).check(ctx)


# --------------------------------------------------------------------------
# sharding plane: the PR-8 committed-replicated leak
# --------------------------------------------------------------------------


def _replicated_sharding():
    devs = jax.devices()
    assert len(devs) >= 2, "conftest forces 8 host devices"
    mesh = make_mesh((len(devs),), ("m",))
    return jax.sharding.NamedSharding(mesh, P())


def test_find_sharding_leaks_flags_committed_replication():
    rep = _replicated_sharding()
    tree = {"good": jnp.ones(3), "bad": jax.device_put(jnp.ones(3), rep)}
    leaks = find_sharding_leaks(tree)
    assert [p for p, _ in leaks] == ["bad"]
    assert leaks[0][1] == len(jax.devices())
    # the allow predicate admits deliberately-sharded leaves by path
    assert not find_sharding_leaks(tree, allow=lambda p: p.startswith("bad"))


def test_shard_map_identity_output_is_committed_and_detected():
    """The PR-8 mechanism itself: out_specs=P() commits the output to a
    replicated NamedSharding over the whole mesh, and the leak scan sees it."""
    devs = jax.devices()
    mesh = make_mesh((len(devs),), ("m",))
    f = shard_map(lambda x: jax.lax.psum(x, "m"),
                  mesh=mesh, in_specs=P("m"), out_specs=P())
    out = jax.jit(f)(jnp.ones(len(devs)))
    leaks = find_sharding_leaks({"out": out})
    assert leaks == [("out", len(devs))]


def test_check_contracts_catches_pr8_regression(art_center, Xq):
    """Regression for the PR-8 qps collapse: a serving artifact whose leaves
    escaped fit committed-replicated over the mesh violates its contract."""
    rep = _replicated_sharding()
    bad = jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), art_center)
    with pytest.raises(ContractViolation) as exc:
        check_contracts(bad, Xq)
    assert "no-sharding-leak" in str(exc.value)
    report = check_contracts(bad, Xq, raise_on_violation=False)
    assert not report.ok and report.leaks


# --------------------------------------------------------------------------
# ledger plane
# --------------------------------------------------------------------------


def test_ledger_accounting_fires_on_doctored_wire(art_center):
    stream = dataclasses.replace(
        art_center.stream,
        wire_bits=art_center.stream.payload_bits + jnp.int64(1)
        if art_center.stream.wire_bits.dtype == jnp.int64
        else art_center.stream.payload_bits + jnp.int32(1),
    )
    bad = dataclasses.replace(art_center, stream=stream)
    findings = LedgerAccounting().check(_CheckContext(artifact=bad))
    assert findings and "payload_bits" in findings[0]
    with pytest.raises(ContractViolation):
        check_contracts(bad, phase="update")


# --------------------------------------------------------------------------
# contracts: registry, enforcement, trace plane
# --------------------------------------------------------------------------


def test_registered_contracts_pass_on_real_artifacts(Xq):
    rng = np.random.default_rng(2)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    y = rng.normal(size=64).astype(np.float32)
    parts = split_machines(X, y, 4, jax.random.PRNGKey(2))
    for proto, bits, kw in [("center", 16, {}), ("broadcast", 16, {}),
                            ("poe", 0, {"method": "rbcm"})]:
        art = fit(parts, bits, proto, steps=1, **kw)
        report = check_contracts(art, Xq)
        assert report.ok
        assert report.contract == f"{proto}-serve"
        assert sum(report.op_counts.values()) == 0
        assert not report.collectives and not report.leaks
        assert check_contracts(art, phase="update").ok


def test_contract_lookup_precedence_and_duplicates():
    c = contract_for("broadcast", "mesh", "predict")
    assert c.name == "mesh-serve"
    assert contract_for("broadcast", "batched", "predict").name == "broadcast-serve"
    with pytest.raises(KeyError):
        contract_for("nonesuch", "batched", "predict")
    with pytest.raises(ValueError):
        register_contract("center", "predict", Contract("dup", rules=()))


def test_check_contracts_is_trace_neutral(art_center, Xq):
    c0 = serve_trace_count("center")
    for _ in range(3):
        check_contracts(art_center, Xq)
    assert serve_trace_count("center") == c0


def test_retrace_budget_raises_on_violation(art_center):
    # a fresh query shape forces one serve trace — over a budget of zero
    Xodd = np.zeros((11, 3), np.float32)
    with pytest.raises(ContractViolation) as exc:
        with retrace_budget("center", serve=0):
            predict(art_center, Xodd)
    assert "serve-retraces" in str(exc.value)


# --------------------------------------------------------------------------
# source plane: every lint rule on a synthetic source, the real tree clean
# --------------------------------------------------------------------------


def _rules(src, path):
    return sorted({v.rule for v in lint_source(src, path)})


def test_lint_raw_cholesky():
    src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.linalg.cholesky(x)\n"
    assert _rules(src, "src/repro/core/foo.py") == ["raw-cholesky"]
    assert _rules(src, "src/repro/core/linalg_safe.py") == []
    # host numerics are exempt: numpy/scipy carry no jitter policy
    host = "import numpy as np\ndef f(x):\n    return np.linalg.cholesky(x)\n"
    assert _rules(host, "src/repro/core/foo.py") == []


def test_lint_raw_eigh():
    src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.linalg.eigh(x)\n"
    assert _rules(src, "src/repro/core/foo.py") == ["raw-eigh"]
    imp = "from jax.numpy.linalg import eigh\n"
    assert _rules(imp, "src/repro/core/foo.py") == ["raw-eigh"]


def test_lint_local_jitter():
    assert _rules("_JITTER = 1e-6\n", "src/repro/core/foo.py") == ["local-jitter"]
    assert _rules("DEFAULT_JITTER = 1e-5\n", "src/repro/core/foo.py") == ["local-jitter"]
    assert _rules("from .nystrom import _JITTER\n", "src/repro/core/foo.py") == ["local-jitter"]
    assert _rules("DEFAULT_JITTER = 1e-6\n", "src/repro/core/linalg_safe.py") == []


def test_lint_xla_env_mutation():
    src = 'import os\nos.environ["XLA_FLAGS"] = "--x"\n'
    assert _rules(src, "src/repro/launch/foo.py") == ["xla-env-mutation"]
    assert _rules(src, "src/repro/compat.py") == []
    sd = 'import os\nos.environ.setdefault("XLA_FLAGS", "--x")\n'
    assert _rules(sd, "src/repro/launch/foo.py") == ["xla-env-mutation"]


def test_lint_device_get_hot_path():
    src = "import jax\ndef f(x):\n    return jax.device_get(x)\n"
    assert _rules(src, "src/repro/kernels/foo.py") == ["device-get-hot-path"]
    assert _rules(src, "src/repro/core/protocols/foo.py") == ["device-get-hot-path"]
    # the named host-sync boundaries are sanctioned
    boundary = ("import jax\ndef ensure_capacity(x):\n"
                "    return jax.device_get(x)\n")
    assert _rules(boundary, "src/repro/core/protocols/streaming.py") == []
    # outside hot modules device_get is fine (launch scripts, tests)
    assert _rules(src, "src/repro/launch/foo.py") == []


def test_lint_registry_top_level():
    src = "def f():\n    register_kernel('k', object())\n"
    assert _rules(src, "src/repro/kernels/foo.py") == ["registry-top-level"]
    assert _rules("register_kernel('k', object())\n", "src/repro/kernels/foo.py") == []


def test_lint_trace_counter_encapsulation():
    src = "from repro.core.protocols import base\nn = base._SERVE_TRACES['c']\n"
    assert _rules(src, "src/repro/launch/foo.py") == ["trace-counter-encapsulation"]
    assert _rules(src, "src/repro/core/protocols/foo.py") == []
    assert _rules(src, "src/repro/analysis/foo.py") == []


def test_lint_span_funnel():
    src = ("import jax\ndef f():\n"
           "    with jax.profiler.TraceAnnotation('x'):\n        pass\n")
    assert _rules(src, "src/repro/core/foo.py") == ["span-funnel"]
    assert _rules(src, "src/repro/spans.py") == []
    deco = "import jax\n@jax.profiler.annotate_function\ndef f():\n    pass\n"
    assert _rules(deco, "src/repro/core/foo.py") == ["span-funnel"]
    imp = "from jax.profiler import TraceAnnotation\n"
    assert _rules(imp, "src/repro/launch/foo.py") == ["span-funnel"]


def test_lint_rule_table_is_live():
    assert len(RULES) >= 6  # the acceptance floor: at least 6 active rules


def test_repo_tree_is_lint_clean():
    violations = lint_paths(["src"])
    assert not violations, "\n".join(str(v) for v in violations)

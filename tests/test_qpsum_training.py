"""``q_psum``'s quantized gradient reduction trains a GP like the exact one.

Eight machines, each with its own 64 points, fit one set of SE
hyperparameters by Adam on the summed negative log marginal likelihood. Each
machine differentiates its own term; the gradients meet in one reduction
over a ``shard_map`` of 8 forced host devices: ``lax.psum`` in one run,
``q_psum(bits=8)`` (8-bit Gaussian codes on the wire) in the other. Both
runs share one subprocess, which owns its device count."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P
from repro.comm import q_psum
from repro.compat import make_mesh
from repro.core.gp import gram_fn, init_params, nlml_from_gram

M, N_LOC, D, STEPS, LR = 8, 64, 4, 40, 0.05
rng = np.random.default_rng(0)
X = rng.normal(size=(M, N_LOC, D)).astype(np.float32)
w = rng.normal(size=D) / np.sqrt(D)
y = (np.sin(X @ w) + 0.1 * rng.normal(size=(M, N_LOC))).astype(np.float32)
flat0, unravel = ravel_pytree(init_params(a=1.0, b=1.0, noise=0.1))
gram = gram_fn("se")
mesh = make_mesh((M,), ("m",))


def local_nlml(flat, Xi, yi):
    p = unravel(flat)
    return nlml_from_gram(gram(p, Xi), yi, jnp.exp(p.log_noise))


def train(reduce):
    def step(i, flat, m, v, Xs, ys):
        loss, g = jax.value_and_grad(local_nlml)(flat, Xs[0], ys[0])
        g = reduce(g)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        t = i + 1.0
        flat = flat - LR * (m / (1 - 0.9**t)) / (jnp.sqrt(v / (1 - 0.999**t)) + 1e-8)
        return flat, m, v, jax.lax.psum(loss, "m")

    # q_psum's sum of an all_gather is replicated, which the vma checker
    # cannot infer
    step = jax.jit(shard_map(step, mesh=mesh,
                             in_specs=(P(), P(), P(), P(), P("m"), P("m")),
                             out_specs=P(), check_vma=False))
    flat, m, v = flat0, jnp.zeros_like(flat0), jnp.zeros_like(flat0)
    losses = []
    for i in range(STEPS):
        flat, m, v, loss = step(jnp.float32(i), flat, m, v, X, y)
        losses.append(float(loss))
    return losses


print(json.dumps({
    "exact": train(lambda g: jax.lax.psum(g, "m")),
    "q8": train(lambda g: q_psum(g, "m", 8)),
}))
"""


@pytest.fixture(scope="module")
def traces():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_exact_reduction_trains(traces):
    exact = traces["exact"]
    # every Adam step lowers the summed NLML (it falls 480 -> -114 nats over
    # the 40 steps at lr 0.05); half that 594-nat drop is the bar
    assert all(b < a for a, b in zip(exact, exact[1:])), exact
    assert exact[-1] < exact[0] - 297.0, (exact[0], exact[-1])


def test_q8_matches_exact_training(traces):
    exact, q8 = traces["exact"], traces["q8"]
    # the first loss is taken at the shared initial hyperparameters, before
    # any gradient has crossed the wire: only float32 rounding may differ
    assert q8[0] == pytest.approx(exact[0], rel=1e-6)
    # the codes did change the gradients the run trained on
    assert q8 != exact
    # 8-bit codes perturb each summed gradient by about 1%, and Adam's
    # normalised step shrinks that further: the traces stay within 7.2e-3
    # nats of each other over the 594-nat drop; 0.1 nats leaves 14x room
    gap = max(abs(a - b) for a, b in zip(exact, q8))
    assert gap < 0.1, gap

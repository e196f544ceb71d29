"""The paper's quantized cross-pod gradient reduction (§Perf C): convergence
parity with the exact fp32 reduce, on an 8-device (2 pods x 2 data x 2 model)
host mesh in a subprocess."""
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
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import make_train_step
from repro.models.steps import init_train_state
from repro.models.sharding import logical_rules, rules_multi_pod
from repro.compat import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_config("gemma2-2b").reduced()
with jax.set_mesh(mesh), logical_rules(rules_multi_pod()):
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)}
    batch["labels"] = batch["tokens"]
    batch = jax.device_put(batch, NamedSharding(mesh, P(("pod", "data"), None)))
    out = {}
    for qbits in (0, 8):
        step = jax.jit(make_train_step(cfg, qcomm_bits=qbits, peak_lr=1e-3,
                                       warmup=2, total_steps=12))
        p, o = params, opt
        losses = []
        for _ in range(8):
            p, o, m = step(p, o, batch)
            losses.append(float(m["loss"]))
        out[str(qbits)] = losses
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def traces():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_exact_reduction_trains(traces):
    exact = traces["0"]
    assert exact[-1] < exact[0] - 0.5


def test_q8_matches_exact_training(traces):
    exact, q8 = traces["0"], traces["8"]
    assert q8[0] == pytest.approx(exact[0], rel=1e-3)  # same init/first loss
    assert abs(q8[-1] - exact[-1]) < 0.15  # indistinguishable convergence

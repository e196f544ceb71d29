"""Jit-able step functions: train_step / prefill_step / decode_step builders.

These are what the launcher lowers in the multi-pod dry-run and what the
training driver runs.  Loss is next-token cross-entropy computed in fp32 with
the logsumexp trick (no fp32 logits materialization beyond one (B,S,V) temp).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .backbone import forward, init_model
from .decode import decode_step as _decode_step, init_decode_state
from ..optim import AdamWState, adamw_init, adamw_update, cosine_warmup
from jax import shard_map
from jax.sharding import get_abstract_mesh

MOE_AUX_WEIGHT = 0.01
ROUTER_Z_WEIGHT = 1e-3


def loss_fn(params, cfg: ModelConfig, batch):
    logits, aux = forward(params, cfg, batch, kind="train")
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = nll
    if aux:
        total = total + MOE_AUX_WEIGHT * aux.get("load_balance", 0.0)
        total = total + ROUTER_Z_WEIGHT * aux.get("router_z", 0.0)
    metrics = {"loss": nll, **{f"moe/{k}": v for k, v in aux.items()}}
    return total, metrics


def make_train_step(
    cfg: ModelConfig,
    *,
    peak_lr=3e-4,
    warmup=100,
    total_steps=10000,
    microbatches: int = 1,
    qcomm_bits: int = 0,
    pod_axis: str = "pod",
):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches > 1`` runs gradient accumulation: the global batch is split
    on the leading axis and scanned, so live activations (layer-scan carries,
    logits) scale with the microbatch, not the global batch — the difference
    between fitting and not fitting HBM for the large train_4k configs.

    ``qcomm_bits > 0`` applies the PAPER'S quantization scheme to the
    cross-pod gradient reduction (§Perf C): the per-pod gradient is computed
    under a shard_map that is manual over the pod axis only, and the pod-axis
    all-reduce is replaced by repro.comm.q_psum — int codes on the (slow,
    DCN-class) inter-pod links instead of fp32."""

    def grad_fn(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, cfg, batch)

    def accumulate_grads(params, batch):
        if microbatches == 1:
            (_, metrics), grads = grad_fn(params, batch)
            return grads, metrics
        B = batch["tokens"].shape[0]
        assert B % microbatches == 0, (B, microbatches)
        mb = jax.tree.map(
            lambda a: a.reshape(microbatches, B // microbatches, *a.shape[1:]), batch
        )
        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def acc(carry, mbatch):
            g_acc, _ = carry
            (_, metrics), g = grad_fn(params, mbatch)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, metrics), None

        (grads, metrics), _ = jax.lax.scan(acc, (zero_g, _zero_metrics(cfg)), mb)
        return jax.tree.map(lambda g: g / microbatches, grads), metrics

    def train_step(params, opt_state: AdamWState, batch):
        if qcomm_bits:
            from jax.sharding import PartitionSpec as P
            from ..comm import q_psum
            from .sharding import tree_param_specs

            mesh = get_abstract_mesh()
            n_pods = dict(mesh.shape).get(pod_axis, 1)

            # stage 1: per-pod gradients (manual over the pod axis only; NO
            # pod-axis collectives inside — XLA's partitioner cannot lower
            # them under partial-manual mode).  Each pod's grads come out
            # stacked on a new leading pod dim.
            from .sharding import logical_rules, current_rules, tree_param_specs as _tps

            def _strip(ax, rules):
                out = {}
                for k, v in rules.items():
                    if isinstance(v, tuple):
                        v = tuple(a for a in v if a != ax) or None
                        v = v[0] if isinstance(v, tuple) and len(v) == 1 else v
                    elif v == ax:
                        v = None
                    out[k] = v
                return out

            inner_rules = _strip(pod_axis, current_rules())

            # stage 1: per-pod gradients WITHOUT manual mode (XLA's partial-
            # manual partitioner crashes on embedding gather/scatter —
            # b/433785288).  Parameters are stacked on a pod-sharded leading
            # dim and the model is vmapped over it: lane i sees pod i's batch
            # shard only, so autodiff cannot insert a cross-pod all-reduce.
            pspecs0 = _tps(params, mesh)
            params_p = jax.tree.map(
                lambda a, sp: jax.lax.with_sharding_constraint(
                    jnp.broadcast_to(a[None], (n_pods,) + a.shape),
                    P(pod_axis, *tuple(sp)),
                ),
                params, pspecs0,
            )
            batch_p = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(
                    a.reshape((n_pods, a.shape[0] // n_pods) + a.shape[1:]),
                    P(pod_axis, inner_rules.get("batch")),
                ),
                batch,
            )

            def per_pod(params_l, batch_l):
                with logical_rules(inner_rules):
                    return accumulate_grads(params_l, batch_l)

            grads_p, metrics_p = jax.vmap(per_pod)(params_p, batch_p)

            # stage 2: the paper's quantized all-reduce over the pod axis,
            # FULL-manual (per-leaf layouts from the param sharding rules)
            pspecs = tree_param_specs(params, mesh)

            def prepend(spec):
                return P(pod_axis, *tuple(spec))

            def reduce_leaf(g_l):
                return q_psum(g_l[0], pod_axis, qcomm_bits) / n_pods

            grads = jax.tree.map(
                lambda g, sp: shard_map(
                    reduce_leaf,
                    mesh=mesh,
                    in_specs=prepend(sp),
                    out_specs=sp,
                    check_vma=False,
                )(g),
                grads_p, pspecs,
            )
            metrics = jax.tree.map(lambda t: jnp.mean(t, axis=0), metrics_p)
        else:
            grads, metrics = accumulate_grads(params, batch)
        lr = cosine_warmup(opt_state.step, peak_lr=peak_lr, warmup_steps=warmup, total_steps=total_steps)
        params, opt_state, gnorm = adamw_update(params, grads, opt_state, lr)
        metrics = {**metrics, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


def _zero_metrics(cfg: ModelConfig):
    m = {"loss": jnp.zeros((), jnp.float32)}
    if cfg.family == "moe":
        m.update({
            "moe/load_balance": jnp.zeros((), jnp.float32),
            "moe/router_z": jnp.zeros((), jnp.float32),
            "moe/drop_frac": jnp.zeros((), jnp.float32),
        })
    return m


def make_prefill_step(cfg: ModelConfig):
    """(params, batch) -> last-position logits (B, V): the inference prefill."""

    def prefill_step(params, batch):
        logits, _ = forward(params, cfg, batch, kind="prefill")
        return logits[:, -1]

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    """(params, state, tokens (B,1), pos) -> (next_tokens (B,1), state)."""

    def step(params, state, tokens, pos):
        logits, state = _decode_step(params, cfg, state, tokens, pos)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt[:, None].astype(jnp.int32), state

    return step


def init_train_state(key, cfg: ModelConfig):
    params = init_model(key, cfg)
    return params, adamw_init(params)

"""Logical-axis sharding for the model zoo.

Activations are annotated with *logical* names; a context-scoped rules table
maps them to physical mesh axes.  The launcher sets the rules per mesh:

    single-pod (16, 16) ("data", "model"):   batch->data,  tensor->model
    multi-pod (2, 16, 16) ("pod","data","model"): batch->(pod,data), tensor->model
    long-context decode:                      seq->data (batch is 1)

Parameter shardings are derived from leaf names via PARAM_RULES — every
parameter name in the zoo encodes its role (see models/*.py).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def current_rules() -> dict:
    return getattr(_state, "rules", {})


@contextlib.contextmanager
def logical_rules(rules: dict):
    """rules: logical name -> physical axis (str, tuple, or None)."""
    prev = getattr(_state, "rules", {})
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def resolve(*logical_names) -> P:
    rules = current_rules()
    return P(*[rules.get(n, None) for n in logical_names])


def _mesh_sizes():
    try:
        from jax.sharding import get_abstract_mesh

        am = get_abstract_mesh()
        return dict(am.shape) if am.axis_names else None
    except Exception:
        return None


def _fit_spec_sizes(spec: P, shape, sizes) -> P:
    """Drop sharding on dims whose size isn't divisible by the axis product."""
    if sizes is None:
        return spec
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        prod = 1
        ok = all(a in sizes for a in axes)
        for a in axes:
            prod *= sizes.get(a, 1)
        fixed.append(ax if (ok and dim % prod == 0) else None)
    return P(*fixed)


def constrain(x, *logical_names):
    """with_sharding_constraint if rules are active (no-op in smoke tests).
    Axes that don't divide the corresponding dim are dropped."""
    if not current_rules():
        return x
    spec = _fit_spec_sizes(resolve(*logical_names), x.shape, _mesh_sizes())
    return jax.lax.with_sharding_constraint(x, spec)


# --- parameter rules -------------------------------------------------------
# leaf-name -> logical axes for the *trailing* dims (a leading scan/layer dim,
# if present, is unsharded).  fsdp == the data axis, tensor == the model axis.

PARAM_RULES = {
    # embeddings
    "embedding": ("tensor", "fsdp"),        # (V, D)
    "unembed": ("fsdp", "tensor"),          # (D, V)
    "pos_embedding": (None, "fsdp"),        # (S, D)
    # attention
    "wq": ("fsdp", "tensor"),               # (D, H*hd)
    "wk": ("fsdp", "tensor"),
    "wv": ("fsdp", "tensor"),
    "wo": ("tensor", "fsdp"),               # (H*hd, D)
    # dense mlp (wi covers fused gate+up)
    "wi": ("fsdp", "tensor"),               # (D, {1,2}F)
    "wo_mlp": ("tensor", "fsdp"),           # (F, D)
    # moe — expert-parallel over the model axis; F stays unsharded (the same
    # physical axis cannot appear twice in one spec)
    "router": ("fsdp", None),               # (D, E) — E small, replicate
    "w_in_e": ("expert", "fsdp", None),     # (E, D, {1,2}F)
    "w_out_e": ("expert", None, "fsdp"),    # (E, F, D)
    # ssm / xlstm
    "w_ssm_in": ("fsdp", "tensor"),
    "w_ssm_out": ("tensor", "fsdp"),
    "conv_w": (None, "tensor"),             # (K, d_inner)
    "a_log": ("tensor",),
    "dt_bias": ("tensor",),
    "r_h": (None, "tensor"),                # sLSTM recurrent (hd, H*hd) blocks
    # norms / scalars
    "scale": (None,),
    "bias": (None,),
}


def gather_layer_params(layer_params):
    """FSDP gather INSIDE the layer-scan body.

    Constrains every weight leaf to its compute sharding with the fsdp axis
    dropped (tensor-parallel axis kept).  Placing this constraint inside the
    scan body pins the all-gather to one layer at a time — without it XLA may
    hoist the gather of the whole stacked (L, ...) parameter out of the loop,
    exploding peak memory (observed: 433 GB/device on mistral-large-123b).
    """
    rules = current_rules()
    if not rules:
        return layer_params
    sizes = _mesh_sizes()

    def f(path, leaf):
        name = getattr(path[-1], "key", getattr(path[-1], "name", "")) if path else ""
        logical = PARAM_RULES.get(name)
        if logical is None or not hasattr(leaf, "ndim"):
            return leaf
        axes = [
            (rules.get(a, None) if a not in (None, "fsdp") else None) if a else None
            for a in logical
        ]
        pad = leaf.ndim - len(axes)
        if pad < 0:
            return leaf
        spec = _fit_spec_sizes(P(*([None] * pad + axes)), leaf.shape, sizes)
        return jax.lax.with_sharding_constraint(leaf, spec)

    return jax.tree_util.tree_map_with_path(f, layer_params)


def param_spec_for(name: str, ndim: int, stacked: bool) -> P:
    rules = current_rules()
    logical = PARAM_RULES.get(name)
    if logical is None:
        # default: replicate
        return P()
    axes = [rules.get(a, None) if a else None for a in logical]
    # ndim may exceed the rule (e.g. grouped dims) — pad with None on the left
    # after the optional stacked dim
    lead = [None] if stacked else []
    pad = ndim - len(axes) - len(lead)
    return P(*(lead + [None] * pad + axes))


def fit_spec_to_mesh(spec: P, shape, mesh) -> P:
    """Drop sharding on any dim whose size isn't divisible by the mesh-axis
    product (e.g. a 51865 vocab or 4 KV heads can't split 16 ways)."""
    if mesh is None:
        return spec
    try:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    except (AttributeError, ValueError, NotImplementedError):
        sizes = dict(mesh.shape)  # AbstractMesh
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        prod = 1
        ok = True
        for a in axes:
            if a not in sizes:
                ok = False
                break
            prod *= sizes[a]
        fixed.append(ax if (ok and dim % prod == 0) else None)
    return P(*fixed)


def tree_param_specs(params_tree, mesh=None):
    """Map a pytree of arrays/ShapeDtypeStructs to PartitionSpecs by leaf name.

    A leaf is 'stacked' when its first dim is a layer-scan dim — encoded by the
    surrounding dict key 'layers'/'blocks' in its path.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
    specs = []
    for path, leaf in flat:
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        name = keys[-1] if keys else ""
        stacked = any(k in ("layers", "blocks", "enc_layers", "dec_layers", "mamba_layers") for k in keys[:-1])
        spec = param_spec_for(name, leaf.ndim, stacked)
        specs.append(fit_spec_to_mesh(spec, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


# canonical rule tables used by the launcher -------------------------------

def rules_single_pod() -> dict:
    return {"batch": "data", "fsdp": "data", "tensor": "model", "expert": "model", "seq": None}


def rules_multi_pod() -> dict:
    # pure data-parallel across pods: params replicated over 'pod', batch
    # sharded over (pod, data)
    return {"batch": ("pod", "data"), "fsdp": "data", "tensor": "model", "expert": "model", "seq": None}


def rules_long_context(multi_pod: bool) -> dict:
    # batch==1: shard the KV sequence over the data axis instead
    base = rules_multi_pod() if multi_pod else rules_single_pod()
    base = dict(base)
    base["batch"] = None
    base["seq"] = "data"
    return base

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination
with ShapeDtypeStruct inputs (no allocation) and extract the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]

Shape skips (documented in DESIGN.md / EXPERIMENTS.md):
  * long_500k only for sub-quadratic-state archs (ssm / hybrid / gemma2
    sliding window); skipped for pure full-attention archs.

The 512 placeholder devices are forced only under __main__ (or an explicit
force_placeholder_devices() call) — importing this module leaves the
process's device configuration alone.
"""
import os

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config, list_archs, input_specs
from ..models import SHAPES, make_train_step, make_prefill_step, make_decode_step
from ..models.steps import init_train_state
from ..models.decode import init_decode_state, decode_state_specs
from ..models.sharding import (
    logical_rules,
    rules_single_pod,
    rules_multi_pod,
    rules_long_context,
    tree_param_specs,
)
from .mesh import make_production_mesh, PEAK_FLOPS_BF16, HBM_BW, ICI_BW
from ..roofline import analyze_hlo
from ..compat import cost_analysis_dict

LONG_CONTEXT_OK = {"xlstm-125m", "zamba2-2.7b", "gemma2-2b"}


def force_placeholder_devices(n: int = 512) -> None:
    """Force ``n`` placeholder host devices for the multi-pod dry-run.

    Must run before the jax backend initializes (first device query).  This
    is deliberately NOT done at import time: importing this module must not
    stomp the process's device configuration (e.g. the test conftest's
    8-device setting) — only the ``__main__`` entry point forces 512.
    """
    from ..compat import force_host_device_count

    force_host_device_count(n)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-buffer bytes of every collective op in the (per-device SPMD)
    optimized HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    # e.g.:  %ag = bf16[2,4096,3072] all-gather(...)
    pat = re.compile(
        r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\b(" + "|".join(_COLLECTIVES) + r")\b"
    )
    for m in pat.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        size = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                size *= int(d)
        out[op] += size
    return out


def skip_reason(arch: str, shape_name: str):
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_OK:
        return "full-attention arch: 500k dense KV decode is quadratic-state; skipped per assignment"
    return None


def build_lowerable(arch: str, shape_name: str, mesh, multi_pod: bool):
    """Returns (fn, example_args) ready for jit(...).lower(*args)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "decode":
        rules = rules_long_context(multi_pod) if shape_name == "long_500k" else (
            rules_multi_pod() if multi_pod else rules_single_pod()
        )
    else:
        rules = rules_multi_pod() if multi_pod else rules_single_pod()

    with logical_rules(rules):
        params_sds = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg))
        params_abs, opt_abs = params_sds
        pspecs = tree_param_specs(params_abs, mesh)
        ospecs = type(opt_abs)(step=P(), m=tree_param_specs(opt_abs.m, mesh), v=tree_param_specs(opt_abs.v, mesh))

        def shard(sds_tree, spec_tree):
            return jax.tree.map(
                lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
                sds_tree, spec_tree,
            )

        batch_rules = rules  # batch axes
        if shape.kind == "train":
            batch = input_specs(cfg, shape)
            bspec = jax.tree.map(
                lambda s: P(batch_rules["batch"], *([None] * (len(s.shape) - 1))), batch
            )
            # gradient accumulation: keep ~128k global tokens per microbatch
            # (REPRO_MB_TOKENS overrides; perf iterations sweep this)
            # per-device microbatch share halves across pods; scale the
            # global microbatch so per-device live activations stay constant
            default_mb = cfg.train_mb_tokens * (2 if multi_pod else 1)
            mb_tokens = int(os.environ.get("REPRO_MB_TOKENS", default_mb))
            mb = max(1, shape.global_batch * shape.seq_len // mb_tokens)
            while shape.global_batch % mb:
                mb -= 1
            qbits = int(os.environ.get("REPRO_QCOMM_BITS", 0))
            fn = make_train_step(cfg, microbatches=mb,
                                 qcomm_bits=qbits if multi_pod else 0)
            donate = (0, 1)  # params + opt state update in place
            args = (
                shard(params_abs, pspecs),
                type(opt_abs)(
                    step=jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
                    m=shard(opt_abs.m, ospecs.m),
                    v=shard(opt_abs.v, ospecs.v),
                ),
                shard(batch, bspec),
            )
            out_shardings = None
        elif shape.kind == "prefill":
            batch = input_specs(cfg, shape)
            bspec = jax.tree.map(
                lambda s: P(batch_rules["batch"], *([None] * (len(s.shape) - 1))), batch
            )
            fn = make_prefill_step(cfg)
            donate = ()
            args = (shard(params_abs, pspecs), shard(batch, bspec))
            out_shardings = None
        else:  # decode
            B = shape.global_batch
            state_abs = jax.eval_shape(lambda: init_decode_state(cfg, B, shape.seq_len))
            sspecs = decode_state_specs(state_abs, mesh)
            tok = jax.ShapeDtypeStruct(
                (B, 1), jnp.int32,
                sharding=NamedSharding(mesh, P(batch_rules.get("batch"), None)),
            )
            pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
            fn = make_decode_step(cfg)
            donate = (1,)  # cache state updates in place
            args = (shard(params_abs, pspecs), shard(state_abs, sspecs), tok, pos)
            out_shardings = None
    return fn, args, rules, donate


def model_flops_estimate(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D = batch tokens."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_params, n_active = param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per sequence


def param_counts(cfg):
    """(total, active-per-token) parameter counts from the config algebra."""
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hd, Hq, Hkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    attn = D * hd * (Hq + 2 * Hkv) + Hq * hd * D
    gate = 2 if cfg.activation in ("swiglu", "geglu") else 1
    mlp = D * F * gate + F * D if F else 0
    embed = V * D * (1 if cfg.tie_embeddings else 2)
    total = active = 0
    if cfg.family in ("dense", "vlm"):
        total = active = cfg.num_layers * (attn + mlp)
    elif cfg.family == "moe":
        e_mlp = D * cfg.moe_d_ff * gate + cfg.moe_d_ff * D
        shared = (D * cfg.shared_d_ff * gate + cfg.shared_d_ff * D) if cfg.num_shared_experts else 0
        dense_res = mlp if cfg.moe_dense_residual else 0
        total = cfg.num_layers * (attn + cfg.num_experts * e_mlp + shared + dense_res)
        active = cfg.num_layers * (attn + cfg.top_k * e_mlp + shared + dense_res)
    elif cfg.family == "ssm":
        # mLSTM ~ 4 D*Hq*hd + gates; sLSTM ~ 4 D*H*hd + rec
        pair = (4 * D * Hq * hd + D * 2 * Hq + D * Hq * hd) + (4 * D * Hq * hd + Hq * hd * 4 * hd + Hq * hd * D)
        total = active = (cfg.num_layers // 2) * pair
    elif cfg.family == "hybrid":
        d_inner = cfg.ssm_expand * D
        mamba = D * (2 * d_inner + 2 * cfg.ssm_state + Hq) + d_inner * D
        total = active = cfg.num_layers * mamba + (attn + mlp)  # one shared block
    elif cfg.family == "encdec":
        total = active = cfg.enc_layers * (attn + mlp) + cfg.num_layers * (2 * attn + mlp)
    total += embed
    active += embed
    return float(total), float(active)


def run_one(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True):
    reason = skip_reason(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod, "skipped": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    fn, args, rules, donate = build_lowerable(arch, shape_name, mesh, multi_pod)
    with jax.set_mesh(mesh), logical_rules(rules):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = cost_analysis_dict(compiled)
        # trip-count-aware walk of the optimized HLO (XLA's cost_analysis
        # counts while bodies once — see repro.roofline.hlo_cost)
        parsed = analyze_hlo(compiled.as_text())
        t_analyze = time.time() - t0 - t_lower - t_compile

    flops_dev = parsed.flops
    bytes_dev = parsed.bytes
    coll_bytes = parsed.collective_bytes
    coll = {k: v for k, v in parsed.collectives.items()}
    res = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_bytes,
            "collectives": coll,
            "xla_flops_noloop": float(cost.get("flops", 0.0)),
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            # arguments + the temp allocation slab (buffer reuse is already
            # folded into the slab size).  NOTE peak_memory_in_bytes on the
            # CPU backend reports only args+outputs — not usable.
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "roofline": roofline_terms(flops_dev, bytes_dev, coll_bytes),
        "model_flops_global": model_flops_estimate(arch, shape_name),
    }
    res["roofline"]["useful_flops_ratio"] = (
        res["model_flops_global"] / (flops_dev * n_chips) if flops_dev else None
    )
    if verbose:
        r = res["roofline"]
        print(
            f"{arch:20s} {shape_name:12s} pods={2 if multi_pod else 1} "
            f"compile={t_compile:6.1f}s  compute={r['compute_s']:.3e}s "
            f"memory={r['memory_s']:.3e}s coll={r['collective_s']:.3e}s "
            f"dom={r['dominant']}  peakGB={res['memory']['peak_bytes']/1e9 if res['memory']['peak_bytes'] else -1:.2f}",
            flush=True,
        )
    return res


def roofline_terms(flops_dev, bytes_dev, coll_bytes_dev):
    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = bytes_dev / HBM_BW
    coll_s = coll_bytes_dev / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    return {**terms, "dominant": dom.replace("_s", "")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    results = []
    for a, s, mp in combos:
        try:
            results.append(run_one(a, s, mp))
        except Exception as e:  # a failure here is a bug in the system
            results.append({"arch": a, "shape": s, "multi_pod": mp, "error": f"{type(e).__name__}: {e}"})
            print(f"{a:20s} {s:12s} FAILED: {type(e).__name__}: {str(e)[:200]}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_err = sum("error" in r for r in results)
    print(f"\n{len(results)} combos, {n_err} failures, "
          f"{sum('skipped' in r for r in results)} documented skips")
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    force_placeholder_devices()
    main()

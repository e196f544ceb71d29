"""Closed-loop refits: one caller runs back-to-back ``DistributedGP.fit``
calls on one seeded dataset, each blocked until its artifact is ready.

Set-up fits the dataset once.  The program builds each dataset's inner
products into its training program as constants, so a fit of data it has
not fitted compiles anew; the window's fits are refits of data the process
has fitted before, and run only programs that are compiled: a retrace, a
load from the compilation cache and the device work of every fit.

Traffic keys: ``check_batches`` x ``batch`` (held-out rows the last fit of
the window is asked for after it, to compare with the reference).

End-to-end: ``refit_s``, the window's wall time over the fits completed in
it.  Counters: ``fits``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import cells
from bench import data as bdata
from bench.common import block, program_census


def setup(ctx):
    cfg = ctx.cfg
    data_seed, rng_seed = bdata.derived_seeds(ctx.seed, 2)
    ds = cells.dataset(cfg, data_seed)
    est = cells.estimator(cfg)
    ctx.log(f"set-up: one dataset of {cfg['n_train']} points, m={cfg['m']}")
    block(est.fit(ds.X, ds.y, cfg["m"], key=ds.key()))
    return {"est": est, "ds": ds, "rng": np.random.default_rng(rng_seed),
            "art": None}


def window(st, ctx):
    cfg, est, ds = ctx.cfg, st["est"], st["ds"]
    fits = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        st["art"] = None  # one artifact alive at a time, as a user holds it
        with ctx.span("refit"):
            st["art"] = block(est.fit(ds.X, ds.y, cfg["m"], key=ds.key()))
        fits += 1
    elapsed = time.perf_counter() - t0
    ctx.log(f"window: {fits} fits in {elapsed:.3f} s")
    return {"e2e": {"refit_s": elapsed / fits}, "counters": {"fits": fits},
            "attempted": fits, "failed": 0}


def outputs(st, ctx):
    """The last fit's hyperparameters and its answers at held-out rows,
    served through the artifact's own predict program."""
    tr, ds, art = ctx.traffic, st["ds"], st["art"]
    Xq = cells.query_rows(ds, st["rng"], tr["check_batches"], tr["batch"])
    preds = [st["est"].predict(art, q) for q in Xq]
    ctx.log("served program: " + str(program_census(
        lambda x: st["est"].predict(art, x), Xq[0])))
    p = art.params
    return {"dataset": ds, "Xq": Xq,
            "params": np.asarray([float(p.log_a), float(p.log_b),
                                  float(p.log_noise)]),
            "mu": np.concatenate([np.asarray(m) for m, _ in preds]),
            "var": np.concatenate([np.asarray(v) for _, v in preds])}


def reference_outputs(out, ctx, precision: str = "highest") -> dict:
    """``out`` with the program's fit replaced by the reference's, fitted at
    ``precision`` to the same dataset and split."""
    ds = out["dataset"]
    ref = cells.reference_fit(ctx.cfg, ds, precision)
    Xq = out["Xq"].reshape(-1, out["Xq"].shape[-1])
    mu, var = cells.reference_answers(ref, Xq, precision=precision)
    return dict(out, params=np.asarray(ref.params, np.float64), mu=mu, var=var)


def compare(out, ctx) -> dict:
    """The numbers ``correct`` is decided on: this fit against the
    reference's fit of the same dataset and split."""
    ref = reference_outputs(out, ctx)
    nums = {"param_gap": float(np.max(np.abs(out["params"] - ref["params"])))}
    nums.update(cells.gaps(out["mu"], out["var"], ref["mu"], ref["var"],
                           float(np.std(out["dataset"].y)),
                           float(np.exp(ref["params"][0]))))
    return nums

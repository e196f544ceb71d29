"""Faults a fit cell can have, each planted in the program for a whole run:
the check that decides ``correct`` must catch every one of them.

    with faults.planted("no_exchange"):
        ...  # set-up, window, outputs of one run

* ``state_unchanged``: the training scan returns its initial hyperparameters;
* ``half_data``: the fit sees the first half of the rows, the rest left out;
* ``no_exchange``: every peer's reconstruction reaches the receivers as
  zeros, its decoded points and its decode tables both (the broadcast
  between machines left out);
* ``answer_altered``: the first mean of every answer moves by a tenth of the
  targets' spread where predict produces it.

Used by ``bench/tests/test_faults.py`` at a CPU test's size and by
``bench/control.py --fault`` on the chip at the cell's own size.
"""
from __future__ import annotations

import contextlib

import numpy as np


def _state_unchanged():
    from repro.core.protocols import broadcast

    real = broadcast.train_gp
    return broadcast, "train_gp", lambda *a, **k: real(*a, **dict(k, steps=0))


def _half_data():
    from repro.core.api import DistributedGP

    real = DistributedGP.fit

    def half(self, X=None, y=None, m=None, **kw):
        n = np.asarray(X).shape[0] // 2
        return real(self, np.asarray(X)[:n], np.asarray(y)[:n], m, **kw)

    return DistributedGP, "fit", half


def _no_exchange():
    import jax.numpy as jnp
    from repro.core.protocols import wire

    real = wire._run_wire_protocol

    def silent(*a, **k):
        ws = real(*a, **k)
        return ws._replace(decoded=jnp.zeros_like(ws.decoded),
                           scaled_cents=jnp.zeros_like(ws.scaled_cents))

    return wire, "_run_wire_protocol", silent


def _answer_altered():
    from repro.core.protocols import base

    real = base.predict

    def altered(art, X, available=None):
        mu, var = real(art, X, available)
        return mu.at[0].add(0.1 * float(np.std(np.asarray(art.y)))), var

    return base, "predict", altered


FAULTS = {"state_unchanged": _state_unchanged, "half_data": _half_data,
          "no_exchange": _no_exchange, "answer_altered": _answer_altered}


@contextlib.contextmanager
def planted(name: str):
    owner, attr, broken = FAULTS[name]()
    real = getattr(owner, attr)
    setattr(owner, attr, broken)
    try:
        yield
    finally:
        setattr(owner, attr, real)

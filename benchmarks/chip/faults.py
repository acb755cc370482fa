"""Faults planted in the program under test, for the correctness check's
own tests and for reading each fault's numbers on the chip
(``calibrate.py``). Each is a context manager that patches the program's
module attribute that the family's ``build`` looks up when it builds (and
traces) the round, and restores it on exit: ``round.sfpl_round``, or the
split builder that the family names (``SPLIT``):

* ``state_unchanged`` — the round returns the state it was given (and the
  losses it computed);
* ``half_batch`` — the server update sees only the first half of the
  pool, its mean loss taken over that half;
* ``misroute`` — the activation gradient of each pool row is handed to
  the next row's client (an answer altered where it is produced).

The exchange between chips left out is a fault of a four-chip cell, which
the benchmark does not have yet.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib

import jax
import jax.numpy as jnp

FAULTS = ("state_unchanged", "half_batch", "misroute")


@jax.custom_vjp
def _misroute(a):
    return a


_misroute.defvjp(lambda a: (a, None),
                 lambda _, g: (jnp.roll(g, 1, axis=0),))


def _wrap_split(family, mutate):
    module, attr = family.SPLIT
    mod = importlib.import_module(module)
    orig = getattr(mod, attr)

    def make(*args, **kw):
        s = orig(*args, **kw)

        def server_loss(sp, ss, a, y, training=True, rmsd=None,
                        valid=None):
            a, y = mutate(a, y)
            return s.server_loss(sp, ss, a, y, training, rmsd)
        return dataclasses.replace(s, server_loss=server_loss)
    return mod, attr, make


def _half(a, y):
    h = a.shape[0] // 2
    return a[:h], y[:h]


@contextlib.contextmanager
def planted(name, family):
    """``name`` planted in the program of ``family`` (``spec.family``)."""
    from repro.core import round as RD
    if name == "state_unchanged":
        orig = RD.sfpl_round

        def sfpl_round(key, st, *a, **k):
            _, losses = orig(key, st, *a, **k)
            return st, losses
        mod, attr, new = RD, "sfpl_round", sfpl_round
    elif name == "half_batch":
        mod, attr, new = _wrap_split(family, _half)
    elif name == "misroute":
        mod, attr, new = _wrap_split(family, lambda a, y: (_misroute(a), y))
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    old = getattr(mod, attr)
    setattr(mod, attr, new)
    try:
        yield
    finally:
        setattr(mod, attr, old)

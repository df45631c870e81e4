"""Semiring registry for associative arrays.

The paper defines associative arrays over a value semiring
``(V, oplus, otimes, 0, 1)``.  The hierarchical cascade only requires ``oplus`` to
be associative and commutative; every semiring here satisfies that.

Semirings are passed to jitted functions as *static* arguments (they are
hashable singletons), so choosing a semiring never triggers retracing churn
beyond the first compile per semiring.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False -> identity hash, safe as a jit static arg
class Semiring:
    """A value semiring ``(V, add, mul, zero, one)``.

    ``add``/``mul`` must be elementwise-broadcastable jnp functions.
    ``zero`` is the additive identity *and* multiplicative annihilator —
    it is also used as the padding value for dead slots in an Assoc.
    ``add_commutes`` is False only for an ``add`` whose result depends on
    the order of its operands (``union.first``): ``assoc.add`` then keeps
    the left operand's entry first for each shared key.
    """

    name: str
    add: Callable
    mul: Callable
    zero: float
    one: float
    add_commutes: bool = True

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Semiring({self.name})"

    def add_identity(self, dtype) -> jnp.ndarray:
        return jnp.asarray(self.zero, dtype=dtype)


def _min(x, y):
    return jnp.minimum(x, y)


def _max(x, y):
    return jnp.maximum(x, y)


def _plus(x, y):
    return x + y


def _times(x, y):
    return x * y


def _first(x, y):  # union semantics: keep earliest value
    return x


def _second(x, y):  # overwrite semantics: keep latest value
    return y


# --- the standard semirings from the paper (Section II) -------------------
PLUS_TIMES = Semiring("plus.times", _plus, _times, 0.0, 1.0)
MAX_PLUS = Semiring("max.plus", _max, _plus, -jnp.inf, 0.0)
MIN_PLUS = Semiring("min.plus", _min, _plus, jnp.inf, 0.0)
MAX_TIMES = Semiring("max.times", _max, _times, 0.0, 1.0)  # V = [0, inf)
MIN_TIMES = Semiring("min.times", _min, _times, jnp.inf, 1.0)  # V = [0, inf]
MAX_MIN = Semiring("max.min", _max, _min, 0.0, jnp.inf)  # V = [0, inf]
MIN_MAX = Semiring("min.max", _min, _max, jnp.inf, 0.0)  # V = [0, inf]
# Union/intersection analogue on numeric labels: "keep first" fold.
FIRST = Semiring("union.first", _first, _second, jnp.nan, jnp.nan, add_commutes=False)
# Counting semiring: add = +, mul = logical AND-ish product of counts.
COUNT = Semiring("count", _plus, _times, 0.0, 1.0)

REGISTRY = {
    s.name: s
    for s in [
        PLUS_TIMES,
        MAX_PLUS,
        MIN_PLUS,
        MAX_TIMES,
        MIN_TIMES,
        MAX_MIN,
        MIN_MAX,
        FIRST,
        COUNT,
    ]
}


def get(name: str) -> Semiring:
    """Look up a semiring by its ``name`` (e.g. ``"plus.times"``)."""
    try:
        return REGISTRY[name]
    except KeyError:  # pragma: no cover - defensive
        raise KeyError(f"unknown semiring {name!r}; known: {sorted(REGISTRY)}")

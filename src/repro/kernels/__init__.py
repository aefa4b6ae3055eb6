"""Pallas TPU kernel for the paper's congestion hot-spot.

* ``congestion``  — interval-congestion matmul (LP constraints, Lemma-1
                    bound, PDHG operator; the LP's ``operator="pallas"``).

``ops`` holds the jit'd wrappers (interpret=True off-TPU); ``ref`` the
pure-jnp oracles the tests sweep against.
"""

from . import ops, ref
from .ops import congestion

__all__ = ["ops", "ref", "congestion"]

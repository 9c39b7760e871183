"""Dispatching wrapper for the fused stopping-condition check.

Two dispatch layers live here:

  * ``stopcheck`` — XLA-ref vs fused-Pallas backend selection for the
    Bernstein (f/g) reduction, unchanged since PR 1;
  * the *stop-rule registry* — per-estimator dispatch for the
    estimator-plugin substrate (``repro.core.estimators``).  A stop rule
    is a callable ``(counts (V,), tau (), params) -> (done, max_f,
    max_g)`` evaluated on a consistent aggregated snapshot; estimators
    name their rule via the ``stop_rule`` class attribute and the
    engine resolves it here.  The Bernstein rule registered below *is*
    ``repro.core.kadabra.check_stop`` — the same callable the
    pre-refactor drivers invoked, so dispatching through the registry
    is bit-for-bit identical to the PR 1-6 hard-wired call.  All three
    shipped estimators (betweenness, closeness, harmonic) share it:
    their observations live in [0, 1], which is the only property the
    f/g bounds use (DESIGN.md §Estimator substrate).
"""
from __future__ import annotations

from functools import partial

import jax

from .kernel import stopcheck_pallas
from .ref import stopcheck_ref

__all__ = ["stopcheck", "register_stop_rule", "get_stop_rule",
           "stop_rule_names"]


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def stopcheck(counts, tau, log_inv_delta_l, log_inv_delta_u, omega, *,
              use_pallas=False, interpret=None):
    if use_pallas:
        return stopcheck_pallas(counts, tau, log_inv_delta_l,
                                log_inv_delta_u, omega, interpret=interpret)
    return stopcheck_ref(counts, tau, log_inv_delta_l, log_inv_delta_u,
                         omega)


# ---------------------------------------------------------------------------
# Per-estimator stop-rule registry
# ---------------------------------------------------------------------------

_STOP_RULES: dict = {}


def register_stop_rule(name: str, fn) -> None:
    """Register ``fn(counts, tau, params) -> (done, max_f, max_g)``.

    Re-registering the same name with a different callable is an error
    (two estimators silently fighting over a rule name would be a
    correctness bug, not a convenience)."""
    prev = _STOP_RULES.get(name)
    if prev is not None and prev is not fn:
        raise ValueError(f"stop rule {name!r} already registered")
    _STOP_RULES[name] = fn


def get_stop_rule(name: str):
    """Resolve a registered stop rule; KeyError lists what exists."""
    try:
        return _STOP_RULES[name]
    except KeyError:
        raise KeyError(
            f"no stop rule {name!r} registered "
            f"(have: {sorted(_STOP_RULES)})") from None


def stop_rule_names():
    return sorted(_STOP_RULES)


def _register_builtin():
    # check_stop is the exact callable the pre-refactor adaptive drivers
    # used — registering it (not a re-derivation) is what keeps the
    # registry dispatch bit-for-bit identical for run_kadabra.
    from repro.core.kadabra import check_stop
    register_stop_rule("bernstein", check_stop)


_register_builtin()

"""The stochastic-rightsizing smoke: one fixed golden burst grid.

``stochastic_smoke`` fans a pinned GCT-like forecast into K scenarios,
runs the full ``plan_stochastic`` path (ONE batched LP dispatch +
lockstep placement + CVaR selection), and returns the deterministic
summary blob ``benchmarks.run`` merges into ``solver_stats.json``
under the ``stochastic`` key — the blob ``benchmarks.check_stochastic``
gates against ``results/golden/stochastic.json``.

Like the ruiz/pipeline gate grids in ``paper_tables.fleet_sweep``, the
forecast and selection parameters here are FIXED at every ``--scale``:
the CI gate pins the frontier numbers, so the grid must not move when
the surrounding benchmark scales down.  Only K is a parameter
(``benchmarks.run --scenarios``), and the committed golden was
generated at ``GOLDEN_K`` — a run at any other K still satisfies the
structural invariants but skips the frontier diff.

The burst channel is deliberately hot (``burst_prob=0.15`` with a
Pareto-1.6 tail): heavy-tailed spikes are the regime where the
CVaR-selected fleet strictly dominates expected-cost-only selection on
worst-scenario overload — the separation the gate asserts.
"""

from __future__ import annotations

# the golden burst grid: every field pinned, independent of --scale
GOLDEN_FORECAST = {
    "n": 120, "m": 6, "seed": 0, "cost_model": "gce", "e": 1.0,
    "load_sigma": 0.15, "diurnal_amp": 0.10,
    "burst_prob": 0.15, "burst_alpha": 1.6, "burst_cap": 8.0,
}
GOLDEN_SELECT = {
    "seed": 0, "cvar_alpha": 0.9, "cvar_lambda": 2.0,
    "overload_premium": 3.0, "recfg_weight": 0.0, "quantiles": 9,
    "algo": "lp-map-f",
}
GOLDEN_K = 64


def stochastic_smoke(scenarios: int | None = None) -> dict:
    """Run the golden burst grid at K=``scenarios`` (default
    ``GOLDEN_K``) and return the summary blob plus provenance."""
    from repro.stochastic import (StochasticConfig, gct_forecast,
                                  plan_stochastic)

    K = scenarios if scenarios is not None else GOLDEN_K
    forecast = gct_forecast(**GOLDEN_FORECAST)
    config = StochasticConfig(scenarios=K, **GOLDEN_SELECT)
    res = plan_stochastic(forecast, config)
    blob = res.summary()
    blob["forecast"] = dict(GOLDEN_FORECAST)
    blob["golden_k"] = GOLDEN_K
    blob["timings"] = {k: round(v, 3) for k, v in res.timings.items()
                       if k in ("lp_s", "place_s")}
    return blob


if __name__ == "__main__":
    import json

    print(json.dumps(stochastic_smoke(), indent=1))

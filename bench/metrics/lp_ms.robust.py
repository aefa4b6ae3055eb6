"""Mean milliseconds per robust plan in ``plan_stochastic``'s one batched,
tolerance-stopped dispatch of the scenarios' mapping LPs, to its results
on the host: the program's ``repro.lp`` span (``timings["lp_s"]``)."""


def read(record):
    s = record.mean("lp_s")
    return None if s is None else 1e3 * s

"""Mean milliseconds per robust plan on the host around the solve and
the placement: the scenario fan-out and packing (``repro.fanout``,
``timings["fanout_s"]``) and the candidate menu and CVaR selection
(``repro.select``, ``timings["select_s"]``)."""


def read(record):
    s = record.mean("select_s")
    return None if s is None else 1e3 * s

"""Mean milliseconds per robust plan in the placement protocol over the
scenarios (every fit policy, each scenario's cheapest plan kept and
verified): the program's ``repro.place`` span (``timings["place_s"]``)."""


def read(record):
    s = record.mean("place_s")
    return None if s is None else 1e3 * s

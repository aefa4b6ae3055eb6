"""Mean milliseconds per plan in ``FleetEngine.evaluate``'s placement
protocol (every fit policy, the cheapest plan kept and verified): the
program's ``timings["place_s"]``."""


def read(record):
    s = record.mean("place_s")
    return None if s is None else 1e3 * s

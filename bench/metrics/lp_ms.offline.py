"""Mean milliseconds per plan in ``FleetEngine.evaluate``'s batched,
tolerance-stopped mapping LP, to its results on the host: the
program's ``timings["lp_s"]``."""


def read(record):
    s = record.mean("lp_s")
    return None if s is None else 1e3 * s

"""Mean milliseconds per robust plan in ``verify`` of each scenario's
kept plan: the program's ``repro.verify`` spans (``timings["verify_s"]``)."""


def read(record):
    s = record.mean("verify_s")
    return None if s is None else 1e3 * s

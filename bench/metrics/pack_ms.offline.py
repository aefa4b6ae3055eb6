"""Mean milliseconds per plan in ``FleetEngine.evaluate``'s packing
(lower, trim, bucket, pad): the program's ``timings["pack_s"]``."""


def read(record):
    s = record.mean("pack_s")
    return None if s is None else 1e3 * s

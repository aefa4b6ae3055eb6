"""Mean PDHG iterations per LP lane (``SolveStats.iterations``)."""


def read(record):
    return record.mean("lp_iters")

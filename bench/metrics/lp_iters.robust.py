"""Mean PDHG iterations per scenario's LP lane (``SolveStats.iterations``
of ``plan_stochastic``'s batched dispatch)."""


def read(record):
    return record.mean("lp_iters")

"""Share, in %, of the LP program's device time spent in its float64
phases: the union of the intervals of the operations scoped
``certificate`` or ``polish`` over the device time of the programs named
``_pdhg_run_many_tol``, clipped to the traced window."""

from bench import program_trace


def read(record):
    pt = program_trace.of(record)
    return None if pt is None else pt.scope_pct(program_trace.F64_SCOPES)

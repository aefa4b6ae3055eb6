"""Mean milliseconds per traced plan in ``verify`` of each fleet's kept
plan: the program's ``repro.verify`` spans (``timings["verify_s"]``)."""

from bench import program_trace


def read(record):
    pt = program_trace.of(record)
    if pt is None or not pt.plans or not pt.has("repro.evaluate"):
        return None
    return 1e3 * pt.span_seconds("repro.verify") / pt.plans

"""Lockstep placement steps per traced plan, over every placement pass:
the ``steps`` the program's ``repro.place.pass`` spans carry
(``timings["placement"]["steps"]``)."""

from bench import program_trace


def read(record):
    pt = program_trace.of(record)
    steps = None if pt is None else pt.span_stat("repro.place.pass", "steps")
    return None if steps is None or not pt.plans else steps / pt.plans

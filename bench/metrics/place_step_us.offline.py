"""Host microseconds per lockstep placement step: the wave seconds over
the steps that the program's ``repro.place.pass`` spans carry
(``timings["placement"]`` ``wave_s_total`` over ``steps``)."""

from bench import program_trace


def read(record):
    pt = program_trace.of(record)
    if pt is None:
        return None
    steps = pt.span_stat("repro.place.pass", "steps")
    wave_s = pt.span_stat("repro.place.pass", "wave_s")
    return None if not steps or wave_s is None else 1e6 * wave_s / steps

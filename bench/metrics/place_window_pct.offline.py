"""Share, in %, of the timeline slots the lockstep placement steps read:
the ``window_slots`` over the ``slots`` (T' a step) that the program's
``repro.place.pass`` spans carry (``timings["placement"]``)."""

from bench import program_trace


def read(record):
    pt = program_trace.of(record)
    if pt is None:
        return None
    window = pt.span_stat("repro.place.pass", "window_slots")
    slots = pt.span_stat("repro.place.pass", "slots")
    return None if not slots or window is None else 100.0 * window / slots

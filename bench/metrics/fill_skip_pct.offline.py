"""Share, in %, of the cross-fill placement attempts skipped without a
lockstep step, because they fit no node of the fill pool: the
``fill_skipped`` over the ``fill_attempts`` that the program's
``repro.place.pass`` spans carry (``timings["placement"]``)."""

from bench import program_trace


def read(record):
    pt = program_trace.of(record)
    if pt is None:
        return None
    skipped = pt.span_stat("repro.place.pass", "fill_skipped")
    attempts = pt.span_stat("repro.place.pass", "fill_attempts")
    return None if not attempts or skipped is None \
        else 100.0 * skipped / attempts

"""Host microseconds per lockstep placement step in robust plans: the
wave seconds over the steps of every plan (``timings["placement"]``
``wave_s_total`` over ``steps``)."""


def read(record):
    steps = sum(record.samples.get("place_steps", ()))
    wave_s = sum(record.samples.get("place_wave_s", ()))
    return None if not steps else 1e6 * wave_s / steps

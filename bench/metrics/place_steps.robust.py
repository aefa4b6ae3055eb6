"""Lockstep placement steps per robust plan, over every placement pass:
``timings["placement"]["steps"]``, the sum of the ``steps`` that the
program's ``repro.place.pass`` spans carry."""


def read(record):
    return record.mean("place_steps")

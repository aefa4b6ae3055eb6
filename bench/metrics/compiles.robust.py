"""Programs compiled or loaded from the persistent cache inside the
window (JAX's compile events); set-up warms every program the window
runs, so this reads 0."""


def read(record):
    return record.counts.get("compiles")

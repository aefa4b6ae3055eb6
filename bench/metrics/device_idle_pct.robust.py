"""Share, in %, of the traced window of robust plans in which no
operation ran on the device: 1 - (union of the device's operation
intervals) / window."""


def read(record):
    return None if record.trace is None else record.trace.idle_pct()

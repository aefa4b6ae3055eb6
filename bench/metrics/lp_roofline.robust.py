"""Share, in %, of the LP program's device time that the work it needs
would take at the chip's peaks (``bench.lp_work``): the traced robust
plans' bytes and operations, 16 lanes a dispatch, over the device
seconds of the programs named ``_pdhg_run_many_tol`` in the trace."""

from bench.lp_work import roofline_pct

PROGRAM = "_pdhg_run_many_tol"


def read(record):
    if record.trace is None or not record.peaks:
        return None
    nbytes = sum(record.samples.get("lp_bytes_traced", ()))
    flops = sum(record.samples.get("lp_flops_traced", ()))
    return roofline_pct(nbytes, flops, record.trace.module_seconds(PROGRAM),
                        record.peaks)

"""Least time of one PIPECG iteration on one chip / device time per call
of the sweep kernel (%).  The least time is the larger of the
iteration's bytes over the HBM peak and its operations over the compute
peak, both from the operator's shapes (``lib/accounting.py``) and
``peaks.json``."""
from lib import accounting, trace as tr


def read(run):
    if run.kind != "solve" or run.trace is None:
        return None
    us = tr.mean_call_us(run.trace, run.sweep)
    if not us:
        return None
    least, _ = accounting.least_seconds(run.cost, run.peaks)
    return 100.0 * least * 1e6 / us

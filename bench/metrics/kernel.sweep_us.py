"""Device time per call of the PIPECG sweep kernel (us), mean over chips."""
from lib import trace as tr


def read(run):
    if run.kind != "solve" or run.trace is None:
        return None
    return tr.mean_call_us(run.trace, run.sweep)

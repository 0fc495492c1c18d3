"""Collective (all-reduce, collective-permute) device time during which
no other op runs on the chip, per scan step (us), mean over chips.
Nothing to read on one chip."""
from lib import trace as tr


def read(run):
    t = run.trace
    if run.kind != "solve" or t is None or len(t.chips) < 2:
        return None
    ns = sum(tr.exposed_collective_ns(t, c) for c in t.chips) / len(t.chips)
    return ns * 1e-3 / run.steps

"""Iterations the solves needed / scan steps they ran (%): the share of
the fixed ``maxiter`` loop that does useful work (solver's own count)."""


def read(run):
    if run.kind != "solve" or not run.iters:
        return None
    return 100.0 * sum(run.iters) / (len(run.iters) * run.maxiter)

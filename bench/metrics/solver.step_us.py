"""Device time of the traced solves / their scan steps (us), mean over
chips.  Every solve runs ``maxiter`` scan steps, converged or not."""


def read(run):
    if run.kind != "solve" or run.trace is None or not run.steps:
        return None
    return run.trace.mean_busy_s() / run.steps * 1e6

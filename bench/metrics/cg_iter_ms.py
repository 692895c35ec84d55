"""cg_iter_ms: window seconds over the CG iterations completed in it, as
counted by cg_solve's callback (host clock)."""


def read(run):
    w = run.window
    if run.window.counts != "cg_iteration" or not w.completed:
        return None
    return w.seconds / w.completed * 1e3

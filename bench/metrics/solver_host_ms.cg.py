"""solver_host_ms.cg: per CG iteration, the window's wall minus the time
spent inside the spmv callable (host clock): cg_solve's own host work."""


def read(run):
    w = run.window
    if run.window.counts != "cg_iteration" or not w.completed:
        return None
    return (w.seconds - w.spmv_s) / w.completed * 1e3

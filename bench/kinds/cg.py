"""Traffic kind ``cg``: back-to-back sets of ``maxiter`` unpreconditioned
conjugate-gradient iterations (``repro.amg.solve.cg_solve`` with
``spmv=op``, tolerance ``tol``) from x0 = 0 on ``b = A @ x*``, ``x*``
uniform(0, 2) from the seed.  A set that is running when the window
closes runs to its end and is compared; its later iterations are not
counted.  The window counts CG iterations."""
from __future__ import annotations

import time
import traceback

import numpy as np

from bench.drive import Window

KEYS = {"maxiter": int, "tol": float}
LIMITS = ("cg_x_err",)
COUNTS = "cg_iteration"


def view(mix: dict, indptr, indices, data, shape):
    return indptr, indices, data, shape


def target(mix: dict, op):
    return op


def make_inputs(mix: dict, matrix, seed: int) -> dict:
    """``b = A @ x*`` in float64."""
    import scipy.sparse as sp
    indptr, indices, data, shape = matrix
    x_star = np.random.default_rng([seed, 2]).uniform(0.0, 2.0, shape[1])
    return {"b": sp.csr_matrix((data, indices, indptr), shape=shape) @ x_star}


def warm_up(mix: dict, op, inputs: dict) -> None:
    from repro.amg.solve import cg_solve
    cg_solve(None, inputs["b"], tol=0.0, maxiter=2, spmv=op)


def run_window(mix: dict, op, inputs: dict, seconds: float, seed: int,
               span) -> Window:
    from repro.amg.solve import cg_solve
    w = Window(counts=COUNTS)
    t0 = time.perf_counter()
    deadline = t0 + seconds
    window = span("bench.window")
    window.__enter__()
    state = {"open": True, "last": t0}

    def spmv(v):
        with span("bench.spmv"):
            s = time.perf_counter()
            y = op(v)
            if state["open"]:
                w.spmv_s += time.perf_counter() - s
        return y

    def count(it, x):
        if not state["open"]:
            return
        t = time.perf_counter()
        w.completed += 1
        w.latencies.append(t - state["last"])
        state["last"] = t
        if t >= deadline:
            state["open"] = False
            w.seconds = t - t0
            window.__exit__(None, None, None)

    while state["open"]:
        w.attempted += 1
        state["last"] = time.perf_counter()
        try:
            with span("bench.cg_set"):
                x, iters, _ = cg_solve(None, inputs["b"], tol=mix["tol"],
                                       maxiter=mix["maxiter"],
                                       spmv=spmv, callback=count)
        except Exception:
            w.failed += 1
            w.errors.append(traceback.format_exc())
            if w.failed >= 3:
                break
            continue
        w.answers.append((iters, x))
        if state["open"] and time.perf_counter() >= deadline:
            break                     # a set that counted no iteration
    if state["open"]:
        state["open"] = False
        w.seconds = time.perf_counter() - t0
        window.__exit__(None, None, None)
    return w


def compare(mix: dict, ref, inputs: dict, w: Window) -> list:
    from bench.reference import cg_error
    maxiter = mix["maxiter"]
    x_ref = ref.cg(inputs["b"], maxiter)
    short = sum(1 for iters, _ in w.answers if iters != maxiter)
    return [{"name": "cg_x_err", "limit": mix["limits"]["cg_x_err"],
             "value": cg_error(x_ref, [x for _, x in w.answers])},
            {"name": "cg_sets_short", "limit": 0, "value": short},
            {"name": "cg_sets_compared", "limit": 1,
             "value": len(w.answers), "at_least": True}]

"""Traffic kind ``apply``: a closed loop of ``op @ x`` (or ``op.T @ x``
with ``"direction": "transpose"``), one client, host numpy in and host
numpy out, cycling through ``operands`` float32 operands of ``nv``
columns drawn from the seed.  Every apply whose index the seed's sample
mask marks (one in ``sample_every``) keeps its answer for the comparison
after the window.  The window counts applies."""
from __future__ import annotations

import time
import traceback

import numpy as np

from bench.drive import Window

KEYS = {"direction": ("forward", "transpose"), "nv": int, "operands": int,
        "sample_every": int}
LIMITS = ("spmv_max_err",)
COUNTS = "apply"
SAMPLE_MASK_LEN = 1 << 16


def view(mix: dict, indptr, indices, data, shape):
    """CSR arrays of the matrix the mix applies: ``A``, or ``A.T``."""
    if mix["direction"] != "transpose":
        return indptr, indices, data, shape
    import scipy.sparse as sp
    at = sp.csr_matrix((data, indices, indptr), shape=shape).T.tocsr()
    return at.indptr, at.indices, at.data, at.shape


def target(mix: dict, op):
    """What the window calls: the operator or its transpose."""
    return op.T if mix["direction"] == "transpose" else op


def make_inputs(mix: dict, matrix, seed: int) -> dict:
    """``operands`` float32 operands for ``matrix`` = (indptr, indices,
    data, shape) of the matrix the mix applies."""
    n = matrix[3][1]
    rng = np.random.default_rng([seed, 2])
    nv = mix["nv"]
    shape = (n,) if nv == 1 else (n, nv)
    return {"operands": [rng.standard_normal(shape).astype(np.float32)
                         for _ in range(mix["operands"])]}


def warm_up(mix: dict, op, inputs: dict) -> None:
    for x in inputs["operands"]:
        op(x)


def run_window(mix: dict, op, inputs: dict, seconds: float, seed: int,
               span) -> Window:
    operands = inputs["operands"]
    keep = (np.random.default_rng([seed, 3]).random(SAMPLE_MASK_LEN)
            < 1.0 / mix["sample_every"])
    w = Window(counts=COUNTS)
    last = None
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("bench.window"):
        while True:
            s = time.perf_counter()
            if s >= deadline:
                break
            k = w.attempted % len(operands)
            w.attempted += 1
            try:
                with span("bench.apply"):
                    y = op(operands[k])
            except Exception:
                w.failed += 1
                w.errors.append(traceback.format_exc())
                continue
            e = time.perf_counter()
            w.latencies.append(e - s)
            w.completed += 1
            w.seconds = e - t0
            last = (k, y)
            if keep[(w.attempted - 1) % SAMPLE_MASK_LEN]:
                w.answers.append(last)
    if not w.answers and last is not None:
        w.answers.append(last)
    return w


def compare(mix: dict, ref, inputs: dict, w: Window) -> list:
    from bench.reference import spmv_error
    return [{"name": "spmv_max_err", "limit": mix["limits"]["spmv_max_err"],
             "value": spmv_error(ref, inputs["operands"], w.answers)},
            {"name": "answers_compared", "limit": 1,
             "value": len(w.answers), "at_least": True}]

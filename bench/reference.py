"""The plain reference and the comparisons that decide ``correct``.

The reference is a float64 CSR product (``scipy.sparse``) and a textbook
float64 conjugate-gradient loop over it.  It imports nothing of the
program and is built from the benchmark's own matrix arrays, never from
anything the program made.

The control is the same reference computed one precision below the
configuration's float32: matrix values and operand rounded to bfloat16,
products summed in float32.  It stands where the program stood and has
to come out as not correct (see ``bench/readings.py``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np
import scipy.sparse as sp


class Reference:
    """float64 ``A`` and ``|A|`` over the benchmark's matrix arrays."""

    def __init__(self, indptr, indices, data, shape):
        self.a = sp.csr_matrix((np.asarray(data, np.float64), indices,
                                indptr), shape=shape)
        self.abs_a = abs(self.a)

    def apply(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``(A @ x, |A| @ |x|)`` in float64."""
        x = np.asarray(x, np.float64)
        return self.a @ x, self.abs_a @ np.abs(x)

    def cg(self, b: np.ndarray, maxiter: int) -> np.ndarray:
        """``maxiter`` iterations of unpreconditioned CG from x0 = 0."""
        b = np.asarray(b, np.float64)
        x = np.zeros_like(b)
        r = b.copy()
        p = r.copy()
        rr = float(r @ r)
        for _ in range(maxiter):
            ap = self.a @ p
            alpha = rr / float(p @ ap)
            x += alpha * p
            r -= alpha * ap
            rr_new = float(r @ r)
            p = r + (rr_new / rr) * p
            rr = rr_new
        return x


class Bf16Control:
    """The reference in the program's place, one precision down: values
    and operand rounded to bfloat16, products summed in float32."""

    def __init__(self, indptr, indices, data, shape):
        import ml_dtypes
        self._bf16 = ml_dtypes.bfloat16
        vals = np.asarray(data, np.float32).astype(self._bf16)
        self.a = sp.csr_matrix((vals.astype(np.float32), indices, indptr),
                               shape=shape)

    def __call__(self, x) -> np.ndarray:
        x = np.asarray(x, np.float32).astype(self._bf16).astype(np.float32)
        return self.a @ x


def spmv_error(ref: Reference, operands: Sequence[np.ndarray],
               answers: List[Tuple[int, np.ndarray]]) -> float:
    """Largest ``|y - A x| / (|A| |x|)`` over every answer compared;
    ``answers`` holds (operand index, y).  inf when an answer has the
    wrong shape or is not finite."""
    refs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    worst = 0.0
    for k, y in answers:
        if k not in refs:
            refs[k] = ref.apply(operands[k])
        y_ref, mag = refs[k]
        y = np.asarray(y, np.float64)
        if y.shape != y_ref.shape or not np.all(np.isfinite(y)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(y - y_ref) / (mag + 1e-30))))
    return worst


def cg_error(x_ref: np.ndarray, answers: Sequence[np.ndarray]) -> float:
    """Largest ``||x - x_ref|| / ||x_ref||`` over the solves compared."""
    worst = 0.0
    den = float(np.linalg.norm(x_ref))
    for x in answers:
        x = np.asarray(x, np.float64)
        if x.shape != x_ref.shape or not np.all(np.isfinite(x)):
            return float("inf")
        worst = max(worst, float(np.linalg.norm(x - x_ref)) / den)
    return worst

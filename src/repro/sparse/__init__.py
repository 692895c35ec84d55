from repro.sparse.csr import CSR
from repro.sparse.bsr import BSR
from repro.sparse.ell import ELL, stack_ell
from repro.sparse.generators import (graph500_kronecker, linear_elasticity_2d,
                                     pagerank_matrix, poisson_2d,
                                     random_fixed_nnz, rotated_anisotropic_2d)

__all__ = ["CSR", "BSR", "ELL", "stack_ell", "graph500_kronecker",
           "linear_elasticity_2d", "pagerank_matrix", "poisson_2d",
           "random_fixed_nnz", "rotated_anisotropic_2d"]

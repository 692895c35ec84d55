from repro.amg.hierarchy import Level, smoothed_aggregation_hierarchy
from repro.amg.matmul import csr_matmul
from repro.amg.solve import (LevelOperators, amg_vcycle, bicgstab_solve,
                             cg_solve, level_operators, pagerank)

__all__ = ["Level", "LevelOperators", "smoothed_aggregation_hierarchy",
           "csr_matmul", "amg_vcycle", "bicgstab_solve", "cg_solve",
           "level_operators", "pagerank"]

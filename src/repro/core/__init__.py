"""repro.core — the paper's contribution.

Communication-avoiding k-step reformulations of stochastic proximal methods,
per Soori et al., "Avoiding Communication in Proximal Methods for Convex
Optimization Problems" (2017), all instantiations of one shared s-step core
(``repro.core.sstep``): sample T index sets, regroup into T/k blocks, one
collective per block, k communication-free updates. Classical solvers are the
k=1 instantiation of the same code path.

Solver family (classical / CA pairs):
    sfista  / ca_sfista   stochastic FISTA           (paper Alg. I / III)
    spnm    / ca_spnm     stochastic proximal Newton (paper Alg. II / IV)
    pdhg    / ca_pdhg     stochastic primal-dual hybrid gradient (1612.04003)
    bcd     / ca_bcd      proximal block coordinate descent      (1612.04003)

Problems (any solver x any problem; BCD runs the dual SVM CoCoA-style):
    LassoProblem, ElasticNetProblem, DualSVMProblem

Public API:
    SolverConfig                        shared solver configuration
    soft_threshold, prox_elem           element-wise proximal operators
    sample_columns, sample_index_batch  randomized sampling machinery
    sampled_gram, gram_blocks           Gram-matrix machinery
    make_distributed_solver             shard_map-distributed variants
    CostModel                           alpha-beta-gamma cost model (Table I)
    solve_reference, composite_reference, relative_solution_error
"""
from repro.core.problem import (LassoProblem, ElasticNetProblem,
                                DualSVMProblem, SolverConfig, lasso_objective)
from repro.core.soft_threshold import soft_threshold, prox_elem
from repro.core.sampling import sample_columns, sample_index_batch
from repro.core.gram import sampled_gram, gram_blocks
from repro.core.fista import sfista, fista_reference
from repro.core.pnm import spnm
from repro.core.ca_fista import ca_sfista
from repro.core.ca_pnm import ca_spnm
from repro.core.pdhg import pdhg, ca_pdhg
from repro.core.bcd import bcd, ca_bcd
from repro.core.distributed import make_distributed_solver
from repro.core.cost_model import CostModel, MachineParams
from repro.core.convergence import (relative_solution_error, solve_reference,
                                    composite_reference, lasso_reference_f64)

__all__ = [
    "LassoProblem", "ElasticNetProblem", "DualSVMProblem", "SolverConfig",
    "lasso_objective", "soft_threshold", "prox_elem",
    "sample_columns", "sample_index_batch", "sampled_gram", "gram_blocks",
    "sfista", "fista_reference", "spnm", "ca_sfista", "ca_spnm",
    "pdhg", "ca_pdhg", "bcd", "ca_bcd",
    "make_distributed_solver", "CostModel", "MachineParams",
    "relative_solution_error", "solve_reference", "composite_reference",
    "lasso_reference_f64",
]

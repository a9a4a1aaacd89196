"""Public wrapper for csr_spmv: the XLA segment-sum path by default.

``use_pallas=True`` compiles the packed Pallas kernel (TPU only; the TPU
compiler currently rejects it, see ``engine.backends.pr_path``) and
``interpret=True`` runs that kernel in the Pallas interpreter instead.
Neither is chosen from the platform.
"""
from __future__ import annotations

import numpy as np

from .csr_spmv import csr_spmv_pallas, pack_edges
from .ref import csr_spmv_ref


class SpMV:
    """Pre-packed SpMV operator bound to one graph (in-CSR)."""

    def __init__(self, t_indptr, t_indices, weights=None, *,
                 use_pallas: bool = False, interpret: bool = False):
        self.t_indptr = np.asarray(t_indptr)
        self.t_indices = np.asarray(t_indices)
        self.weights = weights
        self.use_pallas = use_pallas
        self.interpret = interpret
        if self.use_pallas:
            (self.src, self.dst_local, self.val, self.bpt, self.ntiles,
             self.n_pad) = pack_edges(self.t_indptr, self.t_indices, weights)

    def __call__(self, x):
        if self.use_pallas:
            return csr_spmv_pallas(
                self.src, self.dst_local, self.val, x,
                blocks_per_tile=self.bpt, num_tiles=self.ntiles,
                n_pad=self.n_pad, interpret=self.interpret)
        w = (np.ones(len(self.t_indices), np.float32)
             if self.weights is None else self.weights)
        return csr_spmv_ref(self.t_indptr, self.t_indices, w, x)

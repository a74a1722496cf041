"""Core library: the paper's contribution (distributed Strassen matmul).

Public API:
  coefficients  — Strassen/Winograd/naive8 schemes as constant matrices
  strassen      — serial recursive + batched-BFS Strassen
  distributed   — the mesh route: row-sharded Strassen with the fused leaf
  backend       — pluggable matmul routing used by all model layers; the one
                  table from a routing kind to the code that runs it
  autotune      — the calibrated cost model behind kind='auto'
  cost_model    — the paper's §IV stage-wise analytical cost model
"""
from repro.core.coefficients import STRASSEN, WINOGRAD, NAIVE8, Scheme, get_scheme
from repro.core.strassen import (
    strassen_matmul,
    strassen_recursive,
    divide_level,
    combine_level,
    split_quadrants,
    merge_quadrants,
    leaf_count,
)
from repro.core.backend import MatmulBackend, matmul, NAIVE_BACKEND, AUTO_BACKEND
# NOTE: the autotune *functions* stay namespaced (repro.core.autotune.autotune)
# so the submodule attribute isn't shadowed; only the data types re-export.
from repro.core.autotune import Calibration, Candidate, Decision, TuningCache

__all__ = [
    "STRASSEN",
    "WINOGRAD",
    "NAIVE8",
    "Scheme",
    "get_scheme",
    "strassen_matmul",
    "strassen_recursive",
    "divide_level",
    "combine_level",
    "split_quadrants",
    "merge_quadrants",
    "leaf_count",
    "MatmulBackend",
    "matmul",
    "NAIVE_BACKEND",
    "AUTO_BACKEND",
    "Calibration",
    "Candidate",
    "Decision",
    "TuningCache",
]

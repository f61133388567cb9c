"""Shipped task-graph algorithms (the DPLASMA-analog layer).

PTG taskpools for dense tiled linear algebra plus DTD builders — the
workloads the reference ecosystem runs on PaRSEC (dpotrf/dgemm-style) and
the BASELINE.md benchmark configs.
"""

from .potrf import build_potrf, insert_potrf_dtd
from .gemm import build_gemm_ptg, insert_gemm_dtd
from .geqrf import build_geqrf, geqrf_flops
from .getrf import (build_getrf, build_getrf_1d, build_getrf_incpiv,
                    build_getrf_left, getrf_flops)
from .stencil import build_stencil_1d

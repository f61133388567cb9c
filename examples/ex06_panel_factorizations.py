"""Ex06: the panel-fused dense factorization trio (POTRF/GEQRF/GETRF).

The flagship execution path: a left-looking taskpool (CTL-gather fan-in
concentrating each tile's updates) lowered by the PanelExecutor onto
Aᵀ-dense storage, so every trailing update is one or two large MXU
matmuls. Run with JAX_PLATFORMS=cpu for a quick local check or on a TPU
for real throughput.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from parsec_tpu.algorithms.geqrf import build_geqrf_hh
from parsec_tpu.algorithms.getrf import build_getrf_left
from parsec_tpu.algorithms.potrf import build_potrf_left
from parsec_tpu.compiled.panels import PanelExecutor
from parsec_tpu.compiled.wavefront import plan_taskpool
from parsec_tpu.data import TiledMatrix
from parsec_tpu.utils import mca_param

# Compile-once serving: the jit.cache_dir knob auto-enables the
# persistent compile caches (XLA cache + serialized executors, under
# JAX_COMPILATION_CACHE_DIR where set, else the checkout's .xla_cache)
# — re-running this example pays zero XLA compiles for the
# already-served shapes. PARSEC_COMPILE_CACHE=0 disables both layers.
mca_param.set("jit.cache_dir", "auto")


def main():
    rng = np.random.default_rng(0)
    n, nb = 256, 64

    # POTRF: SPD input, result is L (lower) with Lᵀ scribble above.
    # segmented=True uses the compile-once serving path (bucketed
    # per-wave kernels, reused across N and across processes); the
    # default whole-DAG form is the fastest steady-state runtime.
    M = rng.standard_normal((n, n))
    spd = (M @ M.T + n * np.eye(n)).astype(np.float32)
    A = TiledMatrix.from_array(spd.copy(), nb, nb, name="A")
    PanelExecutor(plan_taskpool(build_potrf_left(A))).run(segmented=True)
    L = np.tril(A.to_array().astype(np.float64))
    print("potrf  residual:",
          np.linalg.norm(L @ L.T - spd) / np.linalg.norm(spd))

    # GEQRF: any matrix, result is R (upper) + zeros below
    G = rng.standard_normal((n, n)).astype(np.float32)
    B = TiledMatrix.from_array(G.copy(), nb, nb, name="B")
    PanelExecutor(plan_taskpool(build_geqrf_hh(B))).run()
    R = B.to_array().astype(np.float64)
    GtG = G.astype(np.float64).T @ G
    print("geqrf  residual:",
          np.linalg.norm(R.T @ R - GtG) / np.linalg.norm(GtG))

    # GETRF: diagonally dominant (no-pivot contract), packed L\\U result
    D = (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)
    C = TiledMatrix.from_array(D.copy(), nb, nb, name="C")
    PanelExecutor(plan_taskpool(build_getrf_left(C))).run()
    P = C.to_array().astype(np.float64)
    Lu = np.tril(P, -1) + np.eye(n)
    U = np.triu(P)
    print("getrf  residual:",
          np.linalg.norm(Lu @ U - D) / np.linalg.norm(D))


if __name__ == "__main__":
    main()

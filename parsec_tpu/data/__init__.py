"""Data layer: collections, tiled matrices, distributions.

Reference: parsec_data_t + per-device copies (data_internal.h:35-81),
data collections with user-supplied rank_of/vpid_of/data_of vtable
(include/parsec/data_distribution.h:26-100), tiled-matrix descriptors and
2D-block-cyclic distributions (data_dist/matrix/).

TPU-first divergence: a tile's device residency is not a coherency state
machine over explicit copies — tile values are immutable ``jax.Array``s
(HBM-resident) or numpy arrays (host); "coherency" reduces to which value
version a consumer was linked to, which the dataflow core guarantees.
The :class:`~parsec_tpu.data.matrix.TiledMatrix` additionally supports a
*stacked* device representation (ntiles × mb × nb as one jax.Array) used by
the batched/compiled execution path.
"""

from .collection import DataCollection, LocalCollection
from .matrix import (TiledMatrix, TwoDimBlockCyclic, SymTwoDimBlockCyclic,
                     TwoDimTabular, TwoDimBandCyclic, OneDimCyclic,
                     SubtileView, advise_on_devices)
from .data import Data, DataCopy, CoherencyState
from .arena import Arena, ArenaDatatype, ArenaRegistry
from .redistribute import build_redistribute_ptg, insert_redistribute_dtd
from .checkpoint import CheckpointManager
from .recovery import (RecoveryError, RecoveryPlan, plan_recovery,
                       build_replay_taskpool, materialize_shadow,
                       checkpoint_shadow_source, adopt_shard,
                       remap_collection_ranks, shrink_remap,
                       exchange_completed, replay_lost_work)
from .matrix_ops import (build_apply, build_broadcast, build_map_operator,
                         build_reduce)

"""Multi-tenant serving runtime (ROADMAP item 4).

PaRSEC assumes one application driving one context; this package turns a
persistent :class:`~parsec_tpu.core.context.Context` into a shared
service: many client threads submit taskpools concurrently through
``Context.submit`` while the runtime enforces per-tenant admission
windows with backpressure (grown from the PR 3 DTD insertion throttle),
weighted-fair selection across live taskpools (``sched=wfq``),
per-submission deadlines with cancellation, tenant quarantine on
failure (poison bodies, lint-gate refusals, rank death), and open-loop
load shedding under overload — so no tenant can wedge, starve, or crash
another.

The proving workload is the Orca-style continuous-batching transformer
decode loop in :mod:`.decode` (KV cache as a tiled collection under the
HBM budget manager, per-request decode steps as DTD insertions);
``tests/test_serving.py`` drives admission, fairness, deadlines,
quarantine and shedding through it, and
``tests/test_serving_isolation.py`` the rank death beside a rank-local
sibling (its mesh-scoped tenant is :mod:`.serving_bench`).

The KV state layer (:mod:`.kv`, ROADMAP item 3 / ISSUE 15) adds the
cross-request state plane: paged KV allocation (page-granular
refcounts, COW, eviction), a radix prefix cache so requests sharing a
prompt prefix share immutable pages, chunked prefill on the wfq
prefill lane, and speculative decode as a cancellable draft-branch DTD
pattern (:mod:`.spec`); ``tests/test_serving_kv.py`` checks every
shared-prefix request bitwise against the no-sharing replay.
"""

from .runtime import (AdmissionRejected, DeadlineExceeded, ServingRuntime,
                      Submission, Tenant, TenantQuarantined, enable)
from .elastic import (AutoscalePolicy, ElasticController, ElasticWorker,
                      Signals)
from .kv import (KVPagePool, KVPagesExhausted, KVStateLayer, RadixTree,
                 layer_for)

__all__ = ["AdmissionRejected", "DeadlineExceeded", "ServingRuntime",
           "Submission", "Tenant", "TenantQuarantined", "enable",
           "AutoscalePolicy", "ElasticController", "ElasticWorker",
           "Signals", "KVPagePool", "KVPagesExhausted", "KVStateLayer",
           "RadixTree", "layer_for"]

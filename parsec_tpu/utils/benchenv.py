"""Shared comm-knob preamble of the multi-rank wire benches.

Every socket-mesh bench rank (serving, elastic, recovery, bcast, and
the pingpong latency harness) used to copy-paste the same three
``mca_param.set`` lines; each new comm knob then needed seven edits —
and round 11 shipped with one of the seven drifted. This helper is the
ONE pin point: host-payload wire benches measure the WIRE, so every
knob that could route payloads through an accelerator is pinned off,
including the device-plane knobs added after the copy-paste spread
(``comm.device_pipeline`` / ``comm.device_direct``).

``tpu_off=True`` (the default: a host-only rank) disables the device
module AND puts the process on the CPU platform, so that no incidental
``jnp`` call or backend question in the rank can initialise libtpu and
claim a chip that belongs to another process. ``tpu_off=False`` leaves
both alone (the device-payload pingpong ranks own a chip each);
``overrides`` lets a bench turn individual knobs back on (e.g. the
device-plane A/B arms) or pin extra ones — overrides are applied LAST,
so they always win over the defaults pinned here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import mca_param
from .jax_platform import pin_cpu_platform


def pin_wire_bench_env(tpu_off: bool = True,
                       overrides: Optional[Dict[str, Any]] = None
                       ) -> None:
    """Pin the wire-bench comm environment in THIS process (bench rank
    processes call it right after import, before building engines)."""
    pins: Dict[str, Any] = {
        # no stage-through collection reads, no receive staging: host
        # payload rows measure the wire, not the accelerator
        "runtime.stage_reads": "0",
        "comm.stage_recv": "0",
        # device data plane off by default for host-payload benches —
        # the knobs only act on device arrays, but pinning them keeps
        # every bench deterministic under future auto-default changes
        "comm.device_pipeline": "0",
        "comm.device_direct": "0",
    }
    if tpu_off:
        # the rank fleet must never touch (or contend for) an
        # exclusive-access chip
        pins["device.tpu.enabled"] = False
        pin_cpu_platform()
    pins.update(overrides or {})
    for key, val in pins.items():
        mca_param.set(key, val)

"""Device base class and registry (reference parsec/mca/device/device.c)."""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..core.task import Chore, DeviceType, HookReturn, Task
from ..utils import mca_param
from ..utils.debug import debug_verbose

mca_param.register("device.tpu.enabled", True, help="register the TPU device")
mca_param.register("device.tpu.max_devices", 0,
                   help="cap on per-chip TPU modules (0 = all visible)")


class Device:
    """A device module (parsec_device_module_t analog)."""

    device_type = DeviceType.NONE
    name = "device"

    def __init__(self) -> None:
        self.index = -1
        self.registry: Optional["Registry"] = None
        # statistics (reference device.h:132-141 per-device counters)
        self.stats = {"tasks": 0, "bytes_in": 0, "bytes_out": 0,
                      # by task class name, counted while the context's
                      # stage timers are on: a launch is a lone task or
                      # a group, so one class's tasks per launch can be
                      # read apart from another's
                      "tasks_by_class": {}, "launches_by_class": {}}
        # relative throughput weight for load balancing
        # (reference: GFLOPS weights, device_cuda_module.c:53-117)
        self.weight = 1.0
        self.load = 0.0
        self._lock = threading.Lock()
        # extensible per-device info slots (parsec_per_device_infos)
        from ..utils.info import InfoArray, per_device_infos
        self.infos = InfoArray(per_device_infos, self)

    def attach(self, registry: "Registry", index: int) -> None:
        self.registry = registry
        self.index = index

    def execute(self, es, task: Task, chore: Chore) -> HookReturn:
        raise NotImplementedError

    def group_limit(self, task: Task, chore=None) -> int:
        """The most tasks like ``task`` one launch of ``chore`` on this
        module may carry; 0 for a module that launches every task alone
        (then it needs no ``execute_group``, which returns ``(tasks
        launched, bytes of new outputs the launch holds)``, or
        ``group_turn``)."""
        return 0

    def shutdown(self) -> None:
        """Stop any device-owned threads (called from Context.fini);
        base devices have none."""

    def drop_copies(self, pool) -> None:
        """Let go of what the module keeps for ``pool``'s tasks of
        tiles that lie on other chips (called as a pool ends); a module
        that keeps none has nothing to do."""

    def add_load(self, units: float) -> None:
        """In-flight units beyond the one ``Registry.device_for`` added:
        a group launch accounts every member."""
        with self._lock:
            self.load += units

    def release_load(self, units: float = 1.0) -> None:
        """Release in-flight work units (``Registry.device_for`` adds
        one). The context releases them when ``execute`` returns anything
        but ASYNC; an async device owns its unit until it completes the
        task and MUST call this then."""
        with self._lock:
            self.load = max(0.0, self.load - units)

    def _run_hook(self, task: Task, chore: Chore) -> HookReturn:
        """Run the functional body and normalize outputs into
        ``task.output`` keyed by output-flow name."""
        from ..core.task import normalize_outputs
        inputs = task.input_values()
        result = chore.hook(task, *inputs)
        # the task object itself as the label: it is only ever
        # formatted inside the error branches (no per-task repr cost)
        outs = normalize_outputs(
            result, [f.name for f in task.task_class.output_flows],
            task)
        task.output.update(outs)
        with self._lock:
            self.stats["tasks"] += 1
            if task.taskpool.context.stage_timers:
                self._count_launch(task, 1)
        return HookReturn.DONE

    def _count_launch(self, task: Task, tasks: int) -> None:
        """One launch of ``tasks`` tasks of ``task``'s class (under
        ``self._lock``)."""
        name = task.task_class.name
        for key, n in (("tasks_by_class", tasks), ("launches_by_class", 1)):
            counts = self.stats[key]
            counts[name] = counts.get(name, 0) + n

    def dump_statistics(self) -> Dict:
        with self._lock:
            return dict(self.stats, name=self.name, index=self.index,
                        tasks_by_class=dict(self.stats["tasks_by_class"]),
                        launches_by_class=dict(
                            self.stats["launches_by_class"]))


class Registry:
    """Device registry (parsec_mca_device_* analog)."""

    def __init__(self, context) -> None:
        from .cpu import CPUDevice
        from .recursive import RecursiveDevice
        self.context = context
        self.devices: List[Device] = []
        # the chip modules in the order they were registered: what a
        # collection's advice indexes (``preferred``); and how often a
        # task's written tile was looked up for it, which never happens
        # with one chip
        self.chips: List[Device] = []
        self.advice_lookups = 0
        self.add(CPUDevice())
        self.add(RecursiveDevice())
        if mca_param.get("device.tpu.enabled", True):
            # one module per visible chip (reference: per-GPU module
            # instances, device_cuda_module.c:326) so device_for can
            # load-balance across them by load x weight. Discovery
            # failing RAISES: a context that quietly went CPU-only is
            # how a missing chip gets benchmarked under a chip's name.
            import jax
            from .tpu import TPUDevice
            from ..utils.jax_platform import cpu_requested
            limit = int(mca_param.get("device.tpu.max_devices", 0))
            devs = jax.devices()
            if devs[0].platform == "cpu" and not cpu_requested():
                raise RuntimeError(
                    "no accelerator found: JAX fell back to the CPU "
                    "platform. Start the run with JAX_PLATFORMS=cpu to "
                    "use CPU device modules, or set device.tpu.enabled=0 "
                    "for a context without device modules")
            # a registered comm mesh DECLARES which chip each comm rank
            # computes on (compiled.spmd.register_comm_mesh): that
            # rank's context gets that chip's module and no other, or
            # load balancing would pull its tiles back to chip 0
            from ..compiled.spmd import comm_mesh_device
            mine = comm_mesh_device(context.my_rank) \
                if context.comm is not None else None
            if mine is not None:
                devs = [mine]
            elif limit > 0:
                devs = devs[:limit]
            added = [self.add(TPUDevice(jd)) for jd in devs]
            if any(d.platform != "cpu" for d in added):
                # a REAL accelerator is registered: the CPU device's
                # eager jnp ops would dispatch op-by-op to the same
                # chip — make it a last resort, not a load-balancing
                # peer (reference: the GFLOPS weight table keeps CPU
                # cores ~100x below GPUs, device_cuda_module.c:53)
                self.devices[0].weight = 0.01

    def add(self, dev: Device) -> Device:
        dev.attach(self, len(self.devices))
        self.devices.append(dev)
        if dev.device_type == DeviceType.TPU:
            self.chips.append(dev)
        debug_verbose(4, "device", "registered device %d: %s",
                      dev.index, dev.name)
        return dev

    def preferred(self, task: Task) -> Optional[Device]:
        """The chip module ``task`` belongs on: the one the tile it
        writes (``TaskClass.written_tile``: a PTG class's first written
        flow, a DTD task's affinity or first written argument) is advised
        to (``DataCollection.device_advice``, an index among ``chips``),
        or None where the task names no such tile or nobody advised its
        collection. Asked only where several chip modules are
        registered."""
        self.advice_lookups += 1
        where = task.task_class.written_tile(task)
        if where is None:
            return None
        # any object with data_of/write_tile serves as a collection
        advice = getattr(where[0], "device_advice", None)
        if advice is None:
            return None
        return self.chips[advice(where[1]) % len(self.chips)]

    def device_for(self, device_type: DeviceType,
                   task: Task) -> Optional[Device]:
        """parsec_get_best_device analog. With several chip modules
        registered, an accelerator body runs on the module that the tile
        its task WRITES is advised to (``preferred``; upstream asks the
        preferred device of the written data first too): the tile then
        never leaves its chip, and the task updates it where it lies.
        Else, and always with one chip module (one test, no look-up):
        among the devices matching the chore's type, the least
        (load + 1) / weight; ties go to the heavier device (idle
        accelerator beats idle CPU). The recursive pseudo-device is
        never auto-selected — only chores that name it explicitly use it
        (reference: PARSEC_DEV_RECURSIVE is special-cased in the core, not
        part of load balancing)."""
        if len(self.chips) > 1 and device_type & DeviceType.TPU:
            best = self.preferred(task)
            if best is not None:
                with best._lock:
                    best.load += 1.0
                return best
        best, best_score = None, None
        for dev in self.devices:
            if not (dev.device_type & device_type):
                continue
            if dev.device_type == DeviceType.RECURSIVE and \
                    device_type != DeviceType.RECURSIVE:
                continue
            # (load+1)/weight, not load/weight: an IDLE low-weight
            # device must not win ties against an accelerator whose
            # manager holds queued work (a 0.01-weight CPU device then
            # only wins when the accelerator is ~10000 deep)
            score = (dev.load + 1.0) / dev.weight
            if best_score is None or score < best_score or \
                    (score == best_score and dev.weight > best.weight):
                best, best_score = dev, score
        if best is not None:
            with best._lock:
                best.load += 1.0       # in-flight unit; the context
        return best                    # releases it (see release_load)

    def by_type(self, device_type: DeviceType) -> List[Device]:
        return [d for d in self.devices if d.device_type & device_type]

    def dump_statistics(self) -> List[Dict]:
        """parsec_mca_device_dump_and_reset_statistics analog."""
        return [d.dump_statistics() for d in self.devices]

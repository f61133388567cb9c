"""Profiling, instrumentation and DAG capture.

Reference systems (SURVEY §2.7/§2.13):
- PINS callback chains on runtime events (parsec/mca/pins/pins.h:26-53).
- Binary trace with a dictionary of paired begin/end keys (profiling.c),
  converted offline to pandas tables — here :mod:`trace` records events
  in-memory and exports to records/JSON directly.
- DOT grapher of the executed DAG (parsec_prof_grapher.c).
"""

from . import pins
from .pins import PinsManager, PinsEvent
from . import pins_modules
from .pins_modules import TaskProfiler, PrintSteals, Alperf, \
    Counters, IteratorsChecker, StragglerWatchdog, new_module, \
    install_selected
from . import metrics
from .metrics import MetricsRegistry, registry as metrics_registry
from . import spans
from .trace import Trace
from .grapher import Grapher
from .ptg_to_dtd import replay_ptg_through_dtd
from .dictionary import PropertiesDictionary, install_runtime_properties
from .sde import SDERegistry, global_registry, install_runtime_counters
from .sim import SimReport, simulate

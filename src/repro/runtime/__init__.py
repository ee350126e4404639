from .fault_tolerance import RunState, StragglerMonitor, resilient_loop
from .timeline import (
    StepTimer,
    UnitProfile,
    make_unit_probes,
    probe_unit_times,
    time_group_comm,
)

__all__ = [
    "RunState",
    "StragglerMonitor",
    "resilient_loop",
    "StepTimer",
    "UnitProfile",
    "make_unit_probes",
    "probe_unit_times",
    "time_group_comm",
]

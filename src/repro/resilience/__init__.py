"""Fault tolerance for the streaming pipeline (ISSUE 10).

The production-scale north star needs runs that survive what production
throws at them: killed processes (checkpoint/resume — ``checkpoint``),
corrupt or flaky input (validation/quarantine — ``validate``), and a way
to prove both deterministically (fault injection — ``chaos``). Graceful
degradation in the hot paths lives with the code it guards
(``core/forceatlas2.FA2Config.nan_guard``, ``serve/tiles.TileEngine``).
"""
from repro.resilience.checkpoint import (  # noqa: F401
    CheckpointMismatchError,
    Preempted,
    StreamCheckpointer,
    config_fingerprint,
    latest_step,
    load_arrays,
    restore,
    restore_latest_valid,
    save,
)
from repro.resilience.chaos import (  # noqa: F401
    ChaosConfig,
    ChaosEdgeStore,
    KillSwitch,
    SimulatedPreemption,
    poison_weights,
)
from repro.resilience.validate import (  # noqa: F401
    ValidationAccounting,
    ValidationError,
    ValidationPolicy,
    validated_read,
)

__all__ = [
    "CheckpointMismatchError",
    "ChaosConfig",
    "ChaosEdgeStore",
    "KillSwitch",
    "Preempted",
    "SimulatedPreemption",
    "StreamCheckpointer",
    "ValidationAccounting",
    "ValidationError",
    "ValidationPolicy",
    "config_fingerprint",
    "latest_step",
    "load_arrays",
    "poison_weights",
    "restore",
    "restore_latest_valid",
    "save",
    "validated_read",
]

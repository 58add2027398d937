"""simx: vectorized, JAX-compiled simulation backend for datacenter sweeps.

A second simulation backend beside the event-driven one (``repro.core``):
the full Fig. 2 scheduler matrix — Megha and the Sparrow, Eagle, and
Pigeon baselines, plus the omniscient-oracle lower bound — reformulated as
fixed-timestep synchronous rounds over dense arrays, advanced under
``jax.lax.scan`` and ``vmap``-able over seeds/loads (``repro.simx.sweep``
compiles a whole (seed x load) grid into one program).  Every scheduler is
a ``Rule`` on the shared round-stage runtime (``repro.simx.runtime``);
select the backend via ``run_simulation(..., backend="simx")``.

Importing the package registers ``repro.simx.spans``' compile counter.
"""

from repro.simx import spans  # noqa: F401 — registers the compile counter

from repro.simx.engine import (
    SimxRun,
    estimate_rounds,
    run_to_completion,
    scan_rounds,
    simulate_workload,
)
from repro.simx.runtime import (
    RULES,
    Rule,
    compose_step,
    default_match_fn,
    job_delays_from_state,
    register_rule,
)
from repro.simx.faults import (
    FaultPlan,
    FaultSchedule,
    GmOutage,
    WorkerFailure,
    empty_schedule,
    fault_grid_schedule,
    is_empty,
    jobs_with_reservation,
)
from repro.simx.state import (
    CoreState,
    EagleState,
    MeghaState,
    OracleState,
    PigeonState,
    SimxConfig,
    SparrowState,
    TaskArrays,
    export_workload,
    init_eagle_state,
    init_megha_state,
    init_oracle_state,
    init_pigeon_state,
    init_sparrow_state,
)
from repro.simx.sweep import (
    fault_sweep_grid,
    fig2_sweep,
    fig4_sweep,
    point_summary,
    sweep_grid,
)
from repro.simx.telemetry import TelemetryConfig, Timeline

def __getattr__(name: str):
    """``SCHEDULERS`` stays a live view of the rule registry (see
    ``repro.simx.engine.__getattr__``)."""
    if name == "SCHEDULERS":
        from repro.simx import engine

        return engine.SCHEDULERS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "RULES",
    "Rule",
    "SCHEDULERS",
    "SimxRun",
    "SimxConfig",
    "TaskArrays",
    "CoreState",
    "EagleState",
    "FaultPlan",
    "FaultSchedule",
    "GmOutage",
    "MeghaState",
    "OracleState",
    "PigeonState",
    "SparrowState",
    "TelemetryConfig",
    "Timeline",
    "WorkerFailure",
    "compose_step",
    "default_match_fn",
    "empty_schedule",
    "estimate_rounds",
    "export_workload",
    "fault_grid_schedule",
    "fault_sweep_grid",
    "fig2_sweep",
    "fig4_sweep",
    "init_eagle_state",
    "init_megha_state",
    "init_oracle_state",
    "init_pigeon_state",
    "init_sparrow_state",
    "is_empty",
    "job_delays_from_state",
    "jobs_with_reservation",
    "point_summary",
    "register_rule",
    "run_to_completion",
    "scan_rounds",
    "simulate_workload",
    "sweep_grid",
]

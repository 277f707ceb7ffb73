"""Production traffic simulator (ISSUE 15 tentpole, half 2).

Declarative scenarios replayed against the REAL HTTP server with
seeded-deterministic arrival schedules; each scenario asserts its SLOs
through the server's own SLO plane (``GET /minio/admin/v3/slo``) and a
violated scenario pulls the retained trace store to attribute the
violation to the dominant span stage.  tests/test_simulator.py replays
short scenarios of every family.
"""

from .engine import ScenarioEngine, build_schedule, schedule_digest
from .scenarios import (Scenario, builtin_scenarios,
                        controller_scenarios, georep_scenarios)

__all__ = ["Scenario", "ScenarioEngine", "build_schedule",
           "builtin_scenarios", "controller_scenarios",
           "georep_scenarios", "schedule_digest"]

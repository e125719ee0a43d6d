"""Rank health: graceful shutdown, progress heartbeats, stall detection.

SURVEY.md §5's failure classification has two classes the trial- and
rank-death layers (PR 1/PR 2) structurally cannot reach:

- PREEMPTION: the platform asks the process to die (SIGTERM) instead of
  killing it. Treating that like a crash wastes the in-flight work and,
  worse, burns the supervisor's ``--retries`` budget on something that
  is not a failure at all. ``shutdown`` turns the signal into a
  cooperative drain: finish the in-flight batch/launch, flush
  checkpoint + ledger, exit ``EX_TEMPFAIL`` (75) — the dedicated
  "restart me with --resume, for free" code the launch supervisor
  understands.
- HANG: a rank that is alive but no longer making progress (wedged
  collective, dead-peer I/O). Exit-code polling never sees it; per-trial
  timeouts can't reach it (the wedge is below the trial layer).
  ``heartbeat`` gives every rank a monotonic progress pulse and
  ``watchdog`` gives the supervisor the reader that turns a frozen
  pulse into a kill + coordinated restart.
"""

from mpi_opt_tpu.health.heartbeat import (  # noqa: F401
    Heartbeat,
    beat,
    configure,
    deconfigure,
    read_beat,
)
from mpi_opt_tpu.health.shutdown import (  # noqa: F401
    EX_TEMPFAIL,
    ShutdownGuard,
    SweepInterrupted,
)
from mpi_opt_tpu.health.watchdog import StallDetector  # noqa: F401

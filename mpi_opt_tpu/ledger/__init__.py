"""Durable sweep ledger: journaled trial history (SURVEY.md §5).

The coordinator's trial history IS the product of a long HPO sweep, and
this package makes it durable at TRIAL granularity: ``store.SweepLedger``
appends one fsync'd JSONL record per FINAL TrialResult (a fused
boundary's member records as one block, one fsync), the driver
replays completed records through the algorithm on resume
(``run_search(ledger=...)``), ``cache.EvalCache`` skips re-evaluating
exactly-seen params, ``warmstart`` feeds a prior sweep's ledger into a
new algorithm as observations, and ``report`` renders one-or-many
ledgers for operators. Coarser-grained orbax snapshots
(``utils.checkpoint``) keep backend/train-state duty; the ledger covers
the gap between them — a crash between snapshots loses no completed
evaluation.
"""

from mpi_opt_tpu.ledger.cache import CorpusCache, EvalCache
from mpi_opt_tpu.ledger.fused import FusedJournal, make_journal
from mpi_opt_tpu.ledger.store import (
    LEDGER_SCHEMA_VERSION,
    LedgerError,
    SweepLedger,
    read_ledger,
    scan_boundaries,
    validate_ledger,
)
from mpi_opt_tpu.ledger.warmstart import warm_start

__all__ = [
    "CorpusCache",
    "EvalCache",
    "FusedJournal",
    "LEDGER_SCHEMA_VERSION",
    "LedgerError",
    "SweepLedger",
    "make_journal",
    "read_ledger",
    "scan_boundaries",
    "validate_ledger",
    "warm_start",
]

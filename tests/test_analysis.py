"""sweeplint (mpi_opt_tpu/analysis/): the invariant-checker suite.

ISSUE-9 coverage contract: every checker gets one seeded true-positive
and one true-negative fixture (string-source parse — no temp repos),
plus suppression/baseline mechanics, the `lint --json` schema gate
mirroring the fsck/report --validate pattern, the full-repo self-lint
(tier-1: the tree must be clean at HEAD), and unit tests for the
runtime sanitizers' leak detectors.

ISSUE-15 (racelint) extends both layers: the five concurrency-contract
checkers (guarded-by, beat-path-nonblocking, signal-safety, lock-order,
fsync-before-rename) get the same TP/TN fixture treatment — project
checkers run through the same ``check_source`` door, building a
single-file symbol table — plus an anti-vacuity test that the table
over the REAL repo discovers the engine's locks/thread entries, and
unit tests for the runtime lock-order sanitizer (inversion detected,
consistent order passes, per-test windows, leaks_ok honored,
creation-site tracking coverage).
"""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from mpi_opt_tpu.analysis import all_checkers, check_source
from mpi_opt_tpu.analysis.checkers_drain import DrainSwallowChecker
from mpi_opt_tpu.analysis.checkers_durability import (
    AtomicWriteChecker,
    JournalOrderChecker,
    LedgerFsyncChecker,
    LedgerGateChecker,
)
from mpi_opt_tpu.analysis.checkers_exit import ExitCodeChecker
from mpi_opt_tpu.analysis.checkers_jax import HostSyncChecker, KeyReuseChecker
from mpi_opt_tpu.analysis.checkers_registry import EventRegistryChecker
from mpi_opt_tpu.analysis.cli import lint_main, repo_root

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_one(checker, src, path="snippet.py"):
    return check_source(textwrap.dedent(src), path=path, checkers=[checker])


# -- exit-code ------------------------------------------------------------


def test_exit_code_true_positive():
    findings = run_one(
        ExitCodeChecker(),
        """
        import sys
        def bail():
            sys.exit(75)
        """,
    )
    assert [f.check for f in findings] == ["exit-code"]
    assert findings[0].line == 4

    # raise SystemExit(65) and comparisons against rc-named vars count
    assert run_one(ExitCodeChecker(), "raise SystemExit(65)\n")
    assert run_one(ExitCodeChecker(), "ok = rc == 75\n")


def test_exit_code_true_negative():
    clean = """
    import sys
    from mpi_opt_tpu.utils.exitcodes import EX_TEMPFAIL
    def bail():
        sys.exit(EX_TEMPFAIL)
    def chaos_kill():
        import os
        os._exit(13)  # not a contract code: chaos drills may be weird
    n_dims_ok = len((1, 2)) == 2  # bare small ints are not exit codes
    """
    assert run_one(ExitCodeChecker(), clean) == []
    # the one home for the literals is exempt by path
    assert (
        run_one(ExitCodeChecker(), "EX_TEMPFAIL = 75\nassert EX_TEMPFAIL == 75\n",
                path="mpi_opt_tpu/utils/exitcodes.py")
        == []
    )


# -- journal-order --------------------------------------------------------


def test_journal_order_true_positive():
    findings = run_one(
        JournalOrderChecker(),
        """
        def run(snap, journal):
            for g in range(3):
                snap.save(g, sweep={})
                journal_boundary(journal, g, [], [], [], step=1)
        """,
    )
    assert [f.check for f in findings] == ["journal-order"]
    assert findings[0].line == 4


def test_journal_order_true_negative():
    # correct order in the same loop; and a cross-region pair (drain
    # snapshot in one loop, journal in a later one) is NOT an ordering
    # violation — the contract binds within one boundary's region
    clean = """
    def run(snap, journal):
        for g in range(3):
            journal_boundary(journal, g, [], [], [], step=1)
            snap.save(g, sweep={})

    def drain_then_finish(snap, journal):
        for w in range(2):
            snap.save_wave_sweep(w)
        for g in range(3):
            journal_boundary(journal, g, [], [], [], step=1)

    def deferred(snap, journal):
        for g in range(3):
            def save_boundary():
                snap.save(g, sweep={})
            journal_boundary(journal, g, [], [], [], step=1)
            save_boundary()
        """
    assert run_one(JournalOrderChecker(), clean) == []


# -- ledger-gate ----------------------------------------------------------


def test_ledger_gate_true_positive():
    findings = run_one(
        LedgerGateChecker(),
        "led = SweepLedger('/tmp/x.jsonl')\n",
        path="mpi_opt_tpu/somewhere.py",
    )
    assert [f.check for f in findings] == ["ledger-gate"]


def test_ledger_gate_true_negative():
    gated = "led = SweepLedger(path, read_only=rank != 0)\n"
    assert run_one(LedgerGateChecker(), gated, path="mpi_opt_tpu/cli.py") == []
    # the ledger package's own internals are exempt by path
    ungated = "led = SweepLedger(path)\n"
    assert (
        run_one(LedgerGateChecker(), ungated, path="mpi_opt_tpu/ledger/warmstart.py")
        == []
    )


# -- atomic-write ---------------------------------------------------------


def test_atomic_write_true_positive():
    # signature 1: named .json destination
    f1 = run_one(
        AtomicWriteChecker(),
        """
        def write_status(path):
            with open(path + ".json", "w") as f:
                f.write("{}")
        """,
    )
    assert [f.check for f in f1] == ["atomic-write"]
    # signature 2: json.dump through a plain open (no .json in the name)
    f2 = run_one(
        AtomicWriteChecker(),
        """
        import json
        def write_out(dest, records):
            with open(dest, "w") as f:
                json.dump(records, f)
        """,
    )
    assert [f.check for f in f2] == ["atomic-write"]


def test_atomic_write_str_replace_does_not_disarm():
    """Review-round fix: only os.replace/os.rename are the atomicity
    idiom — a str.replace() in the scope must not silence the check."""
    findings = run_one(
        AtomicWriteChecker(),
        """
        import json
        def write_status(path, obj):
            name = path.replace("-", "_")
            with open(name + ".json", "w") as f:
                json.dump(obj, f)
        """,
    )
    assert len(findings) == 1  # flagged once (dedup across signatures)


def test_atomic_write_true_negative():
    clean = """
    import json, os
    def write_json_atomic(path, obj):
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def write_log(path, text):
        with open(path, "w") as f:  # not JSON: plain log, no contract
            f.write(text)
    """
    assert run_one(AtomicWriteChecker(), clean) == []


# -- ledger-fsync ---------------------------------------------------------


def test_ledger_fsync_true_positive():
    findings = run_one(
        LedgerFsyncChecker(),
        """
        class L:
            def _write_line(self, rec):
                self._file.write(rec + "\\n")
                self._file.flush()
        """,
        path="mpi_opt_tpu/ledger/store.py",
    )
    assert [f.check for f in findings] == ["ledger-fsync"]


def test_ledger_fsync_true_negative():
    clean = """
    import json, os
    class L:
        def _write_line(self, rec):
            self._file.write(json.dumps(rec) + "\\n")
            self._file.flush()
            os.fsync(self._file.fileno())
    """
    assert run_one(LedgerFsyncChecker(), clean, path="mpi_opt_tpu/ledger/store.py") == []
    # outside ledger/, file-handle writes are not this check's business
    dirty = "class X:\n    def w(self):\n        self._file.write('x')\n"
    assert run_one(LedgerFsyncChecker(), dirty, path="mpi_opt_tpu/utils/metrics.py") == []


# -- drain-swallow --------------------------------------------------------


def test_drain_swallow_true_positive():
    for src in (
        "try:\n    go()\nexcept KeyboardInterrupt:\n    pass\n",
        "try:\n    go()\nexcept (ValueError, SweepInterrupted):\n    log()\n",
        "try:\n    go()\nexcept BaseException:\n    cleanup()\n",
        "try:\n    go()\nexcept:\n    pass\n",
    ):
        findings = run_one(DrainSwallowChecker(), src)
        assert [f.check for f in findings] == ["drain-swallow"], src


def test_drain_swallow_true_negative():
    clean = """
    def contained():
        try:
            go()
        except BaseException:
            cleanup()
            raise

    def retry_loop():
        try:
            go()
        except Exception:  # Exception-level containment is not gated
            pass

    def cli_endpoint(metrics):
        try:
            go()
        except SweepInterrupted as e:  # THE protocol endpoint: maps to 75
            metrics.count_preempted()
            return EX_TEMPFAIL
    """
    assert run_one(DrainSwallowChecker(), clean) == []


# -- key-reuse ------------------------------------------------------------


def test_key_reuse_true_positive():
    findings = run_one(
        KeyReuseChecker(),
        """
        import jax
        def sample(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
        """,
    )
    assert [f.check for f in findings] == ["key-reuse"]
    assert findings[0].line == 5
    # reuse AFTER a split is the same bug
    assert run_one(
        KeyReuseChecker(),
        """
        import jax
        def sample(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(key, (4,))
        """,
    )


def test_key_reuse_true_negative():
    clean = """
    import jax
    def sample(key):
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (4,))
        b = jax.random.uniform(k2, (4,))
        return a + b

    def rebind(key):
        key, k = jax.random.split(key)
        a = jax.random.normal(k, (4,))
        key, k = jax.random.split(key)  # rebound: fresh again
        b = jax.random.normal(k, (4,))
        return a + b

    def branches(key, flag):
        if flag:
            return jax.random.normal(key, (4,))
        else:
            return jax.random.uniform(key, (4,))

    def folded(key):
        outs = []
        for i in range(4):
            outs.append(jax.random.fold_in(key, i))  # derives, not consumes
        return outs

    def numpy_is_not_jax(arr):
        import numpy as np
        np.random.shuffle(arr)
        np.random.shuffle(arr)
    """
    assert run_one(KeyReuseChecker(), clean) == []


# -- host-sync ------------------------------------------------------------

_HOT = "mpi_opt_tpu/train/fused_pbt.py"


def test_host_sync_true_positive():
    findings = run_one(
        HostSyncChecker(),
        """
        import numpy as np
        def inner_step(state, scores):
            best = scores.max().item()
            host = np.asarray(scores)
            return best, host
        """,
        path=_HOT,
    )
    assert [f.check for f in findings] == ["host-sync", "host-sync"]


def test_host_sync_true_negative():
    # annotated barrier functions may sync; nested defs judged alone;
    # non-hot-path modules not scanned at all
    clean = """
    import numpy as np
    def host_loop(scores):  # sweeplint: barrier(generation boundary)
        return np.asarray(scores)

    def annotated_line(x):
        y = x.block_until_ready()  # sweeplint: barrier(final fetch)
        return y
    """
    assert run_one(HostSyncChecker(), clean, path=_HOT) == []
    dirty_elsewhere = "import numpy as np\ndef f(x):\n    return np.asarray(x)\n"
    assert run_one(HostSyncChecker(), dirty_elsewhere, path="mpi_opt_tpu/driver.py") == []


def test_host_sync_nested_def_not_exempted_by_parent():
    findings = run_one(
        HostSyncChecker(),
        """
        import numpy as np
        def host_loop(xs):  # sweeplint: barrier(boundary)
            a = np.asarray(xs)  # fine: annotated function body
            def traced_program(c, x):
                return c, x.item()  # NOT exempt: nested def judged alone
            return a, traced_program
        """,
        path=_HOT,
    )
    assert [f.line for f in findings] == [6]


# -- event-registry -------------------------------------------------------


def test_event_registry_true_positive():
    findings = run_one(
        EventRegistryChecker(),
        "metrics.log('totally_new_event', x=1)\n",
    )
    assert [f.check for f in findings] == ["event-registry"]


def test_event_registry_true_negative():
    clean = (
        "metrics.log('summary', x=1)\n"
        "with trace.span('train'):\n    pass\n"
        "log('not an emitter: bare log is bench stderr')\n"
        "metrics.log(variable_name, x=1)\n"
    )
    assert run_one(EventRegistryChecker(), clean) == []


def test_event_registry_shim_still_serves_test_obs():
    """The obs.events surface the historical tier-1 lint uses delegates
    to the framework and sees the same sites (coverage must not drop
    during the migration)."""
    from mpi_opt_tpu.obs import events

    assert events.lint(REPO_ROOT) == []
    kinds = {(k, n) for _p, _l, k, n in events.scan_call_sites(REPO_ROOT)}
    assert ("event", "summary") in kinds
    assert ("span", "train") in kinds


# -- lease-write (ISSUE 12) ----------------------------------------------


def test_lease_write_true_positive():
    from mpi_opt_tpu.analysis.checkers_lease import LeaseWriteChecker

    # direct write to a lease path in a scheduler-ish file
    f1 = run_one(
        LeaseWriteChecker(),
        """
        import json
        def grab(t):
            with open(t.lease, "w") as f:
                json.dump({"server_id": "me"}, f)
        """,
        path="service/scheduler.py",
    )
    assert [f.check for f in f1] == ["lease-write"]
    # rename onto a lease file (the tomb protocol is helper-only)
    f2 = run_one(
        LeaseWriteChecker(),
        """
        import os
        def sneak(tmp, lease_path):
            os.replace(tmp, lease_path)
        """,
        path="service/spool.py",
    )
    assert [f.check for f in f2] == ["lease-write"]
    # bare unlink bypasses the token-checked release
    f3 = run_one(
        LeaseWriteChecker(),
        """
        import os
        def drop(d):
            os.unlink(d + "/lease.json")
        """,
    )
    assert [f.check for f in f3] == ["lease-write"]
    # os.open of a lease path (the O_EXCL create is helper-only too)
    f4 = run_one(
        LeaseWriteChecker(),
        """
        import os
        def claim(lease_path):
            return os.open(lease_path, os.O_CREAT | os.O_EXCL)
        """,
    )
    assert [f.check for f in f4] == ["lease-write"]


def test_lease_write_true_negative():
    from mpi_opt_tpu.analysis.checkers_lease import LeaseWriteChecker

    clean = """
    import json, os
    def read_side(t, path, released):
        with open(t.lease) as f:          # reads are free
            cur = json.load(f)
        os.replace(path + ".tmp", path)   # non-lease replace
        with open("release-notes.txt", "w") as f:  # `release` != lease
            f.write("released!")
        os.unlink(released)               # identifier word-boundary
        return cur
    """
    assert run_one(LeaseWriteChecker(), clean, path="service/client.py") == []
    # the helper module itself is the one legal writer
    inside = """
    import os
    def acquire(path):
        return os.open(path + "/lease.json", os.O_CREAT | os.O_EXCL)
    """
    assert run_one(LeaseWriteChecker(), inside, path="mpi_opt_tpu/service/leases.py") == []


# -- corpus-index-write (ISSUE 14) ----------------------------------------


def test_corpus_index_write_true_positive():
    from mpi_opt_tpu.analysis.checkers_corpus import CorpusIndexWriteChecker

    # direct write of the index file outside the helper module
    f1 = run_one(
        CorpusIndexWriteChecker(),
        """
        import json
        def persist(doc, corpus_index_path):
            with open(corpus_index_path, "w") as f:
                json.dump(doc, f)
        """,
        path="corpus/resolve.py",
    )
    assert [f.check for f in f1] == ["corpus-index-write"]
    # rename onto the on-disk name, and deletion out from under readers
    f2 = run_one(
        CorpusIndexWriteChecker(),
        """
        import os
        def sneak(tmp, d):
            os.replace(tmp, d + "/corpus-index.json")
            os.unlink(d + "/corpus-index.json")
        """,
    )
    assert [f.check for f in f2] == ["corpus-index-write"] * 2


def test_corpus_index_write_true_negative():
    from mpi_opt_tpu.analysis.checkers_corpus import CorpusIndexWriteChecker

    clean = """
    import json, os
    def read_side(corpus_index_path, reindex_log):
        with open(corpus_index_path) as f:   # reads are free
            doc = json.load(f)
        with open(reindex_log, "w") as f:    # `reindex` != corpus_index
            f.write("ok")
        os.replace("status.tmp", "status.json")  # non-index replace
        return doc
    """
    assert run_one(CorpusIndexWriteChecker(), clean, path="corpus/cli.py") == []
    # the atomic helper's own home is the one legal writer
    inside = """
    import os
    def write_index(path, tmp):
        os.replace(tmp, path + "/corpus-index.json")
    """
    assert (
        run_one(
            CorpusIndexWriteChecker(), inside, path="mpi_opt_tpu/corpus/index.py"
        )
        == []
    )


# -- coord-write (ISSUE 20) -----------------------------------------------


def test_coord_write_true_positive():
    from mpi_opt_tpu.analysis.checkers_coord import CoordWriteChecker

    # direct write of a decision file outside the plane module
    f1 = run_one(
        CoordWriteChecker(),
        """
        import json
        def publish(edir, doc):
            with open(edir + "/drain.000001.decision.json", "w") as f:
                json.dump(doc, f)
        """,
        path="mpi_opt_tpu/launch.py",
    )
    assert [f.check for f in f1] == ["coord-write"]
    # os.open of a vote path — the O_EXCL create is plane-only
    f2 = run_one(
        CoordWriteChecker(),
        """
        import os
        def vote(vote_path):
            return os.open(vote_path, os.O_CREAT | os.O_EXCL)
        """,
    )
    assert [f.check for f in f2] == ["coord-write"]
    # rename onto a coord path, and unlink under live readers
    f3 = run_one(
        CoordWriteChecker(),
        """
        import os
        def scrub(tmp, coord_dir):
            os.replace(tmp, coord_dir + "/READY.json")
            os.unlink(coord_dir + "/READY.json")
        """,
        path="tests/test_something.py",
    )
    assert [f.check for f in f3] == ["coord-write"] * 2


def test_coord_write_true_negative():
    from mpi_opt_tpu.analysis.checkers_coord import CoordWriteChecker

    clean = """
    import json, os
    def read_side(edir, coordinator, log_path):
        with open(edir + "/drain.000001.decision.json") as f:  # reads free
            doc = json.load(f)
        with open(log_path, "w") as f:       # non-coord write
            f.write(coordinator)             # jax addr plumbing != coord
        os.replace("hb.tmp", "hb.json")      # non-coord replace
        return doc
    """
    assert run_one(CoordWriteChecker(), clean, path="mpi_opt_tpu/cli.py") == []
    # the plane's own home is the one legal writer
    inside = """
    import os
    def decide(path, tmp):
        os.replace(tmp, path)
        return os.open(path + ".vote.json", os.O_CREAT | os.O_EXCL)
    """
    assert (
        run_one(CoordWriteChecker(), inside, path="mpi_opt_tpu/parallel/coord.py")
        == []
    )


# -- racelint: guarded-by (ISSUE 15) --------------------------------------


def test_guarded_by_true_positive():
    from mpi_opt_tpu.analysis.checkers_concurrency import GuardedByChecker

    findings = run_one(
        GuardedByChecker(),
        """
        import threading
        _LOCK = threading.Lock()
        _COUNT = 0
        def _worker():
            global _COUNT
            _COUNT += 1
        def start():
            threading.Thread(target=_worker).start()
        def reset():
            global _COUNT
            _COUNT = 0
        """,
    )
    assert [f.check for f in findings] == ["guarded-by"]
    assert findings[0].line == 4  # reported at the declaration
    assert "_COUNT" in findings[0].message


def test_guarded_by_write_outside_declared_guard():
    from mpi_opt_tpu.analysis.checkers_concurrency import GuardedByChecker

    findings = run_one(
        GuardedByChecker(),
        """
        import threading
        _LOCK = threading.Lock()
        _COUNT = 0  # sweeplint: guarded-by(_LOCK)
        def _worker():
            global _COUNT
            with _LOCK:
                _COUNT += 1
        def start():
            threading.Thread(target=_worker).start()
        def reset():
            global _COUNT
            _COUNT = 0
        """,
    )
    assert [f.check for f in findings] == ["guarded-by"]
    assert findings[0].line == 13  # the escaping write, not the decl
    assert "outside its declared guard" in findings[0].message


def test_guarded_by_unknown_lock_in_annotation():
    from mpi_opt_tpu.analysis.checkers_concurrency import GuardedByChecker

    findings = run_one(
        GuardedByChecker(),
        """
        import threading
        _COUNT = 0  # sweeplint: guarded-by(_NO_SUCH_LOCK)
        def _worker():
            global _COUNT
            _COUNT += 1
        def start():
            threading.Thread(target=_worker).start()
        def reset():
            global _COUNT
            _COUNT = 0
        """,
    )
    assert [f.check for f in findings] == ["guarded-by"]
    assert "names no lock" in findings[0].message


def test_guarded_by_nested_def_global_does_not_leak_to_parent():
    """Review-round fix: a nested def's `global X` must not make the
    ENCLOSING function's local X read as a module-global write —
    Python scoping keeps the outer assignment local."""
    from mpi_opt_tpu.analysis.checkers_concurrency import GuardedByChecker

    clean = """
    import threading
    _LOCK = threading.Lock()
    _COUNT = 0
    def outer():
        def _inner():
            global _COUNT
            with _LOCK:
                _COUNT += 1
        _COUNT = 5  # LOCAL of outer (no global stmt in outer's scope)
        threading.Thread(target=_inner).start()
        return _COUNT
    def reset():
        global _COUNT
        with _LOCK:
            _COUNT = 0
    """
    assert run_one(GuardedByChecker(), clean) == []


def test_guarded_by_true_negative():
    from mpi_opt_tpu.analysis.checkers_concurrency import GuardedByChecker

    # annotated + every shared write under the declared lock — the
    # branch writes exercise the arms-are-separate-regions discipline
    # (each arm holds the lock; neither arm sees the other)
    clean = """
    import threading
    _LOCK = threading.Lock()
    _COUNT = 0  # sweeplint: guarded-by(_LOCK)
    def _worker(flag):
        global _COUNT
        if flag:
            with _LOCK:
                _COUNT += 1
        else:
            with _LOCK:
                _COUNT = 0
    def start():
        threading.Thread(target=_worker).start()
    def reset():
        global _COUNT
        with _LOCK:
            _COUNT = 0
    """
    assert run_one(GuardedByChecker(), clean) == []
    # unannotated but every shared write holds ONE common lock: clean
    common = """
    import threading
    _LOCK = threading.Lock()
    _SEQ = [0]
    def _worker():
        with _LOCK:
            _SEQ[0] += 1
    def start():
        threading.Thread(target=_worker).start()
    def bump():
        with _LOCK:
            _SEQ[0] += 1
    """
    assert run_one(GuardedByChecker(), common) == []
    # a global only main-line code writes is not shared
    mainline_only = """
    import threading
    _MODE = None
    def configure(m):
        global _MODE
        _MODE = m
    def _worker():
        return _MODE  # thread READS are not this checker's business
    def start():
        threading.Thread(target=_worker).start()
    """
    assert run_one(GuardedByChecker(), mainline_only) == []
    # threading.local containers are per-thread by design
    local_ok = """
    import threading
    _LOCAL = threading.local()
    def _worker():
        _LOCAL.stack = []
    def start():
        threading.Thread(target=_worker).start()
    """
    assert run_one(GuardedByChecker(), local_ok) == []


# -- racelint: beat-path-nonblocking (ISSUE 15) ---------------------------


def test_beat_path_true_positive_registered_listener():
    from mpi_opt_tpu.analysis.checkers_concurrency import BeatPathChecker

    findings = run_one(
        BeatPathChecker(),
        """
        import threading
        class Keeper:
            def __init__(self):
                self._lock = threading.Lock()
            def __call__(self, rec):
                with self._lock:
                    pass
        def wire():
            k = Keeper()
            set_beat_listener(k)
        """,
    )
    assert [f.check for f in findings] == ["beat-path-nonblocking"]
    assert findings[0].line == 7


def test_beat_path_true_positive_heartbeat_root():
    from mpi_opt_tpu.analysis.checkers_concurrency import BeatPathChecker

    # the structural root: `beat` defined in a heartbeat.py is ON the
    # beat path with no registration needed
    findings = run_one(
        BeatPathChecker(),
        """
        import threading
        _LOCK = threading.Lock()
        def beat(**progress):
            with _LOCK:
                pass
        """,
        path="mypkg/health/heartbeat.py",
    )
    assert [f.check for f in findings] == ["beat-path-nonblocking"]


def test_beat_path_true_negative():
    from mpi_opt_tpu.analysis.checkers_concurrency import BeatPathChecker

    # non-blocking and timeout acquires pass; branch arms each
    # acquiring non-blocking never join into a false positive; the
    # same blocking `with` OFF the beat path is not this checker's
    # business
    clean = """
    import threading
    class Keeper:
        def __init__(self):
            self._lock = threading.Lock()
        def __call__(self, rec):
            if not self._lock.acquire(blocking=False):
                return
            try:
                pass
            finally:
                self._lock.release()
        def timed(self):
            if self._lock.acquire(timeout=0.5):
                self._lock.release()
        def stop(self):
            with self._lock:  # slice end, main thread: allowed
                return dict()
    def wire():
        k = Keeper()
        set_beat_listener(k)
    def mainline(k):
        k.stop()
    """
    assert run_one(BeatPathChecker(), clean) == []


def test_beat_path_slice_hook_is_covered():
    from mpi_opt_tpu.analysis.checkers_concurrency import BeatPathChecker

    findings = run_one(
        BeatPathChecker(),
        """
        import threading
        _LOCK = threading.Lock()
        def hook(stage):
            with _LOCK:
                pass
        def wire():
            set_slice_hook(hook)
        """,
    )
    assert [f.check for f in findings] == ["beat-path-nonblocking"]


# -- racelint: signal-safety (ISSUE 15) -----------------------------------


def test_signal_safety_true_positive_io():
    from mpi_opt_tpu.analysis.checkers_concurrency import SignalSafetyChecker

    findings = run_one(
        SignalSafetyChecker(),
        """
        import signal
        def _handler(signum, frame):
            with open("/tmp/dead.json", "w") as f:
                f.write("x")
        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
    )
    assert findings and all(f.check == "signal-safety" for f in findings)


def test_signal_safety_true_positive_lock():
    from mpi_opt_tpu.analysis.checkers_concurrency import SignalSafetyChecker

    findings = run_one(
        SignalSafetyChecker(),
        """
        import signal, threading
        _LOCK = threading.Lock()
        def _handler(signum, frame):
            with _LOCK:
                pass
        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
    )
    assert [f.check for f in findings] == ["signal-safety"]
    assert "self-deadlock" in findings[0].message


def test_signal_safety_transitive_reach():
    from mpi_opt_tpu.analysis.checkers_concurrency import SignalSafetyChecker

    # the unsafe call hides one hop away from the handler
    findings = run_one(
        SignalSafetyChecker(),
        """
        import signal, time
        def _spin():
            time.sleep(1.0)
        def _handler(signum, frame):
            _spin()
        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
    )
    assert [f.check for f in findings] == ["signal-safety"]


def test_signal_safety_true_negative_flag_only():
    from mpi_opt_tpu.analysis.checkers_concurrency import SignalSafetyChecker

    # the ShutdownGuard shape: set a flag, read state, raise
    clean = """
    import signal
    _FLAG = False
    def _handler(signum, frame):
        global _FLAG
        name = signal.Signals(signum).name
        _FLAG = True
        if name == "SIGINT":
            raise KeyboardInterrupt
    def install():
        signal.signal(signal.SIGTERM, _handler)
    def mainline():
        with open("/tmp/log.txt", "w") as f:  # NOT handler-reachable
            f.write("fine")
    """
    assert run_one(SignalSafetyChecker(), clean) == []


# -- racelint: lock-order (ISSUE 15) --------------------------------------


def test_lock_order_cycle_true_positive():
    from mpi_opt_tpu.analysis.checkers_concurrency import LockOrderChecker

    findings = run_one(
        LockOrderChecker(),
        """
        import threading
        _A = threading.Lock()
        _B = threading.Lock()
        def one():
            with _A:
                with _B:
                    pass
        def two():
            with _B:
                with _A:
                    pass
        """,
    )
    assert [f.check for f in findings] == ["lock-order"]
    assert "cycle" in findings[0].message


def test_lock_order_cycle_through_call_edge():
    from mpi_opt_tpu.analysis.checkers_concurrency import LockOrderChecker

    # the inner acquisition hides behind a function call: a with-lock
    # body calling a function that takes another lock is an edge too
    findings = run_one(
        LockOrderChecker(),
        """
        import threading
        _A = threading.Lock()
        _B = threading.Lock()
        def grab_a():
            with _A:
                pass
        def b_then_a():
            with _B:
                grab_a()
        def a_then_b():
            with _A:
                with _B:
                    pass
        """,
    )
    assert [f.check for f in findings] == ["lock-order"]


def test_lock_order_cycle_through_generic_named_self_call():
    """Review-round fix: a self-method call through a GENERIC name
    (``self.put()``) must still resolve via the enclosing class's
    method map — the bare-name fallback deny list exists to stop
    cross-file guessing, not to drop a same-class deadlock edge."""
    from mpi_opt_tpu.analysis.checkers_concurrency import LockOrderChecker

    findings = run_one(
        LockOrderChecker(),
        """
        import threading
        class Box:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
            def put(self):
                with self._b:
                    pass
            def outer(self):
                with self._a:
                    self.put()
            def other(self):
                with self._b:
                    with self._a:
                        pass
        """,
    )
    assert [f.check for f in findings] == ["lock-order"]


def test_lock_order_true_negative():
    from mpi_opt_tpu.analysis.checkers_concurrency import LockOrderChecker

    # one consistent order everywhere; and an opposite-order TRYLOCK
    # contributes no edge (a non-blocking acquire cannot deadlock)
    clean = """
    import threading
    _A = threading.Lock()
    _B = threading.Lock()
    def one():
        with _A:
            with _B:
                pass
    def two():
        with _A:
            with _B:
                pass
    def probe():
        with _B:
            if _A.acquire(blocking=False):
                _A.release()
    """
    assert run_one(LockOrderChecker(), clean) == []


# -- fsync-before-rename (ISSUE 15) ---------------------------------------

_DURABLE = "mpi_opt_tpu/service/spool.py"


def test_fsync_before_rename_true_positive():
    from mpi_opt_tpu.analysis.checkers_concurrency import (
        FsyncBeforeRenameChecker,
    )

    findings = run_one(
        FsyncBeforeRenameChecker(),
        """
        import json, os
        def write_status(path, obj):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(obj, f)
            os.replace(tmp, path)
        """,
        path=_DURABLE,
    )
    assert [f.check for f in findings] == ["fsync-before-rename"]
    assert findings[0].line == 7  # anchored at the publishing rename


def test_fsync_before_rename_true_negative():
    from mpi_opt_tpu.analysis.checkers_concurrency import (
        FsyncBeforeRenameChecker,
    )

    clean = """
    import json, os
    def write_status(path, obj):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(obj, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def quarantine(src, dst):
        os.replace(src, dst)  # rename of an EXISTING file: no tmp write
    """
    assert run_one(FsyncBeforeRenameChecker(), clean, path=_DURABLE) == []
    # out of scope by design: the heartbeat's liveness files are
    # deliberately NOT fsync'd (losing the last beat costs nothing)
    dirty_elsewhere = """
    import json, os
    def beat(path, rec):
        with open(path + ".tmp", "w") as f:
            f.write(json.dumps(rec))
        os.replace(path + ".tmp", path)
    """
    assert (
        run_one(
            FsyncBeforeRenameChecker(), dirty_elsewhere,
            path="mpi_opt_tpu/health/heartbeat.py",
        )
        == []
    )


# -- racelint: the project symbol table over the real repo ----------------


def test_project_table_discovers_engine_symbols():
    """Anti-vacuity for the project pass: the symbol table over the
    real tree must discover the locks/entries the concurrency story is
    actually built on — an empty table would make every project checker
    vacuously green."""
    from mpi_opt_tpu.analysis.core import run_paths_ex

    findings, _n, errors, table = run_paths_ex([repo_root()])
    assert errors == [] and findings == []
    assert table is not None
    lock_names = {d.name for d in table.locks.values()}
    for need in (
        "staging.StagingEngine._lock",
        "heartbeat.Heartbeat._lock",
        "leases._TOKEN_LOCK",
        "leases.Refresher._lock",
        "scheduler.SweepService._reg_lock",
        "memory._PEAK_LOCK",
    ):
        assert need in lock_names, sorted(lock_names)
    thread_fns = {table.functions[k].qualname for k, _ in table.thread_entries}
    assert "StagingEngine._loop" in thread_fns
    signal_fns = {table.functions[k].qualname for k, _ in table.signal_entries}
    assert "ShutdownGuard._handle" in signal_fns
    beat_fns = {table.functions[k].qualname for k, _ in table.beat_entries}
    # the registered closures AND the structural roots
    assert "SweepService._run_slice.on_beat" in beat_fns
    assert "SweepService._run_slice.hook" in beat_fns
    assert "Heartbeat.beat" in beat_fns
    # the repo's static lock order must stay acyclic
    from mpi_opt_tpu.analysis.project import find_cycles, lock_order_edges

    assert find_cycles(lock_order_edges(table)) == []


# -- suppression + baseline ----------------------------------------------


def test_inline_suppression_same_line_and_line_above():
    src = (
        "import sys\n"
        "sys.exit(75)  # sweeplint: disable=exit-code -- historical drill\n"
        "# sweeplint: disable=exit-code -- next line too\n"
        "sys.exit(65)\n"
        "sys.exit(75)\n"
    )
    findings = check_source(src, checkers=[ExitCodeChecker()])
    assert [f.line for f in findings] == [5]  # only the unsuppressed one


def test_suppression_is_per_check_id():
    src = "import sys\nsys.exit(75)  # sweeplint: disable=atomic-write\n"
    assert check_source(src, checkers=[ExitCodeChecker()])  # wrong id: still fires


def test_baseline_roundtrip(tmp_path):
    from mpi_opt_tpu.analysis.core import (
        load_baseline,
        run_paths,
        split_baselined,
        write_baseline,
    )

    bad = tmp_path / "legacy.py"
    bad.write_text("import sys\nsys.exit(75)\n")
    findings, n, errors = run_paths([str(bad)], [ExitCodeChecker()])
    assert n == 1 and not errors and len(findings) == 1
    base = tmp_path / "baseline.json"
    write_baseline(str(base), findings, str(tmp_path))
    fresh, accepted = split_baselined(
        findings, load_baseline(str(base)), str(tmp_path)
    )
    assert fresh == [] and len(accepted) == 1
    # editing the flagged line un-baselines it (content fingerprint)
    bad.write_text("import sys\nsys.exit(75)  # changed\n")
    findings2, _, _ = run_paths([str(bad)], [ExitCodeChecker()])
    fresh2, accepted2 = split_baselined(
        findings2, load_baseline(str(base)), str(tmp_path)
    )
    assert len(fresh2) == 1 and accepted2 == []


def test_unparseable_file_is_an_error_not_a_skip(tmp_path):
    from mpi_opt_tpu.analysis.core import run_paths

    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings, n, errors = run_paths([str(bad)])
    assert findings == [] and n == 1 and len(errors) == 1


# -- lint CLI: schema gate + exit codes ----------------------------------


def test_lint_json_schema_gate(tmp_path, capsys):
    """The tier-1 format-drift gate for `lint --json`, mirroring the
    fsck/report --validate pattern: a stable top-level shape CI can
    parse, exit 1 on findings, exit 0 clean."""
    bad = tmp_path / "legacy.py"
    bad.write_text("import sys\nsys.exit(75)\n")
    rc = lint_main([str(bad), "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert set(rep) == {
        "ok", "tool", "files_scanned", "findings", "baselined", "errors",
        "checks", "project",
    }
    assert rep["ok"] is False and rep["tool"] == "sweeplint"
    assert rep["files_scanned"] == 1 and rep["errors"] == []
    (f,) = rep["findings"]
    assert set(f) == {"check", "file", "line", "severity", "message", "hint"}
    assert f["check"] == "exit-code" and f["line"] == 2
    # the check catalog names every shipped checker, each with its
    # attributed wall time (the slow-checker diagnosability contract)
    assert {c["id"] for c in rep["checks"]} == {
        "exit-code", "journal-order", "ledger-gate", "atomic-write",
        "ledger-fsync", "drain-swallow", "key-reuse", "host-sync",
        "event-registry", "lease-write", "corpus-index-write",
        "resource-funnel", "fsync-before-rename", "guarded-by",
        "beat-path-nonblocking", "signal-safety", "lock-order",
        "http-handler-contained", "coord-write",
        "project-table",  # synthetic: pass-1 symbol-table build time
    }
    assert all(
        isinstance(c["wall_s"], (int, float)) and c["wall_s"] >= 0
        for c in rep["checks"]
    )
    # the project-pass section: symbol-table digest with a stable shape
    proj = rep["project"]
    assert set(proj) == {
        "locks", "thread_entries", "signal_handlers", "beat_entries",
        "lock_order",
    }
    assert set(proj["lock_order"]) == {"edges", "cycles"}


def test_lint_cli_baseline_flow(tmp_path, capsys):
    bad = tmp_path / "legacy.py"
    bad.write_text("import sys\nsys.exit(75)\n")
    base = str(tmp_path / "baseline.json")
    assert lint_main([str(bad), "--write-baseline", base]) == 0
    capsys.readouterr()
    rc = lint_main([str(bad), "--baseline", base, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["ok"] is True
    assert rep["findings"] == [] and len(rep["baselined"]) == 1


def test_lint_cli_write_baseline_refuses_unparseable_tree(tmp_path, capsys):
    """Review-round fix: a baseline recorded while files are
    unparseable omits their findings — write-baseline must refuse, not
    exit 0 with a lying file."""
    (tmp_path / "broken.py").write_text("def f(:\n")
    (tmp_path / "legacy.py").write_text("import sys\nsys.exit(75)\n")
    base = str(tmp_path / "baseline.json")
    assert lint_main([str(tmp_path), "--write-baseline", base]) == 1
    assert "unparseable" in capsys.readouterr().err
    assert not os.path.exists(base)


def test_lint_cli_clean_tree_exits_zero(tmp_path, capsys):
    good = tmp_path / "fine.py"
    good.write_text("x = 1\n")
    assert lint_main([str(good), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True


def test_lint_cli_missing_path_is_usage_error(tmp_path):
    with pytest.raises(SystemExit) as ei:
        lint_main([str(tmp_path / "nope")])
    assert ei.value.code == 2


# -- the tier-1 self-lint -------------------------------------------------


def test_self_lint_repo_is_clean():
    """The whole suite over the whole repo: zero non-baselined findings
    at HEAD (fixes + inline disables, per ISSUE 9 — the committed
    baseline is deliberately empty). Also the perf gate: parse+walk of
    ~90 files must stay inside the tier-1 budget."""
    import time

    from mpi_opt_tpu.analysis.core import run_paths

    t0 = time.perf_counter()
    findings, n_files, errors = run_paths([repo_root()])
    wall = time.perf_counter() - t0
    assert errors == [], errors
    assert findings == [], "\n".join(f.render(repo_root()) for f in findings)
    assert n_files > 95  # the scan actually saw the tree (ISSUE 15 floor)
    assert wall < 15.0, f"self-lint took {wall:.1f}s — over the tier-1 budget"


def test_self_lint_scanner_sees_known_shapes():
    """Anti-vacuity: the self-lint's walker actually visits the files
    the invariants live in (an over-eager exclusion list would make the
    clean result meaningless)."""
    from mpi_opt_tpu.analysis.core import iter_python_files

    seen = {os.path.relpath(p, repo_root()) for p in iter_python_files(repo_root())}
    for must in (
        "mpi_opt_tpu/cli.py",
        "mpi_opt_tpu/ledger/store.py",
        "mpi_opt_tpu/train/fused_pbt.py",
        "bench.py",
    ):
        assert must in seen
    assert not any(p.startswith(("tests/", "probes/")) for p in seen)


# -- runtime sanitizers (tests/sanitizers.py) -----------------------------


def test_sanitizer_detects_thread_leak():
    import threading

    import sanitizers

    before = sanitizers.snapshot()
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="leaky", daemon=False)
    t.start()
    try:
        problems = sanitizers.leaks(before)
        assert any("leaky" in p for p in problems), problems
    finally:
        stop.set()
        t.join()
    assert sanitizers.leaks(before) == []


def test_sanitizer_detects_signal_handler_leak():
    import signal

    import sanitizers

    before = sanitizers.snapshot()
    prev = signal.signal(signal.SIGTERM, lambda *a: None)
    try:
        problems = sanitizers.leaks(before)
        assert any("SIGTERM" in p for p in problems), problems
    finally:
        signal.signal(signal.SIGTERM, prev)
    assert sanitizers.leaks(before) == []


def test_sanitizer_detects_sink_leaks():
    import sanitizers
    from mpi_opt_tpu.health import heartbeat, shutdown
    from mpi_opt_tpu.obs import trace
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    before = sanitizers.snapshot()
    prior = trace.configure(MetricsLogger())
    hb = heartbeat.configure("/tmp/_sanitizer_hb.json")
    shutdown.set_slice_hook(lambda stage: None)
    try:
        problems = sanitizers.leaks(before)
        assert any("trace sink" in p for p in problems)
        assert any("heartbeat" in p for p in problems)
        assert any("slice hook" in p for p in problems)
    finally:
        del hb
        trace.deconfigure(prior)
        heartbeat.deconfigure()
        shutdown.clear_slice_hook()
    assert sanitizers.leaks(before) == []


def test_sanitizer_guard_restores_are_clean():
    """The ShutdownGuard contract the sanitizer enforces, demonstrated
    the way every test should use it: scoped = no residue."""
    import sanitizers
    from mpi_opt_tpu.health.shutdown import ShutdownGuard

    before = sanitizers.snapshot()
    with ShutdownGuard():
        pass
    assert sanitizers.leaks(before) == []


@pytest.mark.leaks_ok
def test_sanitizer_opt_out_marker_is_honored():
    """A leaks_ok test skips the teardown check (this test would fail
    it on purpose if the marker were broken — the handler IS restored,
    but only after the assertion window below)."""
    import signal

    import sanitizers

    before = sanitizers.snapshot()
    prev = signal.signal(signal.SIGTERM, lambda *a: None)
    assert sanitizers.leaks(before)  # detectable...
    signal.signal(signal.SIGTERM, prev)  # ...and restored before exit


# -- the limit every test has, and the session's workloads (ISSUE 30) ------


def test_time_limit_fails_the_test_by_name_and_puts_the_timer_back():
    """``sanitizers.time_limit`` (conftest.py puts every test's call
    phase under it): a block that outlasts its limit is failed with the
    name it was given; the timer that was running before (this test's
    own limit) and the previous handler are back afterwards, whether
    the block ran out or not."""
    import signal
    import time

    import sanitizers

    handler = signal.getsignal(signal.SIGALRM)
    remaining_before, _ = signal.getitimer(signal.ITIMER_REAL)
    # the hook is on: this test itself is being timed, by the one constant
    assert 0 < remaining_before <= sanitizers.TEST_LIMIT_S
    with pytest.raises(pytest.fail.Exception, match=r"tests/x\.py::test_slow\[a\] ran past the limit of 0\.05 s"):
        with sanitizers.time_limit(0.05, "tests/x.py::test_slow[a]"):
            give_up = time.monotonic() + 5.0
            while time.monotonic() < give_up:
                time.sleep(0.01)
    with sanitizers.time_limit(5.0, "quick"):
        pass  # inside its limit: nothing is raised
    assert signal.getsignal(signal.SIGALRM) is handler
    remaining, interval = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < remaining <= remaining_before and interval == 0.0


def test_shared_workload_is_one_instance_for_the_same_arguments(shared_workload):
    from mpi_opt_tpu.workloads import get_workload

    a = shared_workload("fashion_mlp", n_train=64, n_val=32)
    assert shared_workload("fashion_mlp", n_val=32, n_train=64) is a
    assert type(a) is type(get_workload("fashion_mlp")) and (a.n_train, a.n_val) == (64, 32)
    # other sizes, another name, a label (the variant of the trainer the
    # test will ask for) or an attribute: an instance each
    others = [
        shared_workload("fashion_mlp", n_train=64, n_val=16),
        shared_workload("digits_mlp"),
        shared_workload("fashion_mlp", label="pop8 data1 mesh", n_train=64, n_val=32),
        shared_workload("fashion_mlp", n_train=64, n_val=32, attrs={"batch_size": 8}),
    ]
    assert len({id(w) for w in [a, *others]}) == 5
    assert others[3].batch_size == 8 and a.batch_size != 8
    assert shared_workload("fashion_mlp", n_train=64, n_val=32, attrs={"batch_size": 8}) is others[3]


# -- lock-order runtime sanitizer (ISSUE 15) ------------------------------


@pytest.mark.leaks_ok  # the seeded inversion WOULD fail the autouse
# fixture — which is the feature; judged explicitly below instead
def test_lock_order_sanitizer_detects_inversion():
    import sanitizers

    before = sanitizers.snapshot()
    a = sanitizers.tracked_lock("inv-a")
    b = sanitizers.tracked_lock("inv-b")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    problems = sanitizers.leaks(before)
    assert any("lock-order inversion" in p for p in problems), problems
    # the report names both locks and the first-observed site
    msg = next(p for p in problems if "lock-order inversion" in p)
    assert "inv-a" in msg and "inv-b" in msg
    # a fresh window (the next test's snapshot) starts clean
    assert sanitizers.leaks(sanitizers.snapshot()) == []


def test_lock_order_sanitizer_consistent_order_passes():
    import sanitizers

    before = sanitizers.snapshot()
    a = sanitizers.tracked_lock("ord-a")
    b = sanitizers.tracked_lock("ord-b")
    for _ in range(3):
        with a:
            with b:
                pass
    # reentrant same-lock handling: acquire of the lock you hold (the
    # RLock shape) must not self-edge
    r = sanitizers.TrackedLock(sanitizers._REAL_RLOCK(), "ord-r")
    with r:
        with r:
            pass
    assert sanitizers.leaks(before) == []


@pytest.mark.leaks_ok  # the second half SEEDS an inversion on purpose
def test_lock_order_sanitizer_trylock_contributes_no_edge():
    """Review-round fix: the PR-12 idiom — `with B:` then
    `A.acquire(blocking=False)` — is deadlock-free (a trylock never
    waits) and passes the STATIC lock-order checker; the runtime
    tracker must apply the same rule instead of reporting a false
    inversion."""
    import sanitizers

    before = sanitizers.snapshot()
    a = sanitizers.tracked_lock("try-a")
    b = sanitizers.tracked_lock("try-b")
    with a:
        with b:
            pass
    with b:
        assert a.acquire(blocking=False)
        a.release()
    assert sanitizers.leaks(before) == []
    # ...but a blocking acquire UNDER a trylock-held lock still edges:
    # the trylock holder waiting on another lock can deadlock
    before = sanitizers.snapshot()
    assert a.acquire(blocking=False)
    with b:
        pass
    a.release()
    with b:
        assert a.acquire(timeout=1.0)  # bounded wait still waits
        a.release()
    problems = sanitizers.leaks(before)
    assert any("lock-order inversion" in p for p in problems), problems


def test_lock_order_serial_identity_survives_gc():
    """Review-round fix: edges were keyed by id(), and CPython's
    freelist reuses a dead lock's address immediately — a fresh lock
    inherited the dead one's edges and fabricated inversions. Serial
    identity makes this deterministic."""
    import sanitizers

    before = sanitizers.snapshot()
    keeper = sanitizers.tracked_lock("gc-keeper")
    dead = sanitizers.tracked_lock("gc-dead")
    with keeper:
        with dead:
            pass
    del dead  # its serial retires with it; its edges are inert
    fresh = sanitizers.tracked_lock("gc-fresh")
    with fresh:
        with keeper:
            pass
    assert sanitizers.leaks(before) == []


def test_lock_order_windows_are_per_test():
    """Opposite orders in two different WINDOWS (= tests) never
    cross-contaminate: each window judges only its own observations."""
    import sanitizers

    a = sanitizers.tracked_lock("win-a")
    b = sanitizers.tracked_lock("win-b")
    before = sanitizers.snapshot()
    with a:
        with b:
            pass
    assert sanitizers.leaks(before) == []
    before = sanitizers.snapshot()  # new window: the a->b edge is gone
    with b:
        with a:
            pass
    assert sanitizers.leaks(before) == []


@pytest.mark.leaks_ok
def test_lock_order_sanitizer_leaks_ok_honored():
    """An inversion under @pytest.mark.leaks_ok is detectable through
    leaks() but must not fail the test via the autouse fixture — this
    test IS the proof: the fixture sees the violation below and skips
    judgement because of the marker."""
    import sanitizers

    before = sanitizers.snapshot()
    a = sanitizers.tracked_lock("ok-a")
    b = sanitizers.tracked_lock("ok-b")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert any(
        "lock-order inversion" in p for p in sanitizers.leaks(before)
    )


def test_lock_order_tracker_covers_symbol_table_locks():
    """The runtime tracker wraps the same named locks the static symbol
    table discovers (creation-site identity): an engine lock created
    after install is tracked; a lock created by non-package code is the
    real primitive."""
    import threading

    import sanitizers
    from mpi_opt_tpu.health.heartbeat import Heartbeat
    from mpi_opt_tpu.service import leases

    hb = Heartbeat("/tmp/_lo_track_hb.json")
    assert sanitizers.is_tracked(hb._lock)
    assert sanitizers.is_tracked(leases._TOKEN_LOCK)
    assert "heartbeat" in hb._lock.name
    assert not sanitizers.is_tracked(threading.Lock())  # test-frame caller


def test_lock_order_tracked_lock_works_under_condition():
    """threading.Condition over a tracked lock (the StagingEngine
    shape: Condition(self._lock)) — wait/notify round-trips keep the
    held bookkeeping straight."""
    import threading

    import sanitizers

    before = sanitizers.snapshot()
    lk = sanitizers.tracked_lock("cond-lock")
    cond = threading.Condition(lk)
    seen = []

    def waiter():
        with cond:
            while not seen:
                cond.wait(timeout=1.0)

    t = threading.Thread(target=waiter)
    t.start()
    with cond:
        seen.append(1)
        cond.notify_all()
    t.join(timeout=5)
    assert not t.is_alive()
    assert sanitizers.leaks(before) == []

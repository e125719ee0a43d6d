"""Boundary-agreement control plane drills (ISSUE 20).

Under multi-process SPMD every rank-divergent decision — drain from a
one-sided SIGTERM, OOM wave-halving, a stall verdict — must be
unanimous BEFORE the next collective, or the world wedges.
``parallel/coord.py`` makes them unanimous with a filesystem
vote/decide barrier built from the spool's O_EXCL primitives. These
tests drive the protocol three ways:

- UNIT: thread-per-rank worlds over one tmp dir pin the barrier
  semantics (unanimity, signal carry, min-cap reduction, single-use
  epochs, duplicate-vote refusal, the bounded-wait wedge verdict);
- WIRING: the drain gate in ``train.common.launch_boundary`` (a
  locally-seen request must WAIT for the agreed verdict) and the slice
  hook chaining;
- DRILLS: real ``python -m mpi_opt_tpu`` rank subprocesses over a
  shared ``--coord-dir``. The 2-rank drills run ``--no-mesh`` (each
  rank computes locally; the control plane is what is under test — it
  is pure filesystem and identical under a real mesh). The heavyweight
  kill -> wedge-classification -> coordinated-resume drill is
  slow-marked and run by probes/tier1.sh (SPMD_DRILL).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from mpi_opt_tpu.health import shutdown
from mpi_opt_tpu.parallel import coord
from mpi_opt_tpu.parallel.coord import (
    CoordError,
    CoordPlane,
    CoordWedged,
    _decide_drain,
    _decide_min_cap,
)
from mpi_opt_tpu.train.common import launch_boundary
from mpi_opt_tpu.utils import resources
from mpi_opt_tpu.utils.exitcodes import EX_TEMPFAIL


# -- unit: the vote/decide barrier ------------------------------------------


def _world(root, n, fn, epoch=0, timeout_s=30.0):
    """Run ``fn(plane)`` on one thread per rank of an ``n``-rank world
    sharing ``root``; returns the per-rank results, re-raising the first
    rank's exception (SPMD: every rank runs the same host code)."""
    results = [None] * n
    errors = [None] * n

    def run(rank):
        try:
            plane = CoordPlane(
                root, rank, n, epoch=epoch, timeout_s=timeout_s
            )
            results[rank] = fn(plane)
        except BaseException as e:  # re-raised on the test thread
            errors[rank] = e

    threads = [
        threading.Thread(target=run, args=(r,), daemon=True) for r in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    for e in errors:
        if e is not None:
            raise e
    return results


def test_barrier_is_unanimous_with_signal_carry(tmp_path):
    """One rank saw SIGTERM, the other saw nothing: both get the SAME
    drain verdict, carrying the signal name so every rank's
    SweepInterrupted reports the same cause. A second, independent kind
    (min-cap) runs its own ordinal sequence in the same epoch."""

    def ranked(plane):
        vote = (
            {"drain": True, "signal": "SIGTERM", "stage": "b1"}
            if plane.rank == 1
            else {"drain": False, "signal": None, "stage": "b1"}
        )
        drain = plane.agree("drain", vote, _decide_drain)
        cap1 = plane.agree_cap("oom", 0 if plane.rank == 0 else 4)
        cap2 = plane.agree_cap("oom", 2 if plane.rank == 0 else 4)
        return drain, cap1, cap2

    a, b = _world(str(tmp_path / "c"), 2, ranked)
    assert a == b  # unanimity is the whole point
    drain, cap1, cap2 = a
    assert drain == {"drain": True, "signal": "SIGTERM"}
    assert cap1 == 4  # the only positive proposal wins
    assert cap2 == 2  # most constrained rank wins


def test_wave_cap_min_agreement_across_ranks(tmp_path):
    """The sizing door's agreement: heterogeneous per-host budgets
    (rank 0 fits 8, rank 1 only 2) settle on the binding host's cap."""
    caps = _world(
        str(tmp_path / "c"),
        2,
        lambda p: p.agree_cap("wave_cap", 8 if p.rank == 0 else 2),
    )
    assert caps == [2, 2]


def test_epochs_are_single_use(tmp_path):
    root = str(tmp_path / "c")
    plane = CoordPlane(root, 0, 1)
    plane.agree_cap("oom", 3)
    # same (dir, epoch) again: refused — an in-place wipe would race
    # peers still reading the previous attempt's READY
    with pytest.raises(CoordError, match="previous attempt"):
        CoordPlane(root, 0, 1)
    # the supervisor's per-attempt answer: advance the epoch
    fresh = CoordPlane(root, 0, 1, epoch=1)
    assert fresh.agree_cap("oom", 5) == 5


def test_duplicate_vote_is_protocol_error(tmp_path):
    plane = CoordPlane(str(tmp_path / "c"), 0, 1)
    plane.agree_cap("oom", 3)
    plane._seq["oom"] = 0  # two planes sharing one identity, simulated
    with pytest.raises(CoordError, match="duplicate vote"):
        plane.agree_cap("oom", 3)


def test_missing_peer_wedges_within_timeout(tmp_path):
    """Rank 1 never arrives: rank 0's wait is bounded — CoordWedged
    (the in-rank stall verdict) plus a ``rank_wedge`` event, so an
    unsupervised job exits for a coordinated restart instead of
    hanging forever."""
    events = []
    resources.set_observer(lambda e, **f: events.append((e, f)))
    try:
        plane = CoordPlane(str(tmp_path / "c"), 0, 2, timeout_s=0.3)
        t0 = time.monotonic()
        with pytest.raises(CoordWedged, match="peer died or wedged"):
            plane.agree_cap("oom", 4)
        assert time.monotonic() - t0 < 10
    finally:
        resources.clear_observer()
    wedges = [f for e, f in events if e == "rank_wedge"]
    assert len(wedges) == 1
    assert wedges[0]["rank"] == 0 and wedges[0]["world"] == 2
    assert "votes" in wedges[0]["waiting_for"]


def test_world_size_mismatch_refused(tmp_path):
    root = str(tmp_path / "c")
    CoordPlane(root, 0, 2)  # rank 0 announces world=2
    with pytest.raises(CoordError, match="world mismatch"):
        CoordPlane(root, 1, 3)


def test_decide_functions_are_pure_reductions():
    assert _decide_drain([{"drain": False}, {"drain": False}]) == {
        "drain": False,
        "signal": None,
    }
    # first drain-voter's signal is carried, draining without a name ok
    assert _decide_drain(
        [{"drain": True, "signal": None}, {"drain": True, "signal": "SIGINT"}]
    ) == {"drain": True, "signal": "SIGINT"}
    assert _decide_min_cap([{"cap": 0}, {"cap": 0}]) == {"cap": 0}
    assert _decide_min_cap([{"cap": 6}, {"cap": 0}, {"cap": 4}]) == {"cap": 4}


def test_reset_dir_is_the_between_jobs_cleanup(tmp_path):
    root = str(tmp_path / "c")
    CoordPlane(root, 0, 1).agree_cap("oom", 1)
    coord.reset_dir(root)
    assert not os.path.exists(root)
    coord.reset_dir(root)  # idempotent on a missing dir
    # a fresh job may reuse epoch 0 after the wipe
    assert CoordPlane(root, 0, 1).agree_cap("oom", 2) == 2


# -- wiring: the drain gate + hook chain ------------------------------------


def test_unagreed_drain_waits_for_the_boundary_vote(tmp_path):
    """The split-drain hazard: a shutdown request seen locally while the
    plane is active but NOT yet agreed must hold (this rank would drain
    while its peers issue the next collective). The boundary that runs
    the vote drains — and ``at`` carries the agreed boundary label."""
    with shutdown.ShutdownGuard():
        plane = CoordPlane(str(tmp_path / "c"), 0, 1, timeout_s=10)
        coord.activate(plane)
        try:
            assert shutdown.request(source="SIGTERM")
            assert not coord.drain_allowed()
            # no hook chained -> no vote runs -> the gate holds
            launch_boundary("gen 1/4", final=False)
        finally:
            coord.deactivate()
        uninstall = coord.install_hook(plane)
        try:
            with pytest.raises(shutdown.SweepInterrupted) as ei:
                launch_boundary("gen 2/4", final=False)
        finally:
            uninstall()
        assert plane.drain_agreed and ei.value.signal == "SIGTERM"
        # the plane labels multi-process boundaries as boundary phases
        # (launch.py's wedge classifier keys on this shape)
        assert ei.value.at == "boundary:gen 2/4"


def test_install_hook_chains_prior_hook_and_restores_it(tmp_path):
    seen = []
    prev = seen.append
    shutdown.set_slice_hook(prev)
    try:
        plane = CoordPlane(str(tmp_path / "c"), 0, 1, timeout_s=10)
        uninstall = coord.install_hook(plane)
        try:
            assert coord.active_plane() is plane
            shutdown.poll_slice("b1")  # prior hook first, then the tick
            assert seen == ["b1"]
            assert not plane.drain_agreed  # nobody requested: no drain
        finally:
            uninstall()
        assert shutdown.get_slice_hook() is prev
        assert coord.active_plane() is None and coord.drain_allowed()
    finally:
        shutdown.set_slice_hook(None)


def test_resolve_wave_size_no_longer_refuses_multiprocess(monkeypatch):
    """The lifted refusal: pre-ISSUE-20 any multi-process wave run was
    rejected at the sizing door. Now a plane-less multi-process run
    proceeds (homogeneous SPMD ranks derive identical caps from
    identical code), and an active plane min-agrees the cap."""
    import jax

    from mpi_opt_tpu.train.engine import resolve_wave_size

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert resolve_wave_size(None, None, 8, wave_size=4) == 4


def test_resolve_wave_size_agrees_through_active_plane(tmp_path):
    from mpi_opt_tpu.train.engine import resolve_wave_size

    plane = CoordPlane(str(tmp_path / "c"), 0, 1, timeout_s=10)
    coord.activate(plane)
    try:
        # world=1: the agreement is with itself, but it RUNS — the
        # vote/decision files exist with the settled cap
        assert resolve_wave_size(None, None, 8, wave_size=4) == 4
    finally:
        coord.deactivate()
    decisions = [
        f for f in os.listdir(plane.dir) if f.startswith("wave_cap")
        and f.endswith("decision.json")
    ]
    assert len(decisions) == 1
    with open(os.path.join(plane.dir, decisions[0])) as f:
        assert json.load(f) == {"cap": 4}


# -- drills: real rank subprocesses over a shared --coord-dir ---------------


def _rank_argv(rank, n, port, coord_dir, hb):
    return [
        sys.executable, "-m", "mpi_opt_tpu",
        "--workload", "fashion_mlp",
        "--algorithm", "pbt",
        "--fused",
        "--population", "4",
        # many cheap boundaries: post-compile each generation is
        # milliseconds, so a SIGTERM sent after the first beat always
        # finds a NON-final boundary to drain at (a 4-gen sweep can
        # finish before the signal lands — a flake, not a regression)
        "--generations", "64",
        "--steps-per-generation", "1",
        "--gen-chunk", "1",
        "--seed", "0",
        "--no-mesh",
        "--platform", "cpu",
        "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", str(n),
        "--process-id", str(rank),
        "--coord-dir", coord_dir,
        "--coord-epoch", "0",
        "--coord-timeout", "120",
        "--heartbeat-file", hb,
    ]


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_one_sided_sigterm_drains_both_ranks_at_same_boundary(tmp_path):
    """The headline agreement drill: SIGTERM lands on rank 0 ONLY.
    Rank 0 votes drain at its next boundary, rank 1 (which never saw a
    signal) adopts the verdict — both exit 75 reporting the SAME
    boundary and the SAME cause, and the control plane's files show one
    affirmative drain decision at the final ordinal."""
    coord_dir = str(tmp_path / "coord")
    hbs = [str(tmp_path / f"rank{i}.hb") for i in range(2)]
    outs = [str(tmp_path / f"rank{i}.out") for i in range(2)]
    port = _free_port()
    procs, handles = [], []
    try:
        for i in range(2):
            out = open(outs[i], "w")
            err = open(str(tmp_path / f"rank{i}.err"), "w")
            handles += [out, err]
            procs.append(
                subprocess.Popen(
                    _rank_argv(i, 2, port, coord_dir, hbs[i]),
                    stdout=out,
                    stderr=err,
                    cwd="/root/repo",
                )
            )
        # first beat = first boundary passed on both ranks (compile is
        # behind them; the drain vote lands at a LATER boundary)
        deadline = time.time() + 540
        while not all(os.path.exists(h) for h in hbs):
            assert time.time() < deadline, "ranks never reached a boundary"
            for i, p in enumerate(procs):
                assert p.poll() is None, (
                    f"rank {i} died early: "
                    + open(str(tmp_path / f"rank{i}.err")).read()[-2000:]
                )
            time.sleep(0.05)
        procs[0].send_signal(signal.SIGTERM)  # one-sided, rank 0 only
        for p in procs:
            p.wait(timeout=540)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for h in handles:
            h.close()

    errs = [open(str(tmp_path / f"rank{i}.err")).read() for i in range(2)]
    assert [p.returncode for p in procs] == [EX_TEMPFAIL, EX_TEMPFAIL], errs
    summaries = []
    for out in outs:
        lines = [
            json.loads(l)
            for l in open(out).read().splitlines()
            if l.startswith("{") and '"preempted": true' in l
        ]
        assert len(lines) == 1, open(out).read()
        summaries.append(lines[0])
    # same boundary, same cause, on BOTH ranks — including the one the
    # platform never signaled
    assert summaries[0]["at"] == summaries[1]["at"]
    assert summaries[0]["at"].startswith("boundary:")
    assert [s["signal"] for s in summaries] == ["SIGTERM", "SIGTERM"]

    # the plane's ground truth: every drain ordinal before the last
    # decided "keep going", the last decided "drain" — unanimously
    edir = os.path.join(coord_dir, "e0000")
    decisions = sorted(
        f for f in os.listdir(edir)
        if f.startswith("drain.") and f.endswith(".decision.json")
    )
    assert decisions, os.listdir(edir)
    verdicts = [json.load(open(os.path.join(edir, f))) for f in decisions]
    assert [v["drain"] for v in verdicts[:-1]] == [False] * (len(verdicts) - 1)
    assert verdicts[-1]["drain"] is True
    assert verdicts[-1]["signal"] == "SIGTERM"
    last_seq = decisions[-1].split(".")[1]
    votes = {
        f.split(".r")[1][0]: json.load(open(os.path.join(edir, f)))
        for f in os.listdir(edir)
        if f.startswith(f"drain.{last_seq}.r") and f.endswith(".vote.json")
    }
    assert set(votes) == {"0", "1"}
    assert votes["0"]["drain"] is True  # the signaled rank proposed
    assert votes["1"]["drain"] is False  # the peer adopted the verdict


@pytest.mark.slow  # 2 supervised 2-rank jobs + a --term-grace drain: the
# full kill -> wedge -> coordinated-resume arc, 51 s here (2026-09-26;
# it passes). probes/tier1.sh runs it as SPMD_DRILL
# (T1_SKIP_SPMD_DRILL=1 to skip there).
def test_rank_kill_escalates_to_coordinated_resume_record_identical(tmp_path):
    """A rank SIGKILLed mid-wave leaves its survivor frozen in the
    boundary barrier. The supervisor classifies the shape (dead rank +
    survivor in a boundary:* phase -> ``rank_wedge``), TERM-drains the
    survivor within --term-grace, and funds ONE coordinated --resume
    restart — whose ledger is record-identical to an unkilled run's."""
    from test_launch import _run_supervisor, _summary_line

    def args(ledger, kill_marker=None):
        a = [
            "--workload", "fashion_mlp",
            "--algorithm", "pbt",
            "--fused",
            "--population", "4",
            "--generations", "4",
            "--steps-per-generation", "1",
            "--gen-chunk", "1",
            "--seed", "0",
            "--no-mesh",
            "--platform", "cpu",
            "--ledger", ledger,
            "--coord-timeout", "60",
        ]
        if kill_marker is not None:
            a += ["--rank-kill", f"rank=1,at=2,marker={kill_marker}"]
        return a

    # --stall-timeout wires per-rank heartbeats (phase evidence for the
    # wedge classifier) without ever firing; --term-grace bounds how
    # long the wedged survivor may sit in its barrier after TERM
    sup = ("--stall-timeout", "300", "--term-grace", "5",
           "--restart-backoff", "0.1")
    led_ref = str(tmp_path / "ref.jsonl")
    rc, out, err = _run_supervisor(
        2, 0, args(led_ref), str(tmp_path / "logs_ref"), extra=sup,
    )
    assert rc == 0, f"{out}\n{err}"
    ref = _summary_line(out)

    led_kill = str(tmp_path / "kill.jsonl")
    marker = str(tmp_path / "killed.once")
    rc, out, err = _run_supervisor(
        2, 1, args(led_kill, kill_marker=marker),
        str(tmp_path / "logs_kill"), extra=sup,
    )
    assert rc == 0, f"{out}\n{err}"
    assert os.path.exists(marker)  # the injector fired exactly once
    events = [json.loads(l) for l in out.splitlines() if '"event"' in l]
    names = [e["event"] for e in events]
    assert "rank_wedge" in names, names  # the classification, not just a death
    wedge = next(e for e in events if e["event"] == "rank_wedge")
    assert wedge["rank"] == 1 and wedge["survivors"] == [0]
    restart = next(e for e in events if e["event"] == "restart")
    assert restart["wedge"] is True and restart["attempt"] == 1
    got = _summary_line(out)
    # the resumed attempt VERIFIES the pre-kill journal prefix instead
    # of rewriting it — same total boundary coverage, split differently
    got_j, ref_j = got.pop("journal"), ref.pop("journal")
    assert got_j["written"] + got_j["verified"] == ref_j["written"] + ref_j["verified"]
    assert got_j["verified"] > 0  # proof a real resume (not a rerun) happened
    assert got == ref

    from mpi_opt_tpu.ledger import validate_ledger

    assert validate_ledger(led_kill) == []
    keep = ("trial_id", "member", "boundary", "boundary_size", "params",
            "status", "score", "step")

    def records(path):
        with open(path) as f:
            return [
                {k: r.get(k) for k in keep}
                for r in map(json.loads, f.read().splitlines()[1:])
            ]

    assert records(led_kill) == records(led_ref)

"""Sweep-as-a-service: the resident multi-tenant scheduler (ISSUE 7).

The headline invariants under test:

- admission is FIFO within a tenant and fair-share across tenants;
- a time-sliced tenant's ledger is record-identical to a solo CLI run
  (slicing preempts ONLY at natural boundaries through the existing
  graceful-drain path, so it cannot alter results);
- cancel drains at a boundary — nothing killed, nothing quarantined,
  the device freed for the next tenant;
- server SIGTERM parks the active tenant and a restarted server
  continues the queue; a SIGKILL-shaped death (stale ``running``
  status, dead server pid) recovers through the same resume machinery;
- a shape-matching second tenant hits the compiled-program cache
  (counter-based; the CPU-backend form of "tenant N+1 costs dispatch,
  not compile").
"""

import json
import os
import signal
import time

import pytest

from mpi_opt_tpu.cli import main
from mpi_opt_tpu.service import service_main
from mpi_opt_tpu.service import tenants as tstates
from mpi_opt_tpu.service.scheduler import SweepService
from mpi_opt_tpu.service.spool import Spool, SpoolError
from mpi_opt_tpu.utils.metrics import MetricsLogger


def _quad(seed=0, trials=6):
    return [
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", str(trials), "--budget", "3",
        "--workers", "1", "--seed", str(seed),
    ]


FUSED = [
    "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
    "--population", "4", "--generations", "3",
    "--steps-per-generation", "2", "--gen-chunk", "1", "--no-mesh",
    "--seed", "0",
]


def _service(state_dir, **kw):
    kw.setdefault("drain_on_empty", True)
    kw.setdefault("poll_seconds", 0.02)
    kw.setdefault(
        "metrics", MetricsLogger(path=os.path.join(state_dir, "server-metrics.jsonl"))
    )
    return SweepService(str(state_dir), **kw)


def _records(path, fused=False):
    keep = ("trial_id", "params", "status", "score", "step")
    if fused:
        keep += ("member", "boundary", "boundary_size")
    return [
        {k: r[k] for k in keep}
        for r in map(json.loads, open(path).read().splitlines()[1:])
    ]


def _events(state_dir, name):
    path = os.path.join(str(state_dir), "server-metrics.jsonl")
    return [
        r
        for r in map(json.loads, open(path).read().splitlines())
        if r.get("event") == name
    ]


# -- exit codes: one home (satellite) --------------------------------------


def test_exitcodes_single_home():
    from mpi_opt_tpu.health import shutdown
    from mpi_opt_tpu.utils import exitcodes, integrity

    assert exitcodes.EX_TEMPFAIL == 75 and shutdown.EX_TEMPFAIL is exitcodes.EX_TEMPFAIL
    assert exitcodes.EX_DATAERR == 65 and integrity.EX_DATAERR is exitcodes.EX_DATAERR
    assert exitcodes.classify(0) == "ok"
    assert exitcodes.classify(2) == "usage"
    assert exitcodes.classify(65) == "data_error"
    assert exitcodes.classify(69) == "unavailable"
    assert exitcodes.classify(75) == "preempted"
    assert exitcodes.classify(1) == "failure"
    assert exitcodes.classify(137) == "failure"


def test_tenant_state_machine():
    assert tstates.after_slice(0, cancel_requested=False) == tstates.DONE
    assert tstates.after_slice(75, cancel_requested=False) == tstates.PARKED
    assert tstates.after_slice(75, cancel_requested=True) == tstates.CANCELLED
    assert tstates.after_slice(65, cancel_requested=False) == tstates.DATA_ERROR
    assert tstates.after_slice(2, cancel_requested=False) == tstates.FAILED
    assert tstates.after_slice(1, cancel_requested=False) == tstates.FAILED
    assert tstates.PARKED in tstates.RUNNABLE
    assert tstates.DATA_ERROR in tstates.TERMINAL


# -- slice-hook plumbing (health/shutdown.py) ------------------------------


def test_slice_request_is_guard_scoped():
    from mpi_opt_tpu.health import shutdown

    # no guard active: a slice request has nothing to drain
    assert shutdown.request() is False
    with shutdown.ShutdownGuard() as g:
        assert shutdown.request() is True
        assert g.requested and g.signal_name == shutdown.SLICE
        assert shutdown.requested()
    # the request died with its guard — nothing leaks to the next sweep
    assert not shutdown.requested()


def test_real_signal_outranks_slice_label():
    from mpi_opt_tpu.health import shutdown

    shutdown.clear_delivered()
    with shutdown.ShutdownGuard() as g:
        shutdown.request()
        g._handle(signal.SIGTERM, None)
        assert g.signal_name == "SIGTERM"  # platform signal wins the label
    assert shutdown.delivered_signal() == "SIGTERM"
    shutdown.clear_delivered()
    assert shutdown.delivered_signal() is None


def test_poll_slice_hook_lifecycle():
    from mpi_opt_tpu.health import shutdown

    seen = []
    shutdown.poll_slice("nobody listening")  # no hook: no-op
    shutdown.set_slice_hook(seen.append)
    try:
        shutdown.poll_slice("stage a")
    finally:
        shutdown.clear_slice_hook()
    shutdown.poll_slice("after clear")
    assert seen == ["stage a"]


# -- spool clients ---------------------------------------------------------


def test_submit_rejects_server_owned_flags(tmp_path):
    spool = Spool(str(tmp_path))
    with pytest.raises(SpoolError, match="server-owned"):
        spool.submit(["--workload", "quadratic", "--ledger", "x.jsonl"])
    with pytest.raises(SpoolError, match="server-owned"):
        spool.submit(["--workload", "quadratic", "--checkpoint-dir=/tmp/x"])
    # argparse resolves unambiguous abbreviations, so the gate must
    # match prefixes: `--platfor` would reach the slice as --platform
    with pytest.raises(SpoolError, match="server-owned"):
        spool.submit(["--workload", "quadratic", "--platfor", "tpu"])
    # the CLI surface maps it to a usage error
    with pytest.raises(SystemExit) as e:
        service_main(
            ["submit", "--state-dir", str(tmp_path), "--",
             "--workload", "quadratic", "--resume"]
        )
    assert e.value.code == 2


def test_submit_status_cancel_roundtrip(tmp_path, capsys):
    d = str(tmp_path)
    assert service_main(
        ["submit", "--state-dir", d, "--tenant", "alice", "--"] + _quad(0)
    ) == 0
    j1 = json.loads(capsys.readouterr().out)["job"]
    assert service_main(["submit", "--state-dir", d, "--"] + _quad(1)) == 0
    j2 = json.loads(capsys.readouterr().out)["job"]

    assert service_main(["status", "--state-dir", d, "--json"]) == 0
    st = json.loads(capsys.readouterr().out)
    assert st["server"]["alive"] is False
    assert [j["job"] for j in st["jobs"]] == [j1, j2]
    # one label across every surface: submit printed "queued", status
    # must agree (no third "submitted" state outside the state machine)
    assert all(j["state"] == tstates.QUEUED for j in st["jobs"])

    # cancel while queued: terminal immediately, never ran
    assert service_main(["cancel", j2, "--state-dir", d]) == 0
    assert json.loads(capsys.readouterr().out)["state"] == tstates.CANCELLED
    assert service_main(["status", "--state-dir", d, "--json"]) == 0
    st = json.loads(capsys.readouterr().out)
    by_job = {j["job"]: j for j in st["jobs"]}
    assert by_job[j2]["state"] == tstates.CANCELLED
    assert by_job[j1]["state"] == tstates.QUEUED

    with pytest.raises(SystemExit):  # unknown job: usage error
        service_main(["cancel", "job-nope", "--state-dir", d])
    capsys.readouterr()


def test_serve_refuses_second_server_with_same_id(tmp_path):
    """The default server-id deliberately collides: two default-id
    servers refuse each other (preserving one-server-per-spool until
    the operator federates with distinct --server-id values)."""
    from mpi_opt_tpu.service.spool import ServerClaimError

    spool = Spool(str(tmp_path))
    spool.write_server()  # this live process "is" the default server
    with pytest.raises(ServerClaimError, match="federate with a distinct"):
        _service(tmp_path).serve()
    # a DISTINCT id registers fine beside the live default one
    assert spool.register_server("srv-b") is True
    assert {s["server_id"] for s in spool.read_servers()} == {"server", "srv-b"}
    spool.clear_server("srv-b")
    spool.clear_server()


def test_serve_main_masks_only_claim_refusals(tmp_path, monkeypatch, capsys):
    """Exit EX_USAGE is reserved for the one-server-per-spool refusal; a
    genuine server crash must propagate with its traceback, not come out
    usage-shaped."""
    from mpi_opt_tpu.service.scheduler import SweepService
    from mpi_opt_tpu.utils.exitcodes import EX_USAGE

    Spool(str(tmp_path)).write_server()  # live claim -> refusal path
    assert service_main(["serve", "--state-dir", str(tmp_path)]) == EX_USAGE
    assert "already owns server-id" in capsys.readouterr().err
    Spool(str(tmp_path)).clear_server()

    def crash(self):
        raise RuntimeError("scheduler bug")

    monkeypatch.setattr(SweepService, "serve", crash)
    with pytest.raises(RuntimeError, match="scheduler bug"):
        service_main(["serve", "--state-dir", str(tmp_path)])


# -- scheduling ------------------------------------------------------------


def test_fair_share_across_tenants_fifo_within(tmp_path):
    """alice submits two jobs, bob one: the schedule alternates tenant
    NAMES while both are runnable (fewest-slices-first) and keeps
    alice's jobs in submission order."""
    spool = Spool(str(tmp_path))
    a1 = spool.submit(_quad(0, trials=4), tenant="alice")
    a2 = spool.submit(_quad(1, trials=4), tenant="alice")
    b1 = spool.submit(_quad(2, trials=4), tenant="bob")
    assert _service(tmp_path, slice_boundaries=2).serve() == 0
    assert all(
        t.status["state"] == tstates.DONE for t in spool.tenants()
    )
    order = [e["job"] for e in _events(tmp_path, "slice_start")]
    # 4 trials / 2-boundary slices = 2 slices per job. Usage balances
    # LIVE work: names alternate while both tenants hold unfinished
    # jobs (a1,b1,a1), a1's completion retires alice's tally so a2
    # competes fresh (fewest-slices -> a2, then FIFO tiebreak -> a2),
    # and bob's remaining slice closes the schedule. FIFO keeps a1
    # before a2 throughout.
    assert order == [a1, b1, a1, a2, a2, b1]


def test_admission_cap_per_tenant(tmp_path):
    spool = Spool(str(tmp_path))
    jobs = [spool.submit(_quad(s, trials=2), tenant="alice") for s in range(3)]
    svc = _service(tmp_path, slice_boundaries=50, max_active_per_tenant=1)
    assert svc.serve() == 0
    # all complete (the cap throttles concurrency, not total work), and
    # admission order follows submission
    assert [e["job"] for e in _events(tmp_path, "tenant_admit")] == jobs
    assert all(t.status["state"] == tstates.DONE for t in spool.tenants())


# -- the acceptance drill: concurrent tenants, bit-identical ledgers -------


def test_three_tenants_slice_interleaved_ledgers_identical_to_solo(
    tmp_path, capsys
):
    """Three concurrent tenants — two driver sweeps and one fused PBT —
    time-sliced at every boundary (>= 2 preemptions each), finish with
    ledger record-sets identical to their solo CLI runs."""
    d = tmp_path / "svc"
    spool = Spool(str(d))
    specs = {
        spool.submit(_quad(0), tenant="alice"): (_quad(0), False),
        spool.submit(_quad(1), tenant="bob"): (_quad(1), False),
        spool.submit(FUSED, tenant="carol"): (FUSED, True),
    }
    assert _service(d, slice_boundaries=1).serve() == 0

    summary = json.loads(
        open(os.path.join(str(d), "server-metrics.jsonl")).read().splitlines()[-1]
    )
    assert summary["slices"] >= 9 and summary["tenants_done"] == 3

    for job_id, (argv, fused) in specs.items():
        t = spool.tenant(job_id)
        s = t.status
        assert s["state"] == tstates.DONE
        assert s["preemptions"] >= 2, (job_id, s)
        solo = str(tmp_path / f"solo-{job_id}.jsonl")
        assert main(argv + ["--ledger", solo]) == 0
        capsys.readouterr()
        assert _records(t.ledger, fused=fused) == _records(solo, fused=fused), job_id
        # and the journal passes the strict schema gate
        assert main(["report", "--validate", t.ledger]) == 0
        capsys.readouterr()


# -- compiled-program reuse ------------------------------------------------


def test_traced_slice_writes_idle_frac(tmp_path):
    """serve --trace (ISSUE 11): every slice end writes the tenant's
    cumulative device-idle fraction — computed from the tenant's own
    span stream by obs/bubbles.py — into status.json beside the memory
    watermark, so the admission layer can spot the co-residency
    candidates (high-idle tenants) without replaying traces."""
    spool = Spool(str(tmp_path))
    j = spool.submit(FUSED, tenant="alice")
    assert _service(tmp_path, slice_boundaries=2, trace=True).serve() == 0
    st = spool.tenant(j).status
    assert st["state"] == tstates.DONE
    assert isinstance(st.get("idle_frac"), float), st.get("idle_frac")
    assert 0.0 <= st["idle_frac"] <= 1.0
    # untraced server: the field never appears (no stream to judge)
    j2 = spool.submit(FUSED, tenant="bob")
    assert _service(tmp_path, slice_boundaries=2).serve() == 0
    assert "idle_frac" not in spool.tenant(j2).status


def test_program_cache_hit_for_shape_matching_second_tenant(tmp_path):
    """Tenant B submits the same (workload, pop-shape, chunking) as A:
    B's first slice reports a program-cache HIT (its trainers/programs
    were built for A and never rebuilt), and B's setup wall collapses
    to dispatch instead of compile."""
    spool = Spool(str(tmp_path))
    a = spool.submit(FUSED, tenant="alice")
    b = spool.submit(FUSED, tenant="bob")
    assert _service(tmp_path, slice_boundaries=1).serve() == 0
    sa, sb = spool.tenant(a).status, spool.tenant(b).status
    assert sa["state"] == sb["state"] == tstates.DONE
    assert sa["first_slice_program_cache_hit"] is False
    assert sb["first_slice_program_cache_hit"] is True
    assert sb["program_cache"]["hits"] == sb["slices"]
    assert sb["program_cache"]["misses"] == 0
    # the warm tenant's time-to-first-trial is dominated by dispatch,
    # not compile — orders of magnitude apart, so the comparison is
    # timing-safe even on a loaded machine
    assert sb["first_slice_wall_s"] < sa["first_slice_wall_s"]
    summary = json.loads(
        open(os.path.join(str(tmp_path), "server-metrics.jsonl")).read().splitlines()[-1]
    )
    assert summary["program_cache_hits"] > 0
    assert summary["program_cache_misses"] >= 1


# -- cancel ----------------------------------------------------------------


def test_cancel_running_tenant_drains_cleanly(tmp_path, capsys):
    """Cancelling a RUNNING tenant takes effect at its next natural
    boundary: the sweep drains (snapshot + ledger intact — nothing
    quarantined, fsck clean) and the device moves on to the next job."""
    from mpi_opt_tpu.utils.integrity import fsck_main

    spool = Spool(str(tmp_path))
    long_job = spool.submit(_quad(0, trials=40), tenant="alice")
    short_job = spool.submit(_quad(1, trials=4), tenant="bob")

    def cancel_mid_slice(t, stage, n):
        if t.job_id == long_job and n == 3:
            spool.tenant(long_job).request_cancel()

    svc = _service(tmp_path, slice_boundaries=100, on_boundary=cancel_mid_slice)
    assert svc.serve() == 0
    s_long = spool.tenant(long_job).status
    assert s_long["state"] == tstates.CANCELLED
    assert s_long["slices"] == 1
    assert spool.tenant(short_job).status["state"] == tstates.DONE
    # drained, not killed: 3 completed trials journaled, nothing torn
    assert len(_records(spool.tenant(long_job).ledger)) == 3
    assert main(["report", "--validate", spool.tenant(long_job).ledger]) == 0
    capsys.readouterr()
    assert fsck_main([spool.tenant(long_job).ckpt]) == 0
    out = capsys.readouterr().out
    assert "quarantined=0" in out.replace(" ", "") or "corrupt" not in out


# -- server death and recovery ---------------------------------------------


def test_sigterm_drains_active_tenant_and_restart_continues(tmp_path, capsys):
    """A real SIGTERM mid-slice: the ACTIVE tenant drains at its next
    boundary and parks, the server exits 0 and clears its liveness
    file; a restarted server resumes the tenant to completion with a
    ledger identical to a solo run."""
    spool = Spool(str(tmp_path))
    job = spool.submit(_quad(0, trials=8), tenant="alice")
    seen = {"n": 0}

    def kill_mid_slice(t, stage, n):
        seen["n"] += 1
        if seen["n"] == 3:
            os.kill(os.getpid(), signal.SIGTERM)

    svc = _service(tmp_path, slice_boundaries=100, on_boundary=kill_mid_slice)
    assert svc.serve() == 0
    st = spool.tenant(job).status
    assert st["state"] == tstates.PARKED
    assert st["slices"] == 1
    assert spool.read_server() is None  # liveness file cleared on exit
    ends = _events(tmp_path, "slice_end")
    assert ends[-1]["signal"] == "SIGTERM"

    assert _service(tmp_path, slice_boundaries=100).serve() == 0
    st = spool.tenant(job).status
    assert st["state"] == tstates.DONE
    solo = str(tmp_path / "solo.jsonl")
    assert main(_quad(0, trials=8) + ["--ledger", solo]) == 0
    capsys.readouterr()
    assert _records(spool.tenant(job).ledger) == _records(solo)


def test_sigkill_shaped_death_recovers_on_restart(tmp_path, capsys):
    """The SIGKILL shape: a tenant left marked ``running`` behind a
    dead server pid. Restart demotes it to parked and the existing
    verified-snapshot + journal machinery resumes it to the same
    record set a solo run produces."""
    spool = Spool(str(tmp_path))
    job = spool.submit(_quad(0, trials=6), tenant="alice")

    # park the tenant mid-sweep via a drain request at its 2nd boundary
    def drain_mid_slice(t, stage, n):
        if n == 2:
            spool.request_drain()

    assert _service(
        tmp_path, slice_boundaries=100, on_boundary=drain_mid_slice
    ).serve() == 0
    t = spool.tenant(job)
    assert t.status["state"] == tstates.PARKED
    # forge the kill shape: status says running, the registration names
    # a pid that no longer exists, and no (or a dead-holder) lease —
    # the restarted server claims the orphan's lease and resumes it
    t.write_status(dict(t.status, state=tstates.RUNNING))
    spool.write_server()
    srv = spool.read_server()
    srv["pid"] = 2**22 + 7919  # vanishingly unlikely to be alive
    import json as _json

    open(spool.server_path, "w").write(_json.dumps(srv))
    assert spool.server_alive() is False

    assert _service(tmp_path, slice_boundaries=100).serve() == 0
    st = spool.tenant(job).status
    assert st["state"] == tstates.DONE
    assert any(e["job"] == job for e in _events(tmp_path, "tenant_takeover"))
    assert st["takeovers"] == 1
    solo = str(tmp_path / "solo.jsonl")
    assert main(_quad(0, trials=6) + ["--ledger", solo]) == 0
    capsys.readouterr()
    assert _records(spool.tenant(job).ledger) == _records(solo)


def test_sigkill_during_first_slice_resumes_not_fails(tmp_path, capsys):
    """The widest kill window: the server dies during a tenant's FIRST
    slice (slices still 0) after the sweep already journaled records.
    The retry must pass --resume — a fresh invocation would trip the
    CLI's stale-ledger refusal (exit 2) and terminally fail a tenant
    whose durable state is perfectly recoverable."""
    spool = Spool(str(tmp_path))
    job = spool.submit(_quad(0, trials=6), tenant="alice")

    def drain_mid_slice(t, stage, n):
        if n == 2:
            spool.request_drain()

    assert _service(
        tmp_path, slice_boundaries=100, on_boundary=drain_mid_slice
    ).serve() == 0
    t = spool.tenant(job)
    assert t.status["state"] == tstates.PARKED
    assert os.path.getsize(t.ledger) > 0  # durable records exist
    # forge "killed before the first slice_end": running, zero slices
    t.write_status(dict(t.status, state=tstates.RUNNING, slices=0))

    assert _service(tmp_path, slice_boundaries=100).serve() == 0
    st = spool.tenant(job).status
    assert st["state"] == tstates.DONE, st
    solo = str(tmp_path / "solo.jsonl")
    assert main(_quad(0, trials=6) + ["--ledger", solo]) == 0
    capsys.readouterr()
    assert _records(spool.tenant(job).ledger) == _records(solo)


def test_program_cache_commits_only_after_a_real_run(tmp_path):
    """A slice that dies before compiling must not make the next
    same-shape slice report a warm start that never happened."""
    from mpi_opt_tpu.service.programs import ProgramCache

    cache = ProgramCache()
    argv = _quad(0, trials=6)
    key, hit, _ = cache.acquire(argv)
    assert key is not None and hit is False
    # no commit (the slice failed pre-compile): still a miss
    key2, hit2, _ = cache.acquire(argv)
    assert key2 == key and hit2 is False
    cache.commit(key)
    _, hit3, _ = cache.acquire(argv)
    assert hit3 is True
    # chaos programs are never warm (wrappers rebuilt per run): no key
    # to commit — so a chaos slice can't falsely warm-start the
    # fault-free tenant of the same shape, nor report hits itself
    ck, chit, cworkload = cache.acquire(argv + ["--chaos", "exc=0.1,seed=1"])
    assert ck is None and chit is False and cworkload is None


def test_unreadable_job_spec_fails_tenant_not_server(tmp_path):
    """One tenant's unreadable job.json terminal-fails that tenant and
    the server keeps scheduling everyone else."""
    spool = Spool(str(tmp_path))
    bad = spool.submit(_quad(0, trials=6), tenant="alice")
    good = spool.submit(_quad(1, trials=6), tenant="bob")
    svc = _service(tmp_path, slice_boundaries=100)
    svc._admit_pending()
    os.unlink(spool.tenant(bad).job_path)
    assert svc.serve() == 0
    assert spool.tenant(bad).status["state"] == tstates.FAILED
    assert spool.tenant(good).status["state"] == tstates.DONE


def test_workload_construction_failure_fails_tenant_not_server(
    tmp_path, monkeypatch
):
    """A workload whose constructor raises (dataset cache, disk,
    arbitrary user code in get_workload -> cls()) terminal-fails its
    tenant at slice setup. The tenant was still RUNNABLE at that point,
    so an uncontained raise would kill the server with the tenant
    re-picked first by every restarted server: a permanent crash loop
    that takes every other tenant's service down with it."""
    import mpi_opt_tpu.workloads as workloads_mod

    real = workloads_mod.get_workload

    def exploding(name):
        if name == "fashion_mlp":
            raise RuntimeError("dataset cache corrupt")
        return real(name)

    monkeypatch.setattr(workloads_mod, "get_workload", exploding)
    spool = Spool(str(tmp_path))
    bad = spool.submit(FUSED, tenant="alice")
    good = spool.submit(_quad(1, trials=6), tenant="bob")
    svc = _service(tmp_path, slice_boundaries=100)
    assert svc.serve() == 0
    bad_status = spool.tenant(bad).status
    assert bad_status["state"] == tstates.FAILED
    assert "dataset cache corrupt" in bad_status["note"]
    assert spool.tenant(good).status["state"] == tstates.DONE


def test_fair_share_usage_is_session_scoped(tmp_path):
    """Fair-share usage dies with the server: a tenant's long-finished
    history must not starve its NEW job on a restarted server for as
    many slices as the history ever consumed. Live (parked) jobs' slice
    counts DO seed the new session, so in-flight fairness resumes."""
    spool = Spool(str(tmp_path))
    svc = _service(tmp_path, slice_boundaries=100)
    a_new = spool.submit(_quad(0, trials=6), tenant="alice")
    b_new = spool.submit(_quad(1, trials=6), tenant="bob")
    # alice's heavy history: a DONE job with 100 lifetime slices, plus
    # bob's PARKED in-flight job holding 3
    hist = spool.submit(_quad(2, trials=6), tenant="alice")
    svc._admit_pending()
    done = spool.tenant(hist)
    done.write_status(dict(done.status, state=tstates.DONE, slices=100))
    parked = spool.tenant(b_new)
    parked.write_status(dict(parked.status, state=tstates.PARKED, slices=3))

    restarted = _service(tmp_path, slice_boundaries=100)
    # history gone (alice back to her live jobs' 0), live seed kept
    assert restarted._usage.get("alice", 0) == 0
    assert restarted._usage["bob"] == 3
    # alice (0) outranks bob (3): her new job is picked immediately
    # (_pick_next now also ACQUIRES the pick's lease — fleet arbitration)
    picked, lease, takeover_from = restarted._pick_next()
    assert picked.job_id == a_new and lease is not None and takeover_from is None


def test_server_alive_counts_eperm_as_alive(tmp_path, monkeypatch):
    """os.kill EPERM means a LIVE process owned by someone else — the
    one-server-per-spool refusal must still see it on a shared dir."""
    spool = Spool(str(tmp_path))
    spool.write_server()

    def kill_eperm(pid, sig):
        raise PermissionError("not your process")

    monkeypatch.setattr(os, "kill", kill_eperm)
    assert spool.server_alive() is True


def test_read_summary_scoped_to_this_slice(tmp_path):
    """A slice that crashed before printing its summary must not
    inherit the previous slice's from the append-only run.log."""
    from mpi_opt_tpu.service.scheduler import _read_summary

    log = tmp_path / "run.log"
    prior = json.dumps({"best_score": 0.5, "workload": "quadratic"})
    log.write_text(prior + "\n")
    start = os.path.getsize(log)
    with open(log, "a") as f:
        f.write("Traceback (most recent call last):\n  boom\n")
    assert _read_summary(str(log), 0) == json.loads(prior)
    assert _read_summary(str(log), start) is None


def test_register_server_is_atomic_and_breaks_stale_registrations(tmp_path):
    """One-process-per-server-id is an O_EXCL claim, not a
    check-then-write: a live registration refuses peers, a dead pid's
    registration is broken."""
    spool = Spool(str(tmp_path))
    assert spool.register_server() is True
    assert Spool(str(tmp_path)).register_server() is False  # we are alive
    spool.clear_server()
    # stale registration: dead pid
    spool.write_server()
    srv = json.loads(open(spool.server_path).read())
    srv["pid"] = 2**22 + 7919
    open(spool.server_path, "w").write(json.dumps(srv))
    assert spool.register_server() is True
    # refresh is token-checked against THIS process: ours refreshes,
    # and a file rewritten by someone else refuses (the step-down cue)
    assert spool.refresh_server(Spool.DEFAULT_SERVER_ID, takeovers=3) is True
    assert spool.read_server()["takeovers"] == 3
    open(spool.server_path, "w").write(json.dumps(dict(srv, pid_start="999")))
    assert spool.refresh_server(Spool.DEFAULT_SERVER_ID) is False


def test_stale_claim_with_recycled_pid_is_broken(tmp_path):
    """A SIGKILLed server's claim keeps its pid forever — and the
    kernel eventually hands that pid to an unrelated process. A
    pid-existence-only liveness check would then block the spool until
    an operator deleted server.json by hand; the recorded process
    start time tells the incarnations apart."""
    from mpi_opt_tpu.service.spool import _write_json_atomic

    spool = Spool(str(tmp_path))
    spool.write_server()
    info = spool.read_server()
    assert info["pid_start"] is not None  # Linux /proc is available here
    # pid reuse shape: the claim's pid is alive (it is OURS), but the
    # claim was written by a previous incarnation of that pid
    _write_json_atomic(spool.server_path, dict(info, pid_start="12345"))
    assert spool.server_alive() is False
    assert spool.register_server() is True
    spool.clear_server()


def test_serve_rejects_zero_local_devices(tmp_path):
    """serve validates --local-devices through the same pin helper the
    flat CLI uses: a zero count is an immediate usage error, not a
    deferred backend-init crash inside the first tenant's slice."""
    from mpi_opt_tpu.service.client import serve_main

    with pytest.raises(SystemExit) as e:
        serve_main(
            [
                "--state-dir", str(tmp_path),
                "--platform", "cpu",
                "--local-devices", "0",
            ]
        )
    assert e.value.code == 2


def test_admission_tolerates_racing_cancel(tmp_path):
    """A queue file claimed by a concurrent peer surfaces as SpoolError
    (handled by _admit_pending), never FileNotFoundError (which would
    crash the server loop)."""
    spool = Spool(str(tmp_path))
    job = spool.submit(_quad(0, trials=4), tenant="alice")
    qpath = spool.pending_jobs()[0]
    os.unlink(qpath)  # the racing peer took it
    with pytest.raises(SpoolError, match="claimed by a peer"):
        spool.admit(qpath)
    # and a cancel that loses the materialize race still cancels via
    # the tenant-dir fall-through
    job2 = spool.submit(_quad(1, trials=4), tenant="bob")
    q2 = spool.pending_jobs()[0]
    spool.admit(q2)  # "the server" admits first
    assert spool.cancel(job2) == tstates.CANCELLED
    assert spool.tenant(job2).cancel_requested()


def test_drain_subcommand_parks_and_preserves_queue(tmp_path, capsys):
    """`mpi_opt_tpu drain`: the server finishes the active slice,
    parks, and exits; queued jobs stay queued for the next server."""
    spool = Spool(str(tmp_path))
    j1 = spool.submit(_quad(0, trials=8), tenant="alice")
    j2 = spool.submit(_quad(1, trials=4), tenant="bob")

    def drain_early(t, stage, n):
        if n == 1:
            assert service_main(["drain", "--state-dir", str(tmp_path)]) == 0

    assert _service(
        tmp_path, slice_boundaries=100, on_boundary=drain_early
    ).serve() == 0
    capsys.readouterr()
    states = {t.job_id: t.status["state"] for t in spool.tenants()}
    assert states[j1] == tstates.PARKED
    # j2 was admitted-or-queued but never ran; either way it is not lost
    assert states.get(j2, tstates.QUEUED) in (tstates.QUEUED,)
    # restart finishes everything
    assert _service(tmp_path, slice_boundaries=100).serve() == 0
    assert all(t.status["state"] == tstates.DONE for t in spool.tenants())


# -- report over a directory (satellite) -----------------------------------


def test_report_over_service_state_dir(tmp_path, capsys):
    spool = Spool(str(tmp_path))
    spool.submit(_quad(0), tenant="alice")
    spool.submit(_quad(1), tenant="bob")
    assert _service(tmp_path, slice_boundaries=2).serve() == 0

    assert main(["report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert out.count("service:") == 2  # per-tenant status lines
    assert "state=done" in out
    assert "sweep identities: 1" in out  # same workload/algo/space
    assert "quadratic/random: 2 ledger(s), 12 trials" in out

    assert main(["report", str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert len(rep["ledgers"]) == 2
    assert all(r["service"]["state"] == "done" for r in rep["ledgers"])
    assert rep["best"] is not None

    # validate mode expands directories the same way
    assert main(["report", str(tmp_path), "--validate"]) == 0
    capsys.readouterr()

    # an empty directory is a loud audit failure, not a green no-op —
    # and the diagnostic goes to stderr, so --json stdout stays a
    # single machine-parseable object even with a mistyped dir mixed in
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["report", str(empty)]) == 1
    captured = capsys.readouterr()
    assert "no ledgers found" in captured.err
    assert main(["report", str(tmp_path), str(empty), "--json"]) == 1
    captured = capsys.readouterr()
    assert "no ledgers found" in captured.err
    assert len(json.loads(captured.out)["ledgers"]) == 2


def test_report_groups_differing_only_by_space_stay_distinguishable(
    tmp_path, capsys
):
    """Identity is (workload, algorithm, space_hash) but the label shows
    workload/algorithm — two groups split ONLY by a changed search space
    (the exact split the grouping exists to make) must not render as two
    identical lines with no way to tell them apart."""
    import time as time_mod

    def write_ledger(name, space_hash, score):
        header = {
            "kind": "header", "version": 1, "sweep_id": name,
            "created_ts": time_mod.time(),
            "config": {
                "workload": "quadratic", "algorithm": "random",
                "backend": "cpu", "seed": 0, "space_hash": space_hash,
            },
        }
        trial = {
            "kind": "trial", "trial_id": 0, "params": {"x": 0.5},
            "status": "ok", "score": score, "step": 3,
            "ts": time_mod.time(),
        }
        path = tmp_path / f"{name}.jsonl"
        path.write_text(
            json.dumps(header) + "\n" + json.dumps(trial) + "\n"
        )

    write_ledger("old-space", "aaaa1111bbbb", 1.0)
    write_ledger("new-space", "cccc2222dddd", 2.0)
    assert main(["report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "sweep identities: 2" in out
    assert "quadratic/random (space aaaa1111):" in out
    assert "quadratic/random (space cccc2222):" in out


# -- slice exit-shape fidelity (review-round fixes) ------------------------


def test_program_key_splits_on_statically_baked_config():
    """--truncation sizes the jitted exploit's n_cut at trace time and
    --workers shapes the driver path's eval batches: same pop-shape with
    either differing must NOT report a program-cache hit."""
    from mpi_opt_tpu.cli import build_parser
    from mpi_opt_tpu.service.programs import program_key

    base = FUSED + ["--trials", "4"]
    k = program_key(build_parser().parse_args(base))
    assert k == program_key(build_parser().parse_args(list(base)))
    assert k != program_key(
        build_parser().parse_args(base + ["--truncation", "0.5"])
    )
    assert k != program_key(build_parser().parse_args(base + ["--workers", "2"]))


def test_program_key_splits_on_warm_start(tmp_path):
    """Fused TPE sizes its compiled obs ring as n_trials + n_warm: a
    warm-starting tenant recompiles relative to the cold shape-match,
    and priors of different length differ again — neither may report a
    program-cache hit against the other."""
    from mpi_opt_tpu.cli import build_parser
    from mpi_opt_tpu.service.programs import program_key

    prior = tmp_path / "prior.jsonl"
    prior.write_text("x\n")
    base = FUSED + ["--trials", "4"]
    warm = base + ["--warm-start", str(prior)]
    cold_key = program_key(build_parser().parse_args(base))
    warm_key = program_key(build_parser().parse_args(warm))
    assert cold_key != warm_key
    assert warm_key == program_key(build_parser().parse_args(list(warm)))
    prior.write_text("x\ny\n")  # a longer prior = a different obs ring
    assert warm_key != program_key(build_parser().parse_args(list(warm)))


def test_slice_systemexit_string_fails_with_message_in_log(tmp_path):
    """cli.py's bare `raise SystemExit("msg")` refusals must classify
    like the subprocess world (rc 1) and leave the message in run.log,
    not vanish with the exception."""
    spool = Spool(str(tmp_path))
    # --no-mesh + --n-data 2 trips build_mesh's SystemExit(str) refusal
    # (the fused path calls build_mesh; the cpu driver path does not)
    spool.submit(FUSED + ["--n-data", "2"], tenant="a")
    assert _service(tmp_path).serve() == 0
    (t,) = spool.tenants()
    assert t.status["state"] == tstates.FAILED
    assert t.status["rc_history"] == [1]
    assert "--no-mesh contradicts" in open(t.log).read()


def test_slice_systemexit_none_is_success(tmp_path, monkeypatch):
    """SystemExit(None) is Python's success convention — a sweep exiting
    that way completed, and the tenant must land `done`, not `failed`."""
    import mpi_opt_tpu.cli as cli_mod

    spool = Spool(str(tmp_path))
    spool.submit(_quad(), tenant="a")
    monkeypatch.setattr(
        cli_mod, "main", lambda argv, _workload=None: (_ for _ in ()).throw(
            SystemExit(None)
        )
    )
    assert _service(tmp_path).serve() == 0
    (t,) = spool.tenants()
    assert t.status["state"] == tstates.DONE
    assert t.status["rc_history"] == [0]


def test_malformed_argv_reports_in_tenant_log_not_server_console(
    tmp_path, capsys
):
    """The program cache's probe parse is silent; the slice's own parse
    re-fails under the log redirect, so the usage text is attributable
    to the tenant (run.log), not interleaved into the server console."""
    spool = Spool(str(tmp_path))
    spool.submit(["--workload", "quadratic", "--algorithm", "nosuch"], tenant="a")
    assert _service(tmp_path).serve() == 0
    (t,) = spool.tenants()
    assert t.status["state"] == tstates.FAILED
    assert t.status["rc_history"] == [2]
    assert "invalid choice" in open(t.log).read()
    captured = capsys.readouterr()
    assert "invalid choice" not in captured.err
    assert "invalid choice" not in captured.out


def test_signal_between_loop_check_and_slice_never_burns_a_quantum(tmp_path):
    """A real signal landing in the window between the serve loop's
    shutdown check and the slice (spool scans) hits the SERVER guard;
    the slice must notice BEFORE running the tenant — not burn a full
    quantum (potentially minutes) while the platform's SIGKILL grace
    window ticks down."""
    from mpi_opt_tpu.health import shutdown

    from mpi_opt_tpu.service import leases

    spool = Spool(str(tmp_path))
    spool.submit(_quad(), tenant="a")
    svc = _service(tmp_path)
    (qpath,) = spool.pending_jobs()
    t = spool.admit(qpath)
    lease = leases.acquire(t.lease, svc.ident, svc.lease_ttl)
    shutdown.clear_delivered()
    try:
        with shutdown.ShutdownGuard() as g:  # the server's guard
            g._handle(signal.SIGTERM, None)  # the race: signal pre-slice
            assert svc._run_slice(t, lease) == "SIGTERM"
        # the tenant never ran: no slice accounting, still runnable
        assert t.status["state"] == tstates.QUEUED
        assert int(t.status.get("slices") or 0) == 0
    finally:
        shutdown.clear_delivered()


def test_signal_during_slice_parks_at_first_boundary(tmp_path):
    """A real delivery the tenant's own guard never saw (it landed on
    the server guard in the install sliver) still parks the tenant at
    its FIRST boundary via the hook's delivered_signal() check."""
    from mpi_opt_tpu.health import shutdown

    spool = Spool(str(tmp_path))
    spool.submit(_quad(0, trials=8), tenant="a")

    def fake_delivery(t, stage, n):
        if n == 1:
            shutdown._DELIVERED = "SIGTERM"  # white-box: the sliver shape

    svc = _service(tmp_path, slice_boundaries=50, on_boundary=fake_delivery)
    try:
        assert svc.serve() == 0
        (t,) = spool.tenants()
        # parked after ONE boundary, nowhere near the 50-boundary budget
        assert t.status["state"] == tstates.PARKED
        assert t.status["boundaries"] <= 2
    finally:
        shutdown.clear_delivered()


def test_help_tenant_never_leaks_into_server_stdout(tmp_path, capsys):
    """A tenant argv containing --help must not print multi-KB help text
    to the server's stdout (its JSONL metrics stream) via the program
    cache's probe parse — the text belongs in the tenant's run.log."""
    spool = Spool(str(tmp_path))
    spool.submit(["--help"], tenant="a")
    assert _service(tmp_path).serve() == 0
    (t,) = spool.tenants()
    assert "--workload" in open(t.log).read()  # help text, attributed
    captured = capsys.readouterr()
    assert "usage:" not in captured.out and "usage:" not in captured.err


def test_fair_share_usage_retires_with_the_job(tmp_path):
    """On a long-lived server, a tenant whose 50-slice job just finished
    must not have its NEXT submission starved for 50 slices: terminal
    jobs retire their slice count from the in-session tally."""
    spool = Spool(str(tmp_path))
    spool.submit(_quad(0, trials=6), tenant="alice")
    svc = _service(tmp_path, slice_boundaries=2)
    assert svc.serve() == 0
    (t,) = spool.tenants()
    assert t.status["state"] == tstates.DONE
    assert int(t.status["slices"]) >= 2  # multi-slice history existed
    assert svc._usage.get("alice", 0) == 0  # ...and was retired


def test_readonly_clients_refuse_a_nonexistent_spool(tmp_path):
    """status/cancel/drain must not fabricate an empty spool at a
    mistyped --state-dir and report healthy-looking answers about it."""
    missing = str(tmp_path / "svc_prod_typo")
    for argv in (
        ["status", "--state-dir", missing],
        ["cancel", "some-job", "--state-dir", missing],
        ["drain", "--state-dir", missing],
    ):
        with pytest.raises(SystemExit) as e:
            service_main(argv)
        assert e.value.code == 2
        assert not os.path.exists(missing)  # nothing fabricated
    # submit still queue-aheads (documented): it CREATES the spool
    spool_dir = str(tmp_path / "fresh")
    assert service_main(
        ["submit", "--state-dir", spool_dir, "--tenant", "a", "--"] + _quad()
    ) == 0
    assert os.path.isdir(os.path.join(spool_dir, "queue"))


# -- persistent compile cache (satellite) ----------------------------------


def test_compile_cache_env_wiring(tmp_path, monkeypatch):
    """utils/compile_cache.py is the one place the cache is placed:
    where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and no
    directory is set in code; where it is not, the fixed directory
    inside the checkout."""
    import jax

    from mpi_opt_tpu.utils.compile_cache import DEFAULT_DIR, wire_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        cache = str(tmp_path / "xla-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
        assert wire_compile_cache() == cache  # placed from outside ...
        assert jax.config.jax_compilation_cache_dir == prev  # ... config untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert wire_compile_cache() == DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert DEFAULT_DIR == os.path.join(repo, ".jax_cache")  # fixed, in-checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_spawn_ranks_propagate_cache_env(tmp_path, monkeypatch):
    """launch.py rank processes INHERIT the environment (Popen env=None),
    so JAX_COMPILATION_CACHE_DIR set on the supervisor reaches every
    rank of every restart/resume attempt without an explicit copy."""
    import mpi_opt_tpu.launch as launch_mod

    cache = str(tmp_path / "xla-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    captured = {}

    class FakeProc:
        def poll(self):
            return None

        def kill(self):
            pass

        def wait(self):
            pass

    def fake_popen(argv, stdout=None, stderr=None, text=None, env=None):
        captured["env"] = env
        return FakeProc()

    monkeypatch.setattr(launch_mod.subprocess, "Popen", fake_popen)
    procs = launch_mod._spawn_ranks(1, ["--workload", "quadratic"], str(tmp_path))
    for _p, out, err in procs:
        out.close()
        err.close()
    # env=None IS the propagation mechanism: the child shares os.environ,
    # where the cache dir is already set
    assert captured["env"] is None
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == cache


# -- priority / deadline scheduling (ISSUE 16) ----------------------------


def test_pick_next_priority_class_outranks_fair_share(tmp_path):
    """A higher --priority job is picked first even when fair share
    favors the other tenant (priority is a CLASS above the usage key,
    not a tiebreak inside it)."""
    spool = Spool(str(tmp_path))
    lo = spool.submit(_quad(0), tenant="cheap", priority=0)
    hi = spool.submit(_quad(1), tenant="busy", priority=5)
    svc = _service(tmp_path)
    svc._admit_pending()
    svc._usage["busy"] = 50  # fair share alone would pick "cheap"
    picked, lease, _ = svc._pick_next()
    assert picked.job_id == hi and lease is not None
    assert spool.tenant(lo).status["priority"] == 0
    assert spool.tenant(hi).status["priority"] == 5


def test_pick_next_earliest_deadline_orders_within_class(tmp_path):
    """Inside one priority class, earliest deadline wins and
    deadline-less jobs sort last — urgency and importance stay
    independent axes."""
    spool = Spool(str(tmp_path))
    nodl = spool.submit(_quad(0), tenant="a")
    late = spool.submit(_quad(1), tenant="b", deadline_ts=time.time() + 3600)
    soon = spool.submit(_quad(2), tenant="c", deadline_ts=time.time() + 60)
    svc = _service(tmp_path)
    svc._admit_pending()
    picked, _, _ = svc._pick_next()
    assert picked.job_id == soon
    st = spool.tenant(soon).status
    assert st["deadline_ts"] == pytest.approx(
        spool.tenant(soon).job["deadline_ts"]
    )
    assert spool.tenant(nodl).status["deadline_ts"] is None
    assert spool.tenant(late).status["deadline_ts"] > st["deadline_ts"]


def test_starvation_floor_promotes_a_waiting_job(tmp_path):
    """A prio-0 job that has waited N floors gains N effective classes,
    so a saturating high-priority stream delays it by a bounded number
    of floors, never forever."""
    spool = Spool(str(tmp_path))
    old = spool.submit(_quad(0), tenant="patient", priority=0)
    fresh = spool.submit(_quad(1), tenant="vip", priority=2)
    svc = _service(tmp_path, starvation_floor_s=0.1)
    svc._admit_pending()
    t = spool.tenant(old)
    t.write_status(dict(t.status, submitted_ts=time.time() - 1.0))
    # waited ~10 floors: effective priority ~10 > the fresh job's 2
    picked, _, _ = svc._pick_next()
    assert picked.job_id == old
    with pytest.raises(ValueError):
        _service(tmp_path, starvation_floor_s=0.0)


def test_submit_cli_priority_deadline_surfaced_in_status(tmp_path, capsys):
    d = str(tmp_path)
    assert service_main(
        ["submit", "--state-dir", d, "--priority", "3", "--deadline", "120",
         "--"] + _quad(0)
    ) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["priority"] == 3
    assert out["deadline_ts"] == pytest.approx(time.time() + 120, abs=30)
    assert service_main(["status", "--state-dir", d, "--json"]) == 0
    st = json.loads(capsys.readouterr().out)
    assert st["jobs"][0]["priority"] == 3
    assert st["jobs"][0]["deadline_ts"] == pytest.approx(out["deadline_ts"])
    # the text rendering names both (the operator's at-a-glance view)
    assert service_main(["status", "--state-dir", d]) == 0
    text = capsys.readouterr().out
    assert "prio=3" in text and "deadline=" in text

"""``chip_smoke.py``'s contract, as far as a machine without a chip can
hold it: it fails here, it fails alone, it takes ``--chips 4``, and its
parent stays off jax (one process per chip: a parent that has touched
jax holds the chip its children need)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300,
    )


def _module():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def _reports_ok(stdout: str) -> bool:
    lines = [l for l in stdout.splitlines() if l.strip()]
    try:
        return bool(lines) and json.loads(lines[-1]).get("ok") is True
    except (ValueError, AttributeError):
        return False


def test_without_an_accelerator_it_exits_nonzero_and_prints_no_result():
    r = _run(SCRIPT, REPO)
    assert r.returncode != 0
    assert not _reports_ok(r.stdout), r.stdout
    assert "not a TPU" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0
    assert not _reports_ok(r.stdout), r.stdout


def test_parser_takes_chips_4_and_nothing_else_but_1():
    parser = _module().build_parser()
    assert parser.parse_args([]).chips == 1  # as the driver runs it
    assert parser.parse_args(["--chips", "4"]).chips == 4
    with pytest.raises(SystemExit):
        parser.parse_args(["--chips", "2"])


def test_importing_the_module_imports_neither_jax_nor_the_package():
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
        "bad = [m for m in ('jax', 'jaxlib', 'mpi_opt_tpu') if m in sys.modules]; "
        "sys.exit(f'imported {bad}' if bad else 0)"
    )
    r = subprocess.run(
        [sys.executable, "-c", code, REPO], capture_output=True, text=True, timeout=60
    )
    assert r.returncode == 0, r.stderr


def _journal(scores0, params1, scores1=None):
    """Two boundaries of a 4-member fused journal."""
    recs = []
    for b, (scores, params) in enumerate(
        [(scores0, [{"lr": 0.1 * m} for m in range(4)]), (scores1 or [0.5] * 4, params1)]
    ):
        for m in range(4):
            recs.append(
                {"trial_id": 4 * b + m, "member": m, "boundary": b, "status": "ok",
                 "step": 10 * (b + 1), "params": params[m], "score": scores[m]}
            )
    return recs


_P1 = [{"lr": 1.0}, {"lr": 2.0}, {"lr": 3.0}, {"lr": 4.0}]
_ROW = 1 / 2048


@pytest.mark.parametrize(
    "theirs,problem",
    [
        (_journal([0.25] * 4, _P1), None),  # equal record for record
        # one row of one member's score, and the exploit flip it explains
        (_journal([0.25 + _ROW, 0.25, 0.25, 0.25], [{"lr": 9.0}] + _P1[1:]), None),
        (_journal([0.25] * 4, [{"lr": 9.0}] + _P1[1:]), "explain at most 0"),
        (_journal([0.25 + 11 * _ROW, 0.25, 0.25, 0.25], _P1), "scores differ by up to"),
    ],
)
def test_equivalence_of_two_compilations_journals(theirs, problem):
    chip_smoke = _module()
    ours = _journal([0.25] * 4, _P1)
    if problem is None:
        assert "boundary 1" in chip_smoke.check_equivalent("t", ours, theirs)
    else:
        with pytest.raises(chip_smoke.Failed, match=problem):
            chip_smoke.check_equivalent("t", ours, theirs)

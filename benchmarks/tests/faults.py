"""Faults planted under the timed path, for test_control.py.

Each returns a context manager that breaks the program underneath the
harness: the run then has to come out with `correct` false.
"""

import contextlib


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    """A step that returns its state unchanged."""
    from mpi_opt_tpu.train.population import PopulationTrainer

    real = PopulationTrainer._member_update

    def update(self, params, momentum, step, hp, key, bx, by):
        _, _, _, loss = real(self, params, momentum, step, hp, key, bx, by)
        return params, momentum, step + 1, loss

    return _patched(PopulationTrainer, "_member_update", update)


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    from mpi_opt_tpu.train.population import PopulationTrainer

    real = PopulationTrainer._member_loss

    def loss(self, params, hp, key, bx, by):
        half = bx.shape[0] // 2
        return real(self, params, hp, key, bx[:half], by[:half])

    return _patched(PopulationTrainer, "_member_loss", loss)


def altered_scores():
    """Answers altered where they are produced: every journaled score
    is another member's (the vector reversed on its way to the ledger)."""
    from mpi_opt_tpu.ledger.fused import FusedJournal

    real = FusedJournal.record_boundary

    def record(self, b_local, members, units, scores, step, scores_mo=None):
        return real(self, b_local, members, units, scores[::-1], step, scores_mo=scores_mo)

    return _patched(FusedJournal, "record_boundary", record)


def altered_rows():
    """Hyperparameters altered where they are journaled: every unit row
    moved by a hundredth."""
    from mpi_opt_tpu.ledger.fused import FusedJournal

    real = FusedJournal.record_boundary

    def record(self, b_local, members, units, scores, step, scores_mo=None):
        return real(self, b_local, members, units * 0.99, scores, step, scores_mo=scores_mo)

    return _patched(FusedJournal, "record_boundary", record)


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "altered_scores": altered_scores,
    "altered_rows": altered_rows,
}

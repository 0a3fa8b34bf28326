"""A run driven with the timed path broken underneath comes out not
correct: once for each fault a one-chip training cell can have (a
server step that leaves its state unchanged; half of the cohort left out
with the mean taken over the rest; one client's answer altered where it
is produced). The harness's look for a chip is skipped (CPU, tiny
size); everything else is the run."""
from __future__ import annotations

import pytest

from fedbench import rehearsal


def _unchanged(orig):
    def apply(self, deltas, weights, **kw):
        params, opt_state = self.params, self.opt_state
        orig(self, deltas, weights, **kw)
        self.params, self.opt_state = params, opt_state
    return apply


def _half(orig):
    def apply(self, deltas, weights, **kw):
        h = max(1, len(deltas) // 2)
        if kw.get("staleness") is not None:
            kw["staleness"] = kw["staleness"][:h]
        orig(self, deltas[:h], weights[:h], **kw)
    return apply


def _altered(orig):
    """Negate the first client's delta as the client update returns it."""
    def deltas(self, *a, **kw):
        out, w = orig(self, *a, **kw)
        return [{k: -v for k, v in out[0].items()}] + list(out[1:]), w

    def delta(self, *a, **kw):
        out, w = orig(self, *a, **kw)
        return {k: -v for k, v in out.items()}, w
    return deltas if orig.__name__ == "client_deltas" else delta


FAULTS = [_unchanged, _half, _altered]


def broken_run_is_not_correct(cell, fault, monkeypatch):
    from repro.federated.real import RealLearner
    names = ("client_deltas", "client_delta") if fault is _altered \
        else ("apply",)
    for name in names:
        monkeypatch.setattr(RealLearner, name,
                            fault(getattr(RealLearner, name)))
    line = rehearsal.rehearse(rehearsal.tiny_cell(cell))
    assert line["correct"] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    broken_run_is_not_correct("charlm-sync", fault, monkeypatch)


def test_a_cohort_program_wrong_at_one_size_alone_is_not_correct(
        monkeypatch):
    """A cohort update that is wrong only at a size the checked updates
    never run (its last client's answer negated) is caught by the answers
    the warm-up keeps of every size of the schedule."""
    from fedbench import harness
    from repro.federated.real import RealLearner
    replay, orig = harness.replay, RealLearner.client_deltas

    def deltas(self, ids, *a, **kw):
        out, w = orig(self, ids, *a, **kw)
        if len(ids) == 3:
            out = list(out[:-1]) + [{k: -v for k, v in out[-1].items()}]
        return out, w

    monkeypatch.setattr(harness, "replay",
                        lambda *a: replay(*a) + [(3, 0.0)])
    monkeypatch.setattr(RealLearner, "client_deltas", deltas)
    line = rehearsal.rehearse(rehearsal.tiny_cell("charlm-sync"))
    assert line["correct"] is False
    assert line["checks"]["client_delta_gap"]["value"] > 1.0

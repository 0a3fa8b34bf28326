"""The server step takes the client deltas from the device copies the
client programs made, wherever the caller passes back the learner's own
host rows (once it has been seen to do so), and uploads any other delta;
on the CPU at a tiny charlm size.
"""
import dataclasses
import gc
import weakref

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs import FederatedConfig, RunConfig, get_config, reduced
from repro.data import FederatedDataset
from repro.federated.real import RealLearner

IDS = [3, 5, 7, 9]


@pytest.fixture(autouse=True)
def _tracer_on():
    spans.reset()
    spans.enable()
    yield
    spans.disable()
    spans.reset()


def _learner(mode: str = "sync", compression: str = "none",
             seed: int = 5) -> RealLearner:
    cfg = dataclasses.replace(
        reduced(get_config("paper-charlm"), layers=1, d_model=32, d_ff=32,
                vocab=128), lstm_hidden=32, max_context=8)
    ds = FederatedDataset(vocab_size=cfg.vocab_size, seq_len=8,
                          char_vocab=cfg.char_vocab,
                          max_word_len=cfg.max_word_len)
    fed = FederatedConfig(mode=mode, concurrency=4, aggregation_goal=4,
                          client_lr=0.1, client_batch_size=4,
                          staleness_cap=4, compression=compression,
                          quant_block=32)
    return RealLearner(cfg, fed, RunConfig(max_rounds=2), ds,
                       max_client_steps=2, seed=seed)


def _copy(d):
    return {k: v.copy() for k, v in d.items()}


def _counts():
    c = spans.snapshot()["counters"]
    return (c.get("server.deltas_resident", 0),
            c.get("server.deltas_uploaded", 0))


def _state(lr: RealLearner):
    return jax.tree_util.tree_leaves(
        jax.device_get((lr.params, lr.opt_state)))


def _assert_same_state(a: RealLearner, b: RealLearner) -> None:
    for x, y in zip(_state(a), _state(b), strict=True):
        np.testing.assert_array_equal(x, y)


def _returning(lr: RealLearner) -> RealLearner:
    """The learner after one server step from its own rows, which turns
    the keeping of device copies on."""
    if lr.fed.mode == "sync":
        lr.apply(*lr.client_deltas(IDS), n_contributors=len(IDS))
    else:
        d, w = lr.client_delta(3)
        lr.apply([d], [w], n_contributors=1, staleness=[0])
    return lr


def _sync(lr, rows_as):
    """Three updates; returns the deltas applied in each."""
    for _ in range(3):
        rows, w = lr.client_deltas(IDS)
        lr.apply([rows_as(r) for r in rows], w, n_contributors=len(rows))
    return [len(IDS)] * 3


def _fedbuff(lr, rows_as):
    """Version 0 -> 1 from one client, then 1 -> 2 from two clients
    trained from version 0 (stale by 1) and one from version 1."""
    d, w = lr.client_delta(3)
    lr.apply([rows_as(d)], [w], n_contributors=1, staleness=[0])
    out = [lr.client_delta(c, v) for c, v in ((5, 0), (7, 1), (9, 0))]
    lr.apply([rows_as(d) for d, _ in out], [w for _, w in out],
             n_contributors=3, staleness=[1, 0, 1])
    return [1, 3]


@pytest.mark.parametrize("mode,compression,run", [
    ("sync", "none", _sync), ("async", "none", _fedbuff),
    ("sync", "int8", _sync)], ids=["sync", "fedbuff", "int8"])
def test_resident_and_uploaded_deltas_give_the_same_state_bit_for_bit(
        mode, compression, run):
    """The first update passes the learner's own rows back before any
    copy is kept, so it uploads them; every later one takes them from the
    device."""
    kept, copied = _learner(mode, compression), _learner(mode, compression)
    n = run(kept, lambda r: r)
    assert _counts() == (sum(n[1:]), n[0])
    spans.reset()
    assert run(copied, _copy) == n
    assert _counts() == (0, sum(n))
    _assert_same_state(kept, copied)


def _negated(rows, w):
    return [{k: -v for k, v in rows[0].items()}] + rows[1:], w, (3, 1)


def _new_dicts(rows, w):
    return [dict(r) for r in rows], w, (4, 0)


def _prefix(rows, w):
    return rows[:2], w[:2], (2, 0)


def _reordered_subset(rows, w):
    pick = [3, 0, 2]
    return [rows[i] for i in pick], [w[i] for i in pick], (3, 0)


def _twice_and_uploaded(rows, w):
    return ([rows[1], _copy(rows[2]), rows[1]], [w[1], w[2], w[3]], (2, 1))


@pytest.mark.parametrize("mode", ["sync", "async"])
@pytest.mark.parametrize("passed", [_negated, _new_dicts, _prefix,
                                    _reordered_subset, _twice_and_uploaded])
def test_each_delta_is_aggregated_exactly_as_passed(mode, passed):
    """Sync passes rows of one kept cohort, FedBuff single clients' rows;
    the reference learner uploads copies of the same deltas."""
    lr, ref = _returning(_learner(mode)), _returning(_learner(mode))
    for x in (lr, ref):
        if mode == "sync":
            rows, w = x.client_deltas(IDS)
        else:
            rows, w = map(list, zip(*(x.client_delta(c) for c in IDS)))
        deltas, weights, counts = passed(rows, w)
        if x is lr:
            spans.reset()
            lr.apply(deltas, weights, n_contributors=len(deltas))
            assert _counts() == counts
        else:
            ref.apply([_copy(d) for d in deltas], weights,
                      n_contributors=len(deltas))
    _assert_same_state(lr, ref)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_host_rows_are_read_only(mode):
    lr = _learner(mode)
    row = lr.client_deltas(IDS)[0][1] if mode == "sync" \
        else lr.client_delta(3)[0]
    assert all(not v.flags.writeable for v in row.values())
    with pytest.raises(ValueError):
        row[lr._lead] += 1.0


def _kept_trees(lr: RealLearner) -> set:
    return {id(t) for _, t, _ in lr._rows.values() if t is not None}


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_no_copy_is_kept_until_a_caller_passes_rows_back(mode):
    lr = _learner(mode)
    for _ in range(2):
        if mode == "sync":
            lr.client_deltas(IDS)
        else:
            lr.client_delta(3)
    assert lr._rows and not _kept_trees(lr)
    _returning(lr)
    if mode == "sync":
        lr.client_deltas(IDS)
    else:
        lr.client_delta(3)
    assert len(_kept_trees(lr)) == 1


def test_one_cohort_stays_on_the_device_at_most_and_none_after_apply():
    lr = _returning(_learner())
    kept_at_dispatch = []
    program = lr._vmapped_update

    def update(*a):
        kept_at_dispatch.append(len(_kept_trees(lr)))
        return program(*a)

    lr._vmapped_update = update
    lr.client_deltas(IDS)
    first = [weakref.ref(t[lr._lead]) for _, t, _ in lr._rows.values()]
    rows, w = lr.client_deltas(IDS)           # no apply in between
    gc.collect()
    assert kept_at_dispatch == [0, 0]
    assert all(r() is None for r in first)
    assert len(_kept_trees(lr)) == 1
    lr.apply(rows, w, n_contributors=len(rows))
    assert lr._rows == {}


def test_counters_read_a_whole_cohort_resident_and_an_altered_row_uploaded():
    lr = _learner()
    ids = list(range(100, 132))
    lr.apply(*lr.client_deltas(ids), n_contributors=32)
    assert _counts() == (0, 32)      # no copy kept before rows came back
    spans.reset()
    lr.apply(*lr.client_deltas(ids), n_contributors=32)
    assert _counts() == (32, 0)
    spans.reset()
    fb = _returning(_learner("async"))
    spans.reset()
    d, w = fb.client_delta(3)
    fb.apply([{k: -v for k, v in d.items()}], [w], n_contributors=1,
             staleness=[0])
    assert _counts() == (0, 1)
    assert set(spans.snapshot()["counters"]) <= set(spans.COUNTERS)

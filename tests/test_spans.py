"""The program's spans and counters (``repro.spans``) at the real learner's
host<->device boundaries, on the CPU at a tiny charlm size."""
import dataclasses
import glob
import json
import os
import tempfile

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs import FederatedConfig, RunConfig, get_config, reduced
from repro.data import FederatedDataset
from repro.federated.real import RealLearner

BATCH, STEPS = 4, 2
SYNC_IDS = [3, 5, 7]


@pytest.fixture(autouse=True)
def _tracer_off():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


def _learner(mode: str = "sync", seed: int = 0) -> RealLearner:
    cfg = dataclasses.replace(
        reduced(get_config("paper-charlm"), layers=1, d_model=32, d_ff=32,
                vocab=128), lstm_hidden=32, max_context=8)
    ds = FederatedDataset(vocab_size=cfg.vocab_size, seq_len=8,
                          char_vocab=cfg.char_vocab,
                          max_word_len=cfg.max_word_len)
    fed = FederatedConfig(mode=mode, concurrency=3, aggregation_goal=3,
                          client_lr=0.1, client_batch_size=BATCH,
                          staleness_cap=4)
    return RealLearner(cfg, fed, RunConfig(max_rounds=2), ds,
                       max_client_steps=STEPS, seed=seed)


def _sync_round(lr: RealLearner) -> None:
    deltas, weights = lr.client_deltas(SYNC_IDS)
    lr.apply(deltas, weights, n_contributors=len(deltas))
    lr.eval_perplexity()


def _fedbuff(lr: RealLearner) -> None:
    """Version 0 -> 1 from one fresh client, then an update from a stale
    client (trained from version 0) and a fresh one."""
    d, w = lr.client_delta(3)
    lr.apply([d], [w], n_contributors=1, staleness=[0])
    d0, w0 = lr.client_delta(5, 0)
    d1, w1 = lr.client_delta(7, 1)
    lr.apply([d0, d1], [w0, w1], n_contributors=2, staleness=[1, 0])
    lr.eval_perplexity()


def _records():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "spans.jsonl")
        n = spans.dump(path)
        with open(path) as f:
            recs = [json.loads(line) for line in f]
    assert n == len(recs)
    return recs


def _param_bytes(lr: RealLearner) -> int:
    return sum(int(v.nbytes) for v in jax.tree_util.tree_leaves(lr.params))


def test_off_records_nothing():
    assert spans.span("client.pack") is spans.span("server.update")
    _sync_round(_learner("sync"))
    _fedbuff(_learner("async"))
    spans.count("client.rows_real", 5)
    assert spans.snapshot() == {"spans": {}, "counters": {}}
    assert _records() == []


@pytest.mark.parametrize("mode,run", [("sync", _sync_round),
                                      ("async", _fedbuff)])
def test_params_are_equal_bit_for_bit_with_tracing_on_and_off(mode, run):
    off, on = _learner(mode, seed=11), _learner(mode, seed=11)
    run(off)
    spans.enable()
    run(on)
    spans.disable()
    assert spans.snapshot()["spans"]
    a, b = jax.device_get(off.params), jax.device_get(on.params)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_each_span_is_recorded_once_per_call_with_its_parent_and_update():
    lr = _learner("sync")
    spans.enable()
    with spans.span("round"):
        lr.client_deltas(SYNC_IDS)
    with spans.span("round"):
        lr.apply(*lr.client_deltas(SYNC_IDS), n_contributors=3)
    with spans.span("round"):
        lr.eval_perplexity()
    recs = _records()
    rounds = [i for i, r in enumerate(recs) if r["name"] == "round"]
    by_round = [[r["name"] for r in recs if r["parent"] == i]
                for i in rounds]
    client = ["client.pack", "client.to_device", "client.wait",
              "client.to_host"]
    server = ["server.to_device", "server.update", "server.history_to_host"]
    assert by_round == [client, client + server, ["server.eval"]]
    assert all(r["update"] == 1 for r in recs if r["parent"] in rounds[:2])
    (ev,) = [r for r in recs if r["name"] == "server.eval"]
    assert ev["update"] == lr.version == 1
    assert all(r["parent"] == -1 for r in recs if r["name"] == "round")
    assert all(r["start"] <= r["end"] for r in recs)
    snap = spans.snapshot()["spans"]
    assert snap["client.pack"]["calls"] == 2
    assert snap["server.update"]["calls"] == 1
    assert set(snap) <= set(spans.SPANS) | {"round"}


def _data_bytes(lr: RealLearner) -> int:
    """One client's batches as the client program gets them: every key
    padded to STEPS, plus the step mask."""
    batch = lr.dataset.client_batches(SYNC_IDS[0], BATCH, 1)[0]
    return STEPS * sum(v.nbytes for v in batch.values()) + 4 * STEPS


def test_sync_transfer_bytes_are_the_shapes_count():
    lr = _learner("sync")
    pb, k = _param_bytes(lr), len(SYNC_IDS)
    data = k * _data_bytes(lr)
    spans.enable()
    _sync_round(lr)
    got = {n: (s["calls"], s["bytes"])
           for n, s in spans.snapshot()["spans"].items()}
    assert got["client.to_device"] == (1, data)   # the current params stay
    assert got["client.to_host"] == (1, k * pb)
    assert got["server.to_device"] == (1, k * pb)
    assert got["server.history_to_host"] == (1, pb)
    for name in ("client.pack", "client.wait", "server.update",
                 "server.eval"):
        assert got[name] == (1, 0)
    # the learner keeps no device copy until rows come back: all uploaded
    counters = spans.snapshot()["counters"]
    assert (counters["server.deltas_resident"],
            counters["server.deltas_uploaded"]) == (0, k)


def test_a_stale_base_is_counted_once_per_stale_client_only():
    lr = _learner("async")
    pb = _param_bytes(lr)
    spans.enable()
    _fedbuff(lr)
    recs = _records()
    data = _data_bytes(lr)
    calls = [(r["update"], r["bytes"]) for r in recs
             if r["name"] == "client.to_device"]
    # client 3 and client 7 train from the current params, client 5 from
    # version 0 while the server is at 1: its base goes up from the ring
    assert calls == [(1, data), (2, pb + data), (2, data)]
    got = spans.snapshot()["spans"]
    assert (got["client.to_host"]["calls"],
            got["client.to_host"]["bytes"]) == (3, 3 * pb)
    # the first update's row goes up again, since no device copy is kept
    # before a row comes back; the second update's two rows stay put
    assert got["server.to_device"]["bytes"] == pb
    assert got["server.history_to_host"]["bytes"] == 2 * pb


def _steps(lr: RealLearner, cid: int) -> int:
    """The local steps that hold data for client ``cid``: its own batches,
    up to ``STEPS``."""
    return min(len(lr.dataset.client_batches(cid, BATCH, 1)), STEPS)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_rows_real_counts_the_datasets_own_mask_rows(mode):
    """Rows computed are clients x the steps the loop runs x batch: in
    sync the cohort runs to its longest client's last real step, in
    FedBuff each client to its own; the padded steps past it are the
    rows skipped."""
    lr = _learner(mode)
    spans.enable()
    if mode == "sync":
        lr.client_deltas(SYNC_IDS)
        runs = [len(SYNC_IDS) * max(_steps(lr, c) for c in SYNC_IDS)]
    else:
        for c in SYNC_IDS:
            lr.client_delta(c)
        runs = [_steps(lr, c) for c in SYNC_IDS]
    want = sum(int(b["mask"][:, 0].sum())
               for c in SYNC_IDS
               for b in lr.dataset.client_batches(c, BATCH, 1)[:STEPS])
    got = spans.snapshot()["counters"]
    assert got == {"client.rows_real": want,
                   "client.rows_computed": sum(runs) * BATCH,
                   "client.rows_skipped": (len(SYNC_IDS) * STEPS
                                           - sum(runs)) * BATCH,
                   "client.programs": 1 if mode == "sync" else len(SYNC_IDS)}
    assert 0 < want <= got["client.rows_computed"]
    # client 3 holds one batch: FedBuff runs it one step and skips one,
    # while the cohort runs it to its longer clients' second step
    assert _steps(lr, 3) == 1 < STEPS == max(_steps(lr, c)
                                             for c in SYNC_IDS)
    assert got["client.rows_skipped"] == (0 if mode == "sync" else BATCH)


@pytest.mark.parametrize("mode,run,calls", [("sync", _sync_round, 1),
                                            ("async", _fedbuff, 3)])
def test_client_programs_counts_one_per_client_program_call(mode, run,
                                                            calls):
    """One vmapped cohort call per sync round, one call per FedBuff
    client; each program computes its clients x the steps its loop runs
    x batch, and skips the rest of the ``STEPS`` x batch."""
    lr = _learner(mode)
    spans.enable()
    run(lr)
    got = spans.snapshot()["counters"]
    assert got["client.programs"] == calls
    # _sync_round trains SYNC_IDS as one cohort; _fedbuff trains clients
    # 3, 5 and 7, in that order, one program each
    programs = ([(len(SYNC_IDS), max(_steps(lr, c) for c in SYNC_IDS))]
                if mode == "sync" else [(1, _steps(lr, c)) for c in (3, 5, 7)])
    assert got["client.rows_computed"] == sum(k * n * BATCH
                                              for k, n in programs)
    assert got["client.rows_skipped"] == sum(k * (STEPS - n) * BATCH
                                             for k, n in programs)


def test_count_reset_and_dump_round_trip():
    spans.enable()
    spans.count("client.rows_real", 2)
    spans.count("client.rows_real", 3)
    with spans.span("client.pack", update=4,
                    copies={"a": np.zeros(10, np.float32)}):
        with spans.span("client.wait", update=4):
            pass
    assert spans.snapshot()["counters"] == {"client.rows_real": 5}
    pack = spans.snapshot()["spans"]["client.pack"]
    assert (pack["calls"], pack["bytes"]) == (1, 40) and pack["s"] >= 0
    recs = _records()
    assert [(r["name"], r["parent"], r["update"], r["bytes"])
            for r in recs] == [("client.pack", -1, 4, 40),
                               ("client.wait", 0, 4, 0)]
    assert all(set(r) == {"name", "start", "end", "parent", "update",
                          "bytes"} for r in recs)
    spans.reset()
    assert spans.snapshot() == {"spans": {}, "counters": {}}
    assert _records() == []
    spans.disable()
    spans.count("client.rows_real", 1)
    assert spans.snapshot()["counters"] == {}


def test_spans_land_in_the_profiler_trace_with_their_update():
    lr = _learner("async")
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        spans.enable()
        _fedbuff(lr)
        spans.disable()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        names = {e.name for p in pd.planes if p.name.startswith("/host:")
                 for line in p.lines for e in line.events}
    assert set(spans.SPANS) <= names

"""Continuous batching engine (``serving/fleet/continuous.py``).

The load-bearing contract is bit-exactness: every sequence admitted to
the iteration-level engine must produce the SAME output row as the
sequential ``session.generate`` oracle, no matter which neighbors
shared its decode iterations or when it was admitted. The policy tests
(slot refill, static-mode convoying, expiry, close) run against a fake
fixed-step session so the iteration math is deterministic.
"""
import threading
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.serving import InferenceSession
from flexflow_tpu.serving.fleet import (ContinuousBatcher,
                                        EngineClosedError,
                                        SequenceError,
                                        kv_slot_capacity)

CAP, SEQ, SEG, EOS = 4, 32, 4, 63


@pytest.fixture(scope="module")
def gpt2_sess():
    from flexflow_tpu.models import GPTConfig, build_gpt2
    cfg = FFConfig()
    cfg.batch_size = CAP
    cfg.only_data_parallel = True
    g = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                  num_heads=4, max_position=SEQ, dropout=0.0)
    ff = FFModel(cfg)
    out = build_gpt2(ff, CAP, SEQ, g)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    return InferenceSession(ff, batch_buckets=(CAP,),
                            decode_segment=SEG)


def _mixed_work(n=10, seed=0):
    """Ragged prompts, alternating short/long decode budgets — the
    workload shape continuous batching exists for."""
    rng = np.random.RandomState(seed)
    work = []
    for k in range(n):
        plen = 2 + int(rng.randint(0, 5))
        max_new = 2 if k % 2 == 0 else 14
        ids = np.zeros(SEQ, np.int32)
        ids[:plen] = 1 + rng.randint(0, 50, size=plen)
        work.append((ids, plen, max_new))
    return work


def _oracle(sess, ids, plen, max_new):
    return np.asarray(sess.generate(
        ids[None], prompt_len=plen, max_new_tokens=max_new,
        temperature=0.0, eos_token_id=EOS))[0]


def test_continuous_bit_exact_vs_sequential_oracle(gpt2_sess):
    work = _mixed_work()
    want = [_oracle(gpt2_sess, *w) for w in work]
    cb = ContinuousBatcher(gpt2_sess, capacity=CAP, eos_token_id=EOS)
    try:
        seqs = [cb.submit(ids, plen, mnew) for ids, plen, mnew in work]
        got = [s.wait(timeout_s=300.0) for s in seqs]
    finally:
        cb.close()
    for k, ((ids, plen, mnew), g, w) in enumerate(zip(work, got, want)):
        np.testing.assert_array_equal(
            g[:plen + mnew], w[:plen + mnew],
            err_msg=f"sequence {k} diverged from the oracle")
    st = cb.stats()
    assert st["completed"] == len(work)
    # the mixed budgets force slot turnover: strictly fewer iterations
    # than one-batch-at-a-time would take, and some sequence joined a
    # batch already in flight
    assert st["iterations"] < sum(-(-mnew // SEG)
                                  for _, _, mnew in work)


def test_plan_session_bucket_pinning_bit_exact(gpt2_sess):
    """A plan-shaped session (``session_for``) has its covering bucket
    instance pinned once; outputs still match the oracle. (The full
    searched ``ServingPlanSession`` wires the same interface — pinned
    end-to-end by test_serving_plan's bucket-routing test.)"""
    picked = []

    class _PlanLike:
        buckets = [CAP]

        def session_for(self, n):
            picked.append(n)
            return gpt2_sess

    work = _mixed_work(n=4, seed=9)
    want = [_oracle(gpt2_sess, *w) for w in work]
    cb = ContinuousBatcher(_PlanLike(), capacity=CAP,
                           eos_token_id=EOS)
    try:
        got = [cb.submit(*w).wait(timeout_s=300.0) for w in work]
    finally:
        cb.close()
    assert picked == [CAP], "bucket routing must be decided ONCE"
    for k, w in enumerate(want):
        plen, mnew = work[k][1], work[k][2]
        np.testing.assert_array_equal(got[k][:plen + mnew],
                                      w[:plen + mnew])


def test_staggered_midflight_admission_bit_exact(gpt2_sess, monkeypatch):
    work = _mixed_work(n=8, seed=3)
    want = [_oracle(gpt2_sess, *w) for w in work]
    # the engine's first segment says when it is in flight and does not
    # end before the late work is queued: a sleep here was the machine's
    # load deciding whether anything was still running when it arrived
    in_flight, queued = threading.Event(), threading.Event()
    generate = gpt2_sess.ff.generate

    def held(*args, **kw):
        out = generate(*args, **kw)
        in_flight.set()
        assert queued.wait(300.0)
        return out

    monkeypatch.setattr(gpt2_sess.ff, "generate", held)
    cb = ContinuousBatcher(gpt2_sess, capacity=CAP, eos_token_id=EOS)
    try:
        first = [cb.submit(*w) for w in work[:CAP]]
        # the first batch is in flight; the rest arrive while it is —
        # they must be admitted at segment boundaries into freed slots
        assert in_flight.wait(300.0)
        late = [cb.submit(*w) for w in work[CAP:]]
        queued.set()
        got = [s.wait(timeout_s=300.0) for s in first + late]
        midflight = sum(1 for s in first + late if s.admitted_midflight)
    finally:
        cb.close()
    for k, (w, g) in enumerate(zip(want, got)):
        plen, mnew = work[k][1], work[k][2]
        np.testing.assert_array_equal(
            g[:plen + mnew], w[:plen + mnew],
            err_msg=f"sequence {k} diverged from the oracle")
    assert midflight >= 1, \
        "staggered submissions never joined an in-flight batch"


def test_static_admission_bit_exact_and_convoys(gpt2_sess):
    work = _mixed_work(n=8, seed=5)
    want = [_oracle(gpt2_sess, *w) for w in work]

    def run(mode):
        cb = ContinuousBatcher(gpt2_sess, capacity=CAP,
                               eos_token_id=EOS, admission=mode)
        try:
            seqs = [cb.submit(*w) for w in work]
            got = [s.wait(timeout_s=300.0) for s in seqs]
            st = cb.stats()
        finally:
            cb.close()
        return got, st

    got_s, st_s = run("static")
    got_c, st_c = run("continuous")
    for k, w in enumerate(want):
        plen, mnew = work[k][1], work[k][2]
        np.testing.assert_array_equal(got_s[k][:plen + mnew],
                                      w[:plen + mnew])
        np.testing.assert_array_equal(got_c[k][:plen + mnew],
                                      w[:plen + mnew])
    # same programs, same outputs — the ONLY difference is scheduling:
    # static runs each batch to its straggler, continuous refills
    assert st_c["iterations"] <= st_s["iterations"]


# -- policy tests on a fake fixed-step session ----------------------


class _FakeFF:
    """Deterministic next-token = (prev + 1) % vocab; shape-compatible
    with the engine's full-capacity ragged dispatch."""

    def __init__(self, vocab=64):
        class _T:
            name = "input_ids"
            shape = (CAP, SEQ)
        self.graph_inputs = [_T()]
        self.vocab = vocab
        self.calls = []

    def generate(self, ids, cur, step, temperature=0.0,
                 eos_token_id=None):
        out = np.array(ids, np.int32)
        self.calls.append(int(step))
        for r in range(out.shape[0]):
            c = int(cur[r])
            for j in range(step):
                out[r, c + j] = (out[r, c + j - 1] + 1) % self.vocab
        return out


class _FakeSession:
    decode_segment = SEG

    def __init__(self, step_s=0.0):
        self.ff = _FakeFF()
        self._lock = threading.Lock()
        self._step_s = step_s
        orig = self.ff.generate

        def slow(*a, **k):
            if self._step_s:
                time.sleep(self._step_s)
            return orig(*a, **k)

        self.ff.generate = slow


def test_expired_before_admission_fails_without_device():
    sess = _FakeSession()
    cb = ContinuousBatcher(sess, capacity=2, eos_token_id=EOS)
    try:
        ids = np.zeros(SEQ, np.int32)
        ids[0] = 1
        s = cb.submit(ids, 1, 4, timeout_s=-1.0)  # already expired
        with pytest.raises(TimeoutError):
            s.wait(timeout_s=10.0)
        assert cb.stats()["expired"] == 1
    finally:
        cb.close()


def test_close_fails_pending_and_rejects_submit():
    sess = _FakeSession(step_s=0.2)
    cb = ContinuousBatcher(sess, capacity=2, eos_token_id=EOS)
    ids = np.zeros(SEQ, np.int32)
    ids[0] = 1
    seqs = [cb.submit(ids, 1, 20) for _ in range(4)]  # 2 run, 2 wait
    time.sleep(0.05)  # first batch is mid-iteration
    cb.close()
    for s in seqs:
        with pytest.raises(EngineClosedError):
            s.wait(timeout_s=10.0)
    with pytest.raises(EngineClosedError):
        cb.submit(ids, 1, 4)


def test_submit_validation():
    sess = _FakeSession()
    cb = ContinuousBatcher(sess, capacity=2, eos_token_id=EOS)
    try:
        ids = np.zeros(SEQ, np.int32)
        with pytest.raises(SequenceError):
            cb.submit(ids, 0, 4)                 # plen < 1
        with pytest.raises(SequenceError):
            cb.submit(ids, 1, SEQ)               # overruns the width
        with pytest.raises(SequenceError):
            cb.submit(np.zeros(SEQ + 1, np.int32), 1, 4)
    finally:
        cb.close()


def test_kv_slot_capacity_tracks_budget(gpt2_sess):
    from flexflow_tpu.search.serving_plan import kv_cache_bytes
    ff = gpt2_sess.ff
    per_seq = sum(kv_cache_bytes(l, 1, SEQ) for l in ff.layers)
    assert per_seq > 0
    # the pool is the envelope divided by per-sequence resident bytes,
    # clamped to [1, hard_cap]
    assert kv_slot_capacity(ff, 3 * per_seq) == 3
    assert kv_slot_capacity(ff, 0) == 1
    assert kv_slot_capacity(ff, 10 ** 12, hard_cap=8) == 8
    cb = ContinuousBatcher(gpt2_sess,
                           kv_cache_bytes_budget=3 * per_seq,
                           eos_token_id=EOS)
    try:
        assert cb.capacity == 3
    finally:
        cb.close()

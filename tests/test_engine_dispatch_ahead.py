"""The engine loop keeps one decode step in flight: a pass dispatches
step n+1 before it fetches step n's tokens. Greedy tokens are each
request's own served alone, on every attention path the engine has;
a stop the host sees one step late emits nothing after it and costs
one lane-step whose write lands past the row's last position; a
cancel mid-flight gives everything back; the books balance at every
pass; and every steady step is dispatched ahead."""

import dataclasses
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, "benchmark"))

import axk1_tiny  # noqa: E402
import exaone_tiny  # noqa: E402
import glm_tiny  # noqa: E402
from benchmark import family  # noqa: E402
from ptype_tpu.metrics import MetricsRegistry  # noqa: E402
from ptype_tpu.serve_engine import PagedGeneratorActor  # noqa: E402

BT, REACH = 8, 128
#: A dense GQA stack whose seeded weights are wide enough apart that
#: its greedy tokens vary.
GQA = {"family": "dense", "hidden_size": 64, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "intermediate_size": 128, "vocab_size": 256, "rope_theta": 10000.0,
       "rms_norm_eps": 1e-06, "initializer_range": 0.5,
       "tie_word_embeddings": False, "param_dtype": "float32"}
#: The engine's decode attention by configuration: GQA's block list,
#: a latent cache read whole (the latent kernel over the block list),
#: a latent cache behind an indexer (the live lanes' list), and window
#: layers beside full ones (two pools).
KINDS = {"gqa": GQA, "latent": axk1_tiny.SMALL, "lanes": glm_tiny.SMALL,
         "two_pools": exaone_tiny.SMALL}


def _model(kind):
    small = KINDS[kind]
    fam = family.of(small)
    cfg = dataclasses.replace(fam.program_config(small, REACH, "float32"),
                              dtype=jnp.float32)
    return cfg, fam.tree(small, 11, "float32")


def _engine(kind, **over):
    cfg, params = _model(kind)
    kw = dict(params=params, n_slots=3, max_len=REACH, block_tokens=BT,
              prefill_chunk=16, n_blocks=64,
              metrics_registry=MetricsRegistry())
    kw.update(over)
    return PagedGeneratorActor(cfg, **kw)


def _tokens(n, seed=5, vocab=128):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         vocab), np.int32)


def _ask(eng, prompt, new, **kw):
    return np.asarray(eng.Generate(jnp.asarray(prompt)[None], new, **kw))[0]


def _wait_for(cond, timeout=120.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.005)


def _audited(eng):
    """Check the books from the engine's own thread after every pass's
    step and every chunk; → the findings."""
    bad = []
    step, chunk = eng._plain_step, eng._prefill_one_chunk

    def audited_step():
        step()
        bad.extend(eng.check_invariants())
        held = {id(r) for r in eng._slot_state.values()}
        live = {id(eng._slot_state[int(s)])
                for s in np.flatnonzero(eng._active)}
        if held != live:
            bad.append("a slot's row and the live mask disagree")

    def audited_chunk(row, budget=None):
        got = chunk(row, budget)
        bad.extend(eng.check_invariants())
        return got

    eng._plain_step, eng._prefill_one_chunk = audited_step, audited_chunk
    return bad


def _pools_empty(eng):
    for pool in (eng.pool, eng._wpool):
        if pool is not None:
            st = pool.stats()
            assert st["kv_used_blocks"] == st["kv_reserved_blocks"] == 0


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_greedy_tokens_are_each_requests_own_served_alone(kind):
    """Three requests that overlap: the second is admitted while the
    first decodes, the third while both do; their rows cross block
    boundaries mid-flight and leave at their ``max_new`` at different
    steps. Each gets, token for token, what it gets served alone."""
    eng = _engine(kind)
    vocab = eng.cfg.vocab_size
    asks = [(_tokens(21, 1, vocab), 30), (_tokens(37, 2, vocab), 9),
            (_tokens(12, 3, vocab), 17)]
    try:
        alone = [_ask(eng, p, n) for p, n in asks]
        bad = _audited(eng)
        outs = [None] * len(asks)
        recs = []
        enq = eng.ledger.enqueued

        def enqueued(*a, **kw):
            recs.append(enq(*a, **kw))
            return recs[-1]

        eng.ledger.enqueued = enqueued

        def ask(i):
            outs[i] = _ask(eng, *asks[i])

        threads = []
        for i in range(len(asks)):
            if i:  # the one before decodes when this one arrives
                _wait_for(lambda: len(recs) == i
                          and len(recs[-1].tok_t) >= 3)
            threads.append(threading.Thread(target=ask, args=(i,)))
            threads[-1].start()
        for th in threads:
            th.join(timeout=300)
        for got, want in zip(outs, alone):
            np.testing.assert_array_equal(got, want)
        assert bad == []
        assert eng._flight is None and not eng._active.any()
        assert eng.check_invariants() == []
        _pools_empty(eng)
        s = eng.ledger.summary()
        assert s["retire_reasons"] == {"complete": 2 * len(asks)}
        # Rows shared steps: some step carried more than one.
        assert s["rows_live_mean"] > 1
    finally:
        eng.close()


def test_a_stop_seen_one_step_late_emits_nothing_after_it():
    """The stop token's step is fetched after the next one is
    dispatched with the row in it: the row stops at the stop token,
    and the one discarded lane-step writes at the stop token's own
    position, one past the last the row's tokens needed and past the
    prompt's sealed blocks."""
    eng = _engine("gqa")
    prompt = _tokens(19, 7, eng.cfg.vocab_size)
    try:
        free = _ask(eng, prompt, 24)
        k = next(i for i in range(3, len(free))
                 if free[i] not in free[:i])
        stop = int(free[k])
        writes = []
        dispatch = eng._dispatch

        def recorded(flight):
            writes.extend(
                (eng._slot_state[int(s)].rec.rid, int(eng._pos[s]))
                for s in np.flatnonzero(eng._active))
            dispatch(flight)

        eng._dispatch = recorded
        got = _ask(eng, prompt, 24, stop_token=stop, pad_token=0)
        np.testing.assert_array_equal(got[:k + 1], free[:k + 1])
        assert (got[k + 1:] == 0).all()
        rec = eng.ledger.records()[-1]
        assert rec["reason"] == "stop" and rec["tokens_out"] == k + 1
        mine = [pos for rid, pos in writes if rid == rec["rid"]]
        L = len(prompt)
        # Step j writes token j-1's position: k steps emitted k tokens
        # after the first, and one more ran past the stop.
        assert mine == list(range(L, L + k + 1))
        assert mine[-1] == L + k >= (L // BT) * BT
        assert eng.check_invariants() == []
        _pools_empty(eng)
        # The pool's next tenant is served as if nothing had happened.
        np.testing.assert_array_equal(_ask(eng, prompt, 24), free)
    finally:
        eng.close()


def test_a_cancel_mid_flight_gives_everything_back():
    """A row withdrawn while a step that holds it is in flight: its
    lane's token is discarded, its blocks and units go back, and a
    row decoding beside it is served its own tokens."""
    eng = _engine("two_pools")
    vocab = eng.cfg.vocab_size
    a, b = _tokens(40, 8, vocab), _tokens(30, 9, vocab)
    try:
        want_b = _ask(eng, b, 20)
        bad = _audited(eng)
        outs = {}
        ta = threading.Thread(target=lambda: outs.update(a=_ask(eng, a, 60)))
        ta.start()
        _wait_for(lambda: any(len(r.emitted) > 2
                              for r in list(eng._slot_state.values())))
        tb = threading.Thread(target=lambda: outs.update(b=_ask(eng, b, 20)))
        tb.start()
        with eng._cond:
            row_a = next(r for r in eng._slot_state.values()
                         if len(r.prompt) == len(a))
        _wait_for(lambda: len(row_a.emitted) > 6)
        eng._cancel_rows([row_a])
        ta.join(timeout=300)
        tb.join(timeout=300)
        assert 6 < len(row_a.emitted) < 60
        assert len(row_a.rec.tok_t) == len(row_a.emitted)
        np.testing.assert_array_equal(outs["b"], want_b)
        assert eng.ledger.summary()["retire_reasons"] == {
            "complete": 2, "cancelled": 1}
        assert bad == []
        _pools_empty(eng)
    finally:
        eng.close()


def test_every_steady_step_is_dispatched_ahead():
    """One request alone: its first step goes out with nothing in
    flight, every later one while the step before it is on the device;
    the pass that fetches the last step dispatches none."""
    eng = _engine("gqa")
    try:
        n = 12
        _ask(eng, _tokens(14, 4, eng.cfg.vocab_size), n)  # one chunk
        _wait_for(lambda: eng.ledger.summary()["iterations"] >= n)
        with eng.ledger._lock:
            iters = list(eng.ledger._iters)
            dispatched, ahead = eng.ledger._dispatched
        assert (dispatched, ahead) == (n - 1, n - 2)
        assert sum(it["ahead"] for it in iters) == n - 2
        assert eng.Info()["steps_ahead_share"] == round((n - 2) / (n - 1), 4)
        # The emits: one a pass, the first step's in the second pass.
        assert [it["active"] for it in iters] == [0] + [1] * (n - 1)
    finally:
        eng.close()


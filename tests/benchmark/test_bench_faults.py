"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false, once for each fault a cell can have. (The look
for a chip is skipped; everything after it is the harness's own.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import run  # noqa: E402

TRAIN1, TRAIN4 = "optimus-125m.train-s1024", "optimus-125m.train-store-4chip"
FAULTS = [
    # A step that returns its state unchanged: the parameters' change
    # reads 1 where the reference moved.
    (TRAIN1, 1, "state_unchanged", "change_norm_gap_worst_leaf"),
    (TRAIN4, 4, "state_unchanged", "change_norm_gap_worst_leaf"),
    # Half of the batch left out, the mean taken over the rest.
    (TRAIN1, 1, "half_batch", "grad_norm_gap_worst_leaf"),
    (TRAIN4, 4, "half_batch", "grad_norm_gap_worst_leaf"),
    # The exchange between chips left out: each keeps a local gradient.
    (TRAIN4, 4, "no_exchange", "grad_norm_gap_worst_leaf"),
    # A token altered where it is produced.
    ("mistral-7b.serve-chat", 1, "token_altered", "served_logit_gap_max"),
    ("mistral-7b.serve-longctx", 1, "token_altered", "served_logit_gap_max"),
]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return perfbench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,chips,fault,caught_by", FAULTS,
                         ids=[f"{c}-{f}" for c, _, f, _ in FAULTS])
def test_fault_comes_out_not_correct(cell, chips, fault, caught_by,
                                     tiny_root):
    import jax

    res = run.execute(cell, 2 ** 31 + 99, 0.5, False, jax.devices()[:chips],
                      root=tiny_root, fault=fault)
    assert res["correct"] is False
    c = res["checks"][caught_by]
    assert not c["value"] <= c["limit"], res["checks"]


def test_a_request_that_never_answers_is_not_correct(tiny_root, monkeypatch):
    """A failed request is counted, sits at the far end of the tail, and
    fails the run: its limit is 0."""
    import jax

    from benchmark import serve_cell

    ask = serve_cell.Server.ask
    calls = {"n": 0}

    def flaky(self, prompt, max_new):
        calls["n"] += 1
        if calls["n"] == 5:       # past the two warm-up asks
            raise RuntimeError("planted: the replica dropped the call")
        return ask(self, prompt, max_new)

    monkeypatch.setattr(serve_cell.Server, "ask", flaky)
    res = run.execute("mistral-7b.serve-chat", 5, 1.0, False,
                      jax.devices()[:1], root=tiny_root)
    assert res["failed"] == 1 and res["correct"] is False
    assert res["checks"]["requests_failed"] == {"value": 1.0, "limit": 0.0}

"""Every traffic file: the same work twice from one seed, other work
from another seed, and the same *amount* of work whatever the seed."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import manifest, traffic  # noqa: E402

M = manifest.load(ROOT)
SERVING = [w["traffic"] for w in M["workloads"]
           if traffic.load(manifest.traffic_file(
               w["traffic"], M["paths"], ROOT))["kind"] != "train"
           ] + ["longctx"]      # not shipped yet: perfbench_tiny holds it
TRAINING = [w["traffic"] for w in M["workloads"] if w["traffic"]
            not in SERVING]
BIG = 2 ** 31 + 12345     # more than 32 signed bits hold


def mix_of(name):
    if name == "longctx":
        return perfbench_tiny.LONGCTX_TRAFFIC
    return traffic.load(manifest.traffic_file(name, M["paths"], ROOT))


@pytest.mark.parametrize("name", SERVING)
def test_same_seed_same_requests(name):
    a = traffic.requests(mix_of(name), BIG, 20.0, 32768)
    b = traffic.requests(mix_of(name), BIG, 20.0, 32768)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", SERVING)
def test_other_seed_other_tokens_same_amount_of_work(name):
    a = traffic.requests(mix_of(name), BIG, 20.0, 32768)
    b = traffic.requests(mix_of(name), 7, 20.0, 32768)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))
    # The same schedule: sizes, sharing, order and due times.
    assert len(a) == len(b)
    assert [(len(x.prompt), x.max_new, x.group, x.shared_tokens, x.due_s)
            for x in a] == [(len(y.prompt), y.max_new, y.group,
                             y.shared_tokens, y.due_s) for y in b]


@pytest.mark.parametrize("name", SERVING)
def test_requests_fit_the_engine_and_share_what_the_file_says(name):
    mix = mix_of(name)
    reqs = traffic.requests(mix, 3, 30.0, 32768)
    reach = mix["engine"]["max_len"]
    assert all(len(r.prompt) + r.max_new <= reach for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.min() >= 1
               and r.prompt.max() < 32768 for r in reqs)
    by_group = {}
    for r in reqs:
        if r.group >= 0:
            by_group.setdefault(r.group, []).append(r)
    assert by_group, "the mix shares nothing"
    for rs in by_group.values():
        head = rs[0].prompt[:rs[0].shared_tokens]
        assert all(np.array_equal(r.prompt[:r.shared_tokens], head)
                   for r in rs)


def test_open_loop_rate_is_the_files():
    mix = mix_of("chat")
    reqs = traffic.requests(mix, 5, 40.0, 32768)
    assert len(reqs) == round(mix["rate_rps"] * 40.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40.0


def test_prompt_lengths_cover_any_seed():
    mix = mix_of("chat")
    warm = set(traffic.prompt_lengths(mix, 20.0))
    for seed in (1, BIG):
        assert {len(r.prompt) for r in traffic.requests(
            mix, seed, 20.0, 32768)} <= warm


@pytest.mark.parametrize("name", TRAINING)
def test_train_batches_from_the_seed(name):
    mix = mix_of(name)
    make = traffic.train_batch_fn(256, 4, 32, BIG)
    a, b = make(np.int32(3)), make(np.int32(3))
    assert np.array_equal(a["tokens"], b["tokens"])
    c = make(np.int32(4))
    assert not np.array_equal(a["tokens"], c["tokens"])
    other = traffic.train_batch_fn(256, 4, 32, 9)(np.int32(3))
    assert not np.array_equal(a["tokens"], other["tokens"])
    # Next-token targets, rows all different.
    assert np.array_equal(np.asarray(a["tokens"])[:, 1:],
                          np.asarray(a["targets"])[:, :-1])
    assert len({bytes(np.asarray(r)) for r in a["tokens"]}) == 4
    assert mix["kind"] == "train" and mix["seq"] == 1024

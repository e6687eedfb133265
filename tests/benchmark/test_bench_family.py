"""A configuration names its family, and the harness reaches the
architecture through that module alone (benchmark/family.py). A second
family, added from the tests' own files with no shipped file touched,
runs a cell to its last line with its own counts in the counters; and
what ``serve_cell.reduce`` hands a family is pinned at fixed rows, where
the dense counts come out as the sums they were."""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import family, run, serve_cell, traffic  # noqa: E402
from benchmark.families import dense, twin  # noqa: E402


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return perfbench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def config(root, name):
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_the_twin_is_found_by_name_beside_dense(tiny_root):
    assert family.of(config(tiny_root, "dense-twin")) is twin
    assert family.of(config(tiny_root, "mistral-7b")) is dense
    assert family.faults(config(tiny_root, "dense-twin")) == []
    assert os.path.dirname(twin.__file__) != os.path.dirname(
        os.path.dirname(dense.__file__))   # not a shipped file


def test_a_cell_of_the_twin_shows_the_twins_counts(tiny_root):
    import jax

    for calls in twin.CALLS.values():
        calls.clear()
    res = run.execute(perfbench_tiny.TWIN_CELL, 2 ** 31 + 77, 1.0, False,
                      jax.devices()[:1], root=tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    c, cfg = res["counters"], config(tiny_root, "dense-twin")
    # The engine was built from the twin's program_config, once, at the
    # mix's reach.
    assert twin.CALLS["program_config"] == [(128, "bfloat16")]
    # Needed bytes: the twin's mark once a decode iteration.
    seen = twin.CALLS["decode_needed_bytes"]
    assert c["decode_steps"] == len(seen) > 0
    assert c["decode_needed_bytes"] == twin.BYTES_A_CALL * len(seen)
    # Model FLOPs: its mark once an iteration and once a prefill chunk.
    flops = twin.CALLS["forward_flops"]
    assert len(flops) > len(seen)
    assert c["model_flops_traced"] == twin.FLOPS_A_CALL * len(flops)
    # What it was handed: one context a live row, each a prompt and the
    # tokens emitted so far; a chunk's tokens with pos+1 .. pos+n keys.
    for (rows, shared), (n, ctxs) in zip(seen, flops):
        assert n == len(rows) == len(ctxs) and rows == ctxs
        assert 1 <= n <= 4 and all(48 < r <= 128 for r in rows)
        assert shared % 16 == 0 and shared < sum(rows)
    for n, ctxs in flops[len(seen):]:
        assert len(ctxs) == n and ctxs == list(range(ctxs[0], ctxs[0] + n))
        assert (ctxs[0] - 1) % 16 == 0        # after whole reused blocks
    # Not dense's numbers for the same rows.
    assert dense.decode_needed_bytes(cfg, *seen[0]) > 1e5
    assert dense.forward_flops(cfg, *flops[0]) > 1e5


# ------------------------------------------- reduce, at fixed rows


def window(bt=16):
    """A hand-made window of 10 s: requests a, b share a 32-token
    prefix (group 0), c stands alone. Four decode iterations, the last
    after the close; five prefill chunks, one before the window."""
    def req(n_prompt, group, shared):
        r = traffic.Request(seq=n_prompt, due_s=0.0,
                            prompt=np.arange(n_prompt, dtype=np.int32),
                            max_new=4, group=group, shared_tokens=shared)
        tap = serve_cell.ReqTap(r)
        tap.t_issue, tap.t_enter, tap.t_exit, tap.t_done = 0.1, 0.2, 12, 12.1
        tap.out = np.zeros(4, np.int32)
        return tap

    a, b, c = req(50, 0, 32), req(70, 0, 32), req(40, -1, 0)
    a.rec, b.rec, c.rec = (types.SimpleNamespace(reused_blocks=n)
                           for n in (0, 2, 0))
    a.chunks = [(-1.0, 32), (1.0, 18)]       # the first before the open
    b.chunks = [(1.5, 32), (1.6, 6)]         # after 2 reused blocks
    c.chunks = [(2.0, 40)]
    a.tok_t, b.tok_t = [1.1, 3, 4, 5], [1.7, 3, 4, 5]
    c.tok_t = [2.1, 4, 5, 11]
    steps = [(3.0, (a, b)), (4.0, (a, b, c)), (5.0, (a, b, c)),
             (11.0, (c,))]
    server = types.SimpleNamespace(
        taps=types.SimpleNamespace(steps=steps), deadline_s=600.0)
    drove = {"taps": [a, b, c], "t_open": 0.0, "t_close": 10.0,
             "backlog_at_close": 0, "drain_s": 1.0}
    mix = {"engine": {"block_tokens": bt}}
    return server, drove, mix


# Per iteration: each live row's prompt + tokens emitted so far (the
# first came from prefill); a, b share 32 tokens while both are live.
ROWS = [[51, 71], [52, 72, 41], [53, 73, 42]]
CHUNKS = [(18, 32), (32, 32), (6, 64), (40, 0)]     # (tokens, pos)


def test_reduce_hands_over_rows_and_chunk_contexts():
    server, drove, mix = window()
    for calls in twin.CALLS.values():
        calls.clear()
    red = serve_cell.reduce({"cfg": {"family": "twin"}, "mix": mix},
                            server, drove)
    assert twin.CALLS["decode_needed_bytes"] == [(r, 32) for r in ROWS]
    assert twin.CALLS["forward_flops"] == (
        [(len(r), r) for r in ROWS]
        + [(n, list(range(pos + 1, pos + n + 1))) for n, pos in CHUNKS])
    assert red["counters"]["decode_steps"] == 3


def test_dense_counts_at_fixed_rows_are_the_sums_they_were():
    """PR 24-26's arithmetic, by hand: bytes = weights + unique tokens
    x K,V bytes; FLOPs = 2 x matmul parameters a token + 4 L H Dh a key,
    a chunk's keys n x pos + n (n + 1) / 2."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b.json")) as f:
        cfg = json.load(f)
    server, drove, mix = window()
    red = serve_cell.reduce({"cfg": cfg, "mix": mix}, server, drove)
    matmul, kv, attn = 3_623_878_656, 65_536, 4 * 16 * 32 * 128
    want_bytes = sum(2.0 * matmul + float(sum(r) - 32) * kv for r in ROWS)
    want_flops = sum(2.0 * matmul * len(r) + float(attn) * sum(r)
                     for r in ROWS)
    want_flops += sum(2.0 * matmul * n
                      + float(attn) * (n * pos + n * (n + 1) // 2)
                      for n, pos in CHUNKS)
    assert red["counters"]["decode_needed_bytes"] == want_bytes
    assert red["counters"]["model_flops_traced"] == want_flops

"""``prefill_unscoped_pct.chat`` (ISSUE 26): the share of the prefill
chunk's device time that belongs to no named part of the model. Data
only: a metric file that hands the accepted ``scope_time_pct`` reader
another program's pattern, and its manifest entry."""

import inspect
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark import manifest  # noqa: E402
from benchmark.readers import scope_time_pct  # noqa: E402
from test_bench_seam import CELL, ctx_of, made, op  # noqa: E402

NAME = "prefill_unscoped_pct.chat"
CHUNK = "jit(prefill_chunk)/while/body/closed_call/"


def spec():
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           NAME + ".json")) as f:
        return json.load(f)


def chunk_trace(bank_ms, scoped=True):
    """One 20-ms decode step (id 7) and two prefill chunks (id 9) of
    ``10 + bank_ms`` ms: gather 3, attention 2, matmuls 4, embed 1, and
    ``bank_ms`` of whole-bank slices and copies that carry no scope."""
    def path(scope, prim):
        return CHUNK + (f"{scope}/" if scoped else "") + prim + ":"

    ops = [op("fusion.1", 0, 20, 7,
              "jit(engine_step)/while/body/closed_call/mlp/dot_general:")]
    modules = [("jit_engine_step(7)", 0, 20)]
    for t0 in (30, 60):
        ops += [
            op("while.3", t0, 10 + bank_ms, 9),  # a container
            op("fusion.1", t0, 3, 9, path("kv_gather", "gather")),
            op("fusion.2", t0 + 3, 2, 9, path("attn", "dot_general")),
            op("fusion.3", t0 + 5, 4, 9, path("mlp", "dot_general")),
            op("fusion.4", t0 + 9, 1, 9, "jit(prefill_chunk)/"
               + ("embed/" if scoped else "") + "gather:"),
        ]
        if bank_ms:
            ops += [op("dynamic-slice_bitcast_fusion.4", t0 + 10,
                       bank_ms / 2, 9, CHUNK + "squeeze:"),
                    op("copy.86", t0 + 10 + bank_ms / 2, bank_ms / 2, 9)]
        modules.append(("jit_prefill_chunk(9)", t0, 10 + bank_ms))
    return ops, modules


def read(xs, tr):
    return scope_time_pct.read(ctx_of(xs, tr), **spec()["params"])


@pytest.mark.parametrize("bank_ms,want", [(10, 50.0), (2, 100 / 6),
                                          (0, 0.0)])
def test_reads_the_chunks_share_outside_every_scope(bank_ms, want):
    """The decode step's time is not in it, nor the container, nor the
    ``embed`` gather (a scope of its own)."""
    assert read(*made(*chunk_trace(bank_ms))) == pytest.approx(want)


def test_finds_nothing_where_there_is_nothing_to_read():
    """No scope names (a commit from before PR 25), no prefill chunk in
    the window, no trace: the line leaves the metric out."""
    assert read(*made(*chunk_trace(10, scoped=False))) is None
    ops, modules = chunk_trace(10)
    assert read(*made([o for o in ops if o[3]["program_id"] == 7],
                      modules[:1])) is None
    assert scope_time_pct.read({"trace": None}, **spec()["params"]) is None


def test_entry_is_the_manifests_last_and_binds_the_accepted_reader():
    m = manifest.load()
    assert manifest.check(m) == []
    entry = m["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "paged step",
        "moves": "ttft_mean_ms", "workloads": [CELL]}
    assert spec()["reader"] == "scope_time_pct"
    assert spec()["params"]["cell"] == CELL
    inspect.signature(scope_time_pct.read).bind({}, **spec()["params"])
    assert entry in manifest.metrics_for(m, CELL, "per_layer")
    assert NAME not in [x["name"] for x in manifest.metrics_for(
        m, "optimus-125m.train-s1024", "per_layer")]

"""The work counts (operations and bytes from shapes) against hand
counts at the cells' own shapes, and the peaks table. The counts the
harness calls are reached the way it reaches them, through the
configuration's family; the dense block's own parts through its
``work`` module."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import family, peaks  # noqa: E402
from benchmark.families.dense import work  # noqa: E402


def cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


OPTIMUS, MISTRAL = cfg("optimus-125m"), cfg("mistral-7b")

# By hand. optimus block: q,k,v 768*128*18 + o 6*128*768 + SwiGLU
# 3*768*2048 = 1,769,472 + 589,824 + 4,718,592.
OPT_LAYER = 7_077_888
OPT_MATMUL = 12 * OPT_LAYER + 768 * 32768            # 110,100,480
# mistral block: 4096*128*(32+16) + 32*128*4096 + 3*4096*14336.
MIS_LAYER = 25_165_824 + 16_777_216 + 176_160_768    # 218,103,808
MIS_MATMUL = 16 * MIS_LAYER + 4096 * 32768           # 3,623,878,656


@pytest.mark.parametrize("c,layer,matmul,total", [
    (OPTIMUS, OPT_LAYER, OPT_MATMUL,
     12 * (OPT_LAYER + 2 * 768) + 768 + 768 * 32768),
    (MISTRAL, MIS_LAYER, MIS_MATMUL,
     16 * (MIS_LAYER + 2 * 4096) + 4096 + 2 * 4096 * 32768),
], ids=["optimus-125m", "mistral-7b"])
def test_parameter_counts(c, layer, matmul, total):
    assert work.layer_matmul_params(c) == layer
    assert work.matmul_params(c) == matmul
    assert work.total_params(c) == total


def test_every_shipped_configuration_is_of_the_dense_family():
    import benchmark.families.dense as dense

    assert family.of(OPTIMUS) is dense and family.of(MISTRAL) is dense
    assert all(callable(getattr(dense, fn)) for fn in family.CONTRACT)


def test_mistral_bytes_are_what_the_cell_states():
    assert work.total_params(MISTRAL) * 2 == 7_516_463_104   # 7.5 GB
    assert family.of(MISTRAL).cache_bytes_per_token(MISTRAL) \
        == 65_536                                            # 64 KiB


def test_train_flops_per_token_palm():
    # 6 per matmul parameter + 12 * L * H * Dh * S.
    assert family.of(OPTIMUS).train_flops_per_token(OPTIMUS, 1024) == (
        6 * 110_100_480 + 12 * 12 * 6 * 128 * 1024) == 773_849_088


def test_forward_flops_of_a_decode_row_and_a_prefill_chunk():
    fam = family.of(MISTRAL)
    # One token attending to 1,000 keys.
    assert fam.forward_flops(MISTRAL, 1, [1000]) == (
        2 * MIS_MATMUL + 4 * 16 * 32 * 128 * 1000)
    # A chunk of 512 tokens after 256 cached: contexts 257..768.
    ctx = sum(range(257, 769))
    assert ctx == 512 * 256 + 512 * 513 // 2
    assert fam.forward_flops(MISTRAL, 512, range(257, 769)) == (
        2 * MIS_MATMUL * 512 + 4 * 16 * 4096 * ctx)


def test_flash_work_at_the_train_cell_shape():
    unit = 16 * 6 * 1024 * 1024 * 128          # B*H*S*S*Dh, causal half
    assert work.flash_train_flops(OPTIMUS, 16, 1024) == {
        "fwd": 2 * unit, "bwd": 5 * unit}
    q = 16 * 1024 * 6 * 128 * 2                # bf16
    assert work.flash_train_bytes(OPTIMUS, 16, 1024) == {
        "fwd": 4 * q, "bwd": 8 * q}            # K == H here
    floor = family.of(OPTIMUS).flash_train_floor_s(
        OPTIMUS, 16, 1024, peaks.peaks_for("TPU v5 lite"))
    assert floor["bound"] == "flops"
    assert floor["floor_s"] == pytest.approx(12 * 7 * unit / 197e12)
    assert floor["floor_s"] == pytest.approx(5.494e-3, rel=1e-3)


def test_flash_bytes_count_kv_heads_for_gqa():
    got = work.flash_train_bytes(MISTRAL, 1, 1024)
    q, kv = 1024 * 32 * 128 * 2, 1024 * 8 * 128 * 2
    assert got == {"fwd": 2 * q + 2 * kv, "bwd": 4 * q + 4 * kv}


@pytest.mark.parametrize("rows", [[], [900] * 32, [10_000] * 8],
                         ids=["no-row", "32x900", "8x10000"])
def test_decode_needed_bytes(rows):
    assert family.of(MISTRAL).decode_needed_bytes(MISTRAL, rows, 0) == (
        2 * MIS_MATMUL + sum(rows) * 65_536)


def test_decode_floor_at_the_hbm_peak():
    # Weights alone: 7.25 GB at 819 GB/s is 8.85 ms an iteration.
    p = peaks.peaks_for("TPU v5 lite")
    assert (family.of(MISTRAL).decode_needed_bytes(MISTRAL, [], 0)
            / p["hbm_bytes_per_s"]) == pytest.approx(8.85e-3, rel=2e-3)


# What the counts were before they took lists (PR 24-26): one sum of
# contexts, the shared tokens already taken off. The dense block's
# list-taking counts have to give the same numbers, at each cell's
# widths and at the chat cell's kind of rows: 32 slots, four groups on
# a 256-token prefix each, contexts of a few hundred to 2,800 tokens.
ROWS = [256 + 16 * (3 + 5 * i % 37) + i for i in range(32)]
SHARED = 4 * (8 - 1) * 256


def sum_taking(c, matmul):
    L, H, Dh = (c["num_hidden_layers"], c["num_attention_heads"],
                c["head_dim"])
    kv = 2 * c["num_key_value_heads"] * Dh * 2 * L
    return {"bytes": lambda unique: float(matmul) * 2 + float(unique) * kv,
            "flops": lambda n, ctx_sum: (2.0 * matmul * n
                                         + 4.0 * L * H * Dh * ctx_sum)}


@pytest.mark.parametrize("c,matmul", [(OPTIMUS, OPT_MATMUL),
                                      (MISTRAL, MIS_MATMUL)],
                         ids=["optimus-125m", "mistral-7b"])
def test_a_decode_iteration_counts_as_its_sum_did(c, matmul):
    fam, old = family.of(c), sum_taking(c, matmul)
    assert fam.decode_needed_bytes(c, ROWS, SHARED) == \
        old["bytes"](sum(ROWS) - SHARED)
    assert fam.forward_flops(c, len(ROWS), ROWS) == \
        old["flops"](len(ROWS), sum(ROWS))


@pytest.mark.parametrize("pos,n", [(0, 512), (256, 512), (1792, 80)])
@pytest.mark.parametrize("c,matmul", [(OPTIMUS, OPT_MATMUL),
                                      (MISTRAL, MIS_MATMUL)],
                         ids=["optimus-125m", "mistral-7b"])
def test_a_prefill_chunk_counts_as_its_sum_did(c, matmul, pos, n):
    # The chunk's tokens attend to pos+1 .. pos+n keys; the sum the old
    # count was given in closed form.
    assert family.of(c).forward_flops(c, n, range(pos + 1, pos + n + 1)) \
        == sum_taking(c, matmul)["flops"](n, n * pos + n * (n + 1) // 2)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        peaks.peaks_for("cpu")

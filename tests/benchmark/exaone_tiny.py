"""The exaone_moe family at test size, as data: a configuration with
every mechanism of ``benchmark/configs/k-exaone-236b-a23b.json`` (two
periods ``LLLG`` of window and full layers, a head width that is not
hidden / heads, the per-head q/k norm, layer 0 dense and then expert
layers with a shared expert, a share of the experts that does not
start at 0), the ``mixed`` mix cut to it, and the limits of its cell.
It enters ``perfbench_tiny.make_root``'s copy as ``glm_tiny`` does, as
new files: :func:`install` (called by ``tests/conftest.py``)."""

import json
import os

CELL = "k-exaone-236b-a23b.serve-mixed"

#: hidden 64, 4 heads of 24 (not 64 / 4) over 2 K,V heads, window 8,
#: 16 experts (4 held, from the fifth) top-4 and 1 shared, 8 layers.
SMALL = {
    "family": "exaone_moe", "model_type": "exaone_moe",
    "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 24, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 4,
    "num_shared_experts": 1, "num_experts_per_tok": 4,
    "num_hidden_layers": 8, "first_k_dense_replace": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "sliding_window": 8, "sliding_window_pattern": "LLLG",
    "sliding_windows": [8, 8, 8, 0, 8, 8, 8, 0],
    "vocab_size": 128, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "hidden_act": "silu", "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "initializer_range": 0.02,
    "param_dtype": "float32", "experts_held_first": 4,
    "reduced": ["num_experts"],
    "published": {"num_experts": 16}}

TRAFFIC = {
    "kind": "open", "rate_rps": 12.0, "sizes_seed": 11,
    "shared_prefixes": {"count": 2, "tokens": 32},
    "suffix": {"dist": "lognormal", "median": 24, "sigma": 1.0,
               "min": 8, "max": 72, "quantum": 8},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
               "min": 4, "max": 12, "quantum": 2},
    "engine": {"n_slots": 4, "max_len": 128, "block_tokens": 8,
               "n_blocks": 64, "prefill_chunk": 16, "max_queue": 4096,
               "admit_timeout_s": 0},
    "gateway": {"per_replica_inflight": 4096, "max_queue_depth": 4096,
                "default_deadline_s": 600.0, "probe_interval_s": 1.0},
    "check_sample": 3, "check_bucket": 32}

#: From readings at this size on the CPU, six seeds x 96 positions:
#: bfloat16 reads at most 8e-4 (the program, bfloat16 compute over
#: float32 weights through the engine, likewise), the float8 control
#: 1.2e-2 to 3.2e-2, rotation on the full layers 1.7e-2 to 4.7e-2, a
#: window layer run as a full one 0.19 to 0.26. Unlike glm_tiny, this
#: size does tell float8 from bfloat16: one discrete selection, not two.
LIMITS = {"served_logit_gap_max": 5e-3, "requests_failed": 0.0}


def put_files(root: str) -> None:
    """The cell's three data files into a tiny copy of the benchmark."""
    bench = os.path.join(root, "benchmark")
    for rel, obj in (("configs/k-exaone-236b-a23b.json", SMALL),
                     ("traffic/mixed.json", TRAFFIC),
                     (f"limits/{CELL}.json", LIMITS)):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)


def install() -> None:
    """Have ``perfbench_tiny.make_root`` write this cell's files too."""
    import perfbench_tiny

    if getattr(perfbench_tiny.make_root, "takes_exaone", False):
        return
    shipped = perfbench_tiny.make_root

    def make_root(tmp: str) -> str:
        root = shipped(tmp)
        put_files(root)
        return root

    make_root.takes_exaone = True
    for flag in ("takes_glm",):
        if getattr(shipped, flag, False):
            setattr(make_root, flag, True)
    perfbench_tiny.make_root = make_root

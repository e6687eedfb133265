"""The axk1 family at test size, as data: a configuration with every
mechanism of ``benchmark/configs/a.x-k1.json`` (latent K,V that a query
reads whole, YaRN positions whose ramp lies inside the rotary pairs
and whose original reach the contexts pass, one dense layer then expert
layers chosen inside groups, a shared expert, a share of the experts
that is half of one group and does not start at 0), the ``hotdocs`` mix
cut to it, and the limits of its cell. It enters
``perfbench_tiny.make_root``'s copy as ``glm_tiny`` does, as new files:
:func:`install` (called by ``tests/conftest.py``)."""

import json
import os

CELL = "a.x-k1.serve-hotdocs"

#: hidden 64, 4 heads, q rank 32, kv rank 16, nope 12 / rope 8 / v 16,
#: 16 experts in 4 groups of 4 (2 kept), top-4, 2 held (the second
#: half of group 1: experts 6, 7) and 1 shared, 1 dense + 2 expert
#: layers; YaRN factor 8 over an original reach of 16 (the ramp runs
#: over pairs 1..3 of the 4).
SMALL = {
    "family": "axk1", "model_type": "axk1",
    "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 128,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "none", "norm_topk_prob": True, "moe_layer_freq": 1,
    "attention_bias": False, "hidden_act": "silu", "seq_aux": True,
    "ep_size": 1, "tie_word_embeddings": False,
    "initializer_range": 0.06, "param_dtype": "float32",
    "experts_held_first": 6,
    "reduced": ["n_routed_experts"],
    "published": {"n_routed_experts": 16}}

TRAFFIC = {
    "kind": "open", "rate_rps": 12.0, "sizes_seed": 13,
    "shared_prefixes": {"count": 2, "tokens": 48},
    "suffix": {"dist": "lognormal", "median": 24, "sigma": 0.5,
               "min": 16, "max": 32, "quantum": 16},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
               "min": 4, "max": 12, "quantum": 2},
    "engine": {"n_slots": 4, "max_len": 128, "block_tokens": 16,
               "n_blocks": 64, "prefill_chunk": 32, "max_queue": 4096,
               "admit_timeout_s": 0},
    "gateway": {"per_replica_inflight": 4096, "max_queue_depth": 4096,
                "default_deadline_s": 600.0, "probe_interval_s": 1.0},
    "check_sample": 3, "check_bucket": 32}

LIMITS = {"served_logit_gap_max": 5e-3, "requests_failed": 0.0}


def put_files(root: str) -> None:
    """The cell's three data files into a tiny copy of the benchmark."""
    bench = os.path.join(root, "benchmark")
    for rel, obj in (("configs/a.x-k1.json", SMALL),
                     ("traffic/hotdocs.json", TRAFFIC),
                     (f"limits/{CELL}.json", LIMITS)):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)


def install() -> None:
    """Have ``perfbench_tiny.make_root`` write this cell's files too."""
    import perfbench_tiny

    if getattr(perfbench_tiny.make_root, "takes_axk1", False):
        return
    shipped = perfbench_tiny.make_root

    def make_root(tmp: str) -> str:
        root = shipped(tmp)
        put_files(root)
        return root

    make_root.takes_axk1 = True
    for flag in ("takes_glm", "takes_exaone"):
        if getattr(shipped, flag, False):
            setattr(make_root, flag, True)
    perfbench_tiny.make_root = make_root

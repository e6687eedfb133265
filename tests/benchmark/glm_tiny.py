"""The glm_moe_dsa family at test size, as data: a configuration with
every mechanism of ``benchmark/configs/glm-5.json`` (latent K,V, the
indexer with contexts past its top-k, one dense layer then expert
layers, a shared expert, a share of the experts that does not start at
0), the ``docqa`` mix cut to it, and the limits of its cell.

``perfbench_tiny.make_root`` writes a tiny copy of the benchmark's data
for the cells that were shipped when it was written. A cell a later
``model_config`` PR ships enters that copy the way it entered the
benchmark, as new files: :func:`install` (called by ``tests/
conftest.py``) has ``make_root`` write this module's files too, so that
every test that walks the shipped manifest over the tiny copy (each
cell to its last line, the manifest's rules, the faults) takes the new
cell in with no shipped file edited."""

import json
import os

CELL = "glm-5.serve-docqa"

#: hidden 64, 4 heads, q rank 32, kv rank 16, nope 12 / rope 4 / v 16,
#: 8 experts (4 held, from the third) top-2 and 1 shared, indexer 2
#: heads x 8 with top-k 16, 1 dense + 2 expert layers.
SMALL = {
    "family": "glm_moe_dsa", "model_type": "glm_moe_dsa",
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "qk_head_dim": 16, "v_head_dim": 16, "index_n_heads": 2,
    "index_head_dim": 8, "index_topk": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "vocab_size": 128, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "rope_interleave": True,
    "indexer_rope_interleave": True, "moe_layer_freq": 1,
    "attention_bias": False, "hidden_act": "silu",
    "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
    "initializer_range": 0.02, "param_dtype": "float32",
    "experts_held_first": 2,
    "reduced": ["n_routed_experts"],
    "published": {"n_routed_experts": 8}}

TRAFFIC = {
    "kind": "open", "rate_rps": 12.0, "sizes_seed": 9,
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

#: From readings at this size on the CPU: the program, bfloat16 compute
#: over float32 weights, reads at most 1.3e-2 over eight seeds x 192
#: positions. A model this small does not tell float8 from bfloat16
#: (the float8 control reads 1.2e-2 to 3.3e-2): the shipped limit is set
#: from readings at the published widths on the chip, and what keeps
#: this size honest is test_bench_glm.py's selection control.
LIMITS = {"served_logit_gap_max": 2e-2, "requests_failed": 0.0}


def put_files(root: str) -> None:
    """The cell's three data files into a tiny copy of the benchmark."""
    bench = os.path.join(root, "benchmark")
    for rel, obj in (("configs/glm-5.json", SMALL),
                     ("traffic/docqa.json", TRAFFIC),
                     (f"limits/{CELL}.json", LIMITS)):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)


def install() -> None:
    """Have ``perfbench_tiny.make_root`` write this cell's files too."""
    import perfbench_tiny

    if getattr(perfbench_tiny.make_root, "takes_glm", False):
        return
    shipped = perfbench_tiny.make_root

    def make_root(tmp: str) -> str:
        root = shipped(tmp)
        put_files(root)
        return root

    make_root.takes_glm = True
    perfbench_tiny.make_root = make_root

"""The glm_moe_dsa family: GLM-5's decoder — latent (MLA) K,V with the
learned sparse-attention indexer in front of it, a leading dense layer
and then dropless sigmoid-routed experts with a shared one — as the
program's ``models/sparse_mla.py`` and ``models/transformer.py`` run it
on the paged serving path. What ``benchmark/family.py`` asks of a
family, from this package's own modules: ``weights`` (the program's
layout, made from the seed), ``reference`` (the plain layers; its
docstring holds the equations) and ``work`` (operations and bytes from
shapes). Served only: the training functions exit with the reason."""

from __future__ import annotations

from benchmark.families.glm_moe_dsa.reference import (served_logits,
                                                      train_steps)
from benchmark.families.glm_moe_dsa.weights import tree
from benchmark.families.glm_moe_dsa.work import (cache_bytes_per_token,
                                                 decode_needed_bytes,
                                                 flash_train_floor_s,
                                                 forward_flops,
                                                 train_flops_per_token)
from benchmark.weights import DTYPES

__all__ = ["program_config", "tree", "served_logits", "train_steps",
           "decode_needed_bytes", "forward_flops", "train_flops_per_token",
           "cache_bytes_per_token", "flash_train_floor_s"]


def program_config(cfg: dict, max_seq: int, param_dtype: str):
    """The object the program's ``PagedGeneratorActor`` takes;
    ``max_seq`` is the engine's reach."""
    # First, and before anything is built: a program from before these
    # layers has no such module, and fails here, at once.
    from ptype_tpu.models import sparse_mla  # noqa: F401

    import jax.numpy as jnp

    from ptype_tpu.models.transformer import (LatentAttention,
                                              TransformerConfig)

    for key, want in (("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("rope_interleave", True),
                      ("indexer_rope_interleave", True),
                      ("moe_layer_freq", 1), ("attention_bias", False),
                      ("hidden_act", "silu"),
                      ("num_nextn_predict_layers", 0)):
        if cfg.get(key) != want:
            raise SystemExit(f"benchmark: the program runs {key} = "
                             f"{want!r}; this file states "
                             f"{cfg.get(key)!r}")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise SystemExit("benchmark: qk_head_dim is not nope + rope")
    held = int(cfg["n_routed_experts"])
    latent = LatentAttention(
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope_dim=int(cfg["qk_nope_head_dim"]),
        rope_dim=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]),
        index_heads=int(cfg["index_n_heads"]),
        index_dim=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        index_rope_dim=int(cfg["qk_rope_head_dim"]))
    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["intermediate_size"]), max_seq=int(max_seq),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.bfloat16, param_dtype=DTYPES[param_dtype],
        norm_eps=float(cfg["rms_norm_eps"]), latent=latent,
        n_dense_layers=int(cfg["first_k_dense_replace"]),
        n_experts=int(cfg["published"]["n_routed_experts"]),
        expert_top_k=int(cfg["num_experts_per_tok"]),
        d_ff_expert=int(cfg["moe_intermediate_size"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        moe_router="sigmoid_bias",
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=(int(cfg.get("experts_held_first", 0)), held))

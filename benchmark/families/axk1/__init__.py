"""The axk1 family: A.X-K1's decoder — latent (MLA) K,V that a query
reads whole (no indexer), YaRN positions, a leading dense layer and
then dropless sigmoid-routed experts chosen inside groups, with a
shared one — as the program's ``models/sparse_mla.py``,
``models/transformer.py`` and ``models/generate.py`` run it on the
paged serving path. What ``benchmark/family.py`` asks of a family, from
this package's own modules: ``weights`` (the program's layout, made
from the seed), ``reference`` (the plain layers; its docstring holds
the equations) and ``work`` (operations and bytes from shapes). Served
only: the training functions exit with the reason."""

from __future__ import annotations

from benchmark.families.axk1.reference import served_logits, train_steps
from benchmark.families.axk1.weights import tree
from benchmark.families.axk1.work import (cache_bytes_per_token,
                                          decode_needed_bytes,
                                          flash_train_floor_s,
                                          forward_flops,
                                          latent_attention_work,
                                          train_flops_per_token)
from benchmark.weights import DTYPES

__all__ = ["program_config", "tree", "served_logits", "train_steps",
           "decode_needed_bytes", "forward_flops", "train_flops_per_token",
           "cache_bytes_per_token", "flash_train_floor_s",
           "latent_attention_work"]


def program_config(cfg: dict, max_seq: int, param_dtype: str):
    """The object the program's ``PagedGeneratorActor`` takes;
    ``max_seq`` is the engine's reach."""
    # First, and before anything is built: a program from before these
    # layers cannot say "latent attention with no indexer", YaRN or a
    # choice inside groups, and fails here, at once.
    from ptype_tpu.models import transformer as tfm

    if not (hasattr(tfm, "YarnScaling")
            and hasattr(tfm.LatentAttention, "indexer")
            and "expert_groups" in tfm.TransformerConfig.__dataclass_fields__):
        raise SystemExit(
            "benchmark: this program has no latent attention without an "
            "indexer, no YaRN positions and no choice of experts inside "
            "groups (ptype_tpu.models.transformer lacks YarnScaling, "
            "LatentAttention.indexer, TransformerConfig.expert_groups): "
            "it cannot serve the axk1 family")

    import jax.numpy as jnp

    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "none"),
                      ("norm_topk_prob", True), ("moe_layer_freq", 1),
                      ("attention_bias", False), ("hidden_act", "silu")):
        if cfg.get(key) != want:
            raise SystemExit(f"benchmark: the program runs {key} = "
                             f"{want!r}; this file states "
                             f"{cfg.get(key)!r}")
    y = cfg["rope_scaling"]
    if y.get("type") != "yarn":
        raise SystemExit(f"benchmark: the program scales positions by "
                         f"yarn; this file states {y.get('type')!r}")
    latent = tfm.LatentAttention(
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope_dim=int(cfg["qk_nope_head_dim"]),
        rope_dim=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]))
    yarn = tfm.YarnScaling(
        factor=float(y["factor"]),
        original_max=int(y["original_max_position_embeddings"]),
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale=float(y["mscale"]),
        mscale_all_dim=float(y["mscale_all_dim"]))
    return tfm.TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["intermediate_size"]), max_seq=int(max_seq),
        rope_theta=float(cfg["rope_theta"]), rope_yarn=yarn,
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
        expert_groups=(int(cfg["n_group"]), int(cfg["topk_group"])),
        experts_held=(int(cfg.get("experts_held_first", 0)),
                      int(cfg["n_routed_experts"])))

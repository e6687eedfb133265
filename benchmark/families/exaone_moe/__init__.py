"""The exaone_moe family: K-EXAONE's decoder — grouped-query attention
with a per-head q/k norm, three layers of a sliding window to each full
one (rotary positions on the window layers alone), a leading dense
layer and then dropless sigmoid-routed experts with a shared one — as
the program's ``models/transformer.py`` and ``models/generate.py`` run
it on the paged serving path, its two kinds of cache in one engine.
What ``benchmark/family.py`` asks of a family, from this package's own
modules: ``weights`` (the program's layout, made from the seed),
``reference`` (the plain layers; its docstring holds the equations) and
``work`` (operations and bytes from shapes). Served only: the training
functions exit with the reason."""

from __future__ import annotations

from benchmark.families.exaone_moe.reference import (served_logits,
                                                     train_steps)
from benchmark.families.exaone_moe.weights import tree
from benchmark.families.exaone_moe.work import (cache_bytes_per_token,
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
    import dataclasses

    import jax.numpy as jnp

    from ptype_tpu.models.transformer import TransformerConfig

    # First, and before anything is built: a program from before these
    # layers has no such fields, and fails here, at once.
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    lacks = sorted({"d_head", "attn_windows", "qk_norm",
                    "nope_full"} - have)
    if lacks:
        raise SystemExit(
            f"benchmark: this program's TransformerConfig has no "
            f"{', '.join(lacks)}: it runs neither window layers beside "
            f"full ones nor a stated head width, so it cannot serve "
            f"exaone_moe")
    for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("hidden_act", "silu"),
                      ("num_nextn_predict_layers", 0),
                      ("tie_word_embeddings", False)):
        if cfg.get(key) != want:
            raise SystemExit(f"benchmark: the program runs {key} = "
                             f"{want!r}; this file states "
                             f"{cfg.get(key)!r}")
    L = int(cfg["num_hidden_layers"])
    windows = tuple(int(w) for w in cfg["sliding_windows"])
    kinds = list(cfg["layer_types"])
    mlps = list(cfg["mlp_layer_types"])
    n_dense = int(cfg["first_k_dense_replace"])
    if not (len(windows) == len(kinds) == len(mlps) == L):
        raise SystemExit("benchmark: layer_types, mlp_layer_types and "
                         "sliding_windows do not each state "
                         f"num_hidden_layers = {L} layers")
    for l in range(L):
        if (kinds[l] == "sliding_attention") != bool(windows[l]) or (
                windows[l] not in (0, int(cfg["sliding_window"]))):
            raise SystemExit(f"benchmark: layer {l} is {kinds[l]!r} with "
                             f"a window of {windows[l]}")
        if (mlps[l] == "dense") != (l < n_dense):
            raise SystemExit(f"benchmark: layer {l} is {mlps[l]!r}; the "
                             f"program runs first_k_dense_replace = "
                             f"{n_dense} leading dense layers")
    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=L, n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_head=int(cfg["head_dim"]),
        d_ff=int(cfg["intermediate_size"]), max_seq=int(max_seq),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        tie_embeddings=False, dtype=jnp.bfloat16,
        param_dtype=DTYPES[param_dtype],
        norm_eps=float(cfg["rms_norm_eps"]),
        attn_windows=windows, qk_norm=True, nope_full=True,
        n_dense_layers=n_dense,
        n_experts=int(cfg["published"]["num_experts"]),
        expert_top_k=int(cfg["num_experts_per_tok"]),
        d_ff_expert=int(cfg["moe_intermediate_size"]),
        n_shared_experts=int(cfg["num_shared_experts"]),
        moe_router="sigmoid_bias",
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=(int(cfg.get("experts_held_first", 0)),
                      int(cfg["num_experts"])))

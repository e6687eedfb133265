"""Operations and bytes the glm_moe_dsa layers need, counted from shapes
alone: the yardstick's numerators for a model whose keys do not all
cost the same. A token's latent cache row costs 1,152 B a layer and is
read only when the indexer selects it; its indexer key costs 256 B and
is read by every later query. ``cfg`` is a configuration file's dict
(the public ``config.json`` key names; ``n_routed_experts`` is what is
held here, ``published["n_routed_experts"]`` the router's width)."""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    """The sizes every count uses, by short name."""
    pub = cfg.get("published", {})
    L = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    return {
        "L": L, "dense": dense, "moe": L - dense,
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "qr": int(cfg["q_lora_rank"]), "c": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "J": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "held": int(cfg["n_routed_experts"]),
        "E": int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        "per_tok": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "V": int(cfg["vocab_size"]),
    }


def attention_params(cfg: dict) -> int:
    """W_DQ, W_UQ, W_DKV, W_UK|W_UV, W_O."""
    d = dims(cfg)
    qk = d["nope"] + d["rope"]
    return (d["D"] * d["qr"] + d["qr"] * d["H"] * qk
            + d["D"] * (d["c"] + d["rope"])
            + d["c"] * d["H"] * (d["nope"] + d["v"])
            + d["H"] * d["v"] * d["D"])


def indexer_params(cfg: dict) -> int:
    """W_IQ, W_IK, W_Iw."""
    d = dims(cfg)
    return d["qr"] * d["J"] * d["di"] + d["D"] * d["di"] + d["D"] * d["J"]


def expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["D"] * d["Fe"]


def dense_mlp_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["D"] * d["F"]


def layer_params(cfg: dict, kind: str) -> int:
    """Stored parameters of one layer (matmuls, norms, the router's
    bias), with the routed experts held here."""
    d = dims(cfg)
    n = (attention_params(cfg) + indexer_params(cfg)
         + 2 * d["D"] + d["qr"] + d["c"] + 2 * d["di"])
    if kind == "dense":
        return n + dense_mlp_params(cfg)
    return (n + d["D"] * d["E"] + d["E"]
            + (d["shared"] + d["held"]) * expert_params(cfg))


def total_params(cfg: dict) -> int:
    d = dims(cfg)
    return (d["dense"] * layer_params(cfg, "dense")
            + d["moe"] * layer_params(cfg, "experts")
            + 2 * d["D"] * d["V"] + d["D"])


def held_hit_expected(cfg: dict, rows: int) -> float:
    """Expected number of distinct held experts that ``rows`` tokens
    hit in one layer, each choosing ``per_tok`` distinct experts of
    ``E`` uniformly: held · (1 − (1 − per_tok/E)^rows)."""
    d = dims(cfg)
    return d["held"] * (1.0 - (1.0 - d["per_tok"] / d["E"]) ** rows)


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes one token holds across all layers: the latent with its
    rotary key, and the indexer's key."""
    d = dims(cfg)
    return (d["c"] + d["rope"] + d["di"]) * itemsize * d["L"]


def decode_needed_bytes(cfg: dict, row_contexts, shared_tokens: int = 0,
                        itemsize: int = 2) -> float:
    """HBM bytes one decode iteration must read. Weights: every layer's
    attention, indexer, router and shared or dense MLP once, the head
    once, and of the routed experts the expected number of distinct
    held ones the rows hit (:func:`held_hit_expected`; a miss reads
    nothing). Cache, per layer and row: the indexer's key of every held
    token (``context × 2·di`` B; a cached prefix that several rows
    share is read once: ``shared_tokens``), and the latent rows of the
    selected keys alone (``min(context, topk) × 2·(c + rope)`` B)."""
    d = dims(cfg)
    rows = len(row_contexts)
    fixed = attention_params(cfg) + indexer_params(cfg)
    w = (d["L"] * fixed + d["dense"] * dense_mlp_params(cfg)
         + d["moe"] * (d["D"] * d["E"] + (d["shared"]
                       + held_hit_expected(cfg, rows))
                       * expert_params(cfg))
         + d["D"] * d["V"])
    keys = float(sum(row_contexts) - shared_tokens) * d["di"]
    latents = float(sum(min(c, d["topk"]) for c in row_contexts)) * (
        d["c"] + d["rope"])
    return (w + d["L"] * (keys + latents)) * itemsize


def forward_flops(cfg: dict, n_tokens: int, contexts) -> float:
    """Forward FLOPs of ``n_tokens`` tokens, ``contexts`` holding for
    each the keys it may attend to (itself included). Per token, 2 per
    matmul parameter it meets: attention, indexer, the dense MLP or the
    router, the shared expert and its ``per_tok`` choices' expected
    share held here (``per_tok × held / E`` experts), and the head.
    Per key, a layer: the indexer scores every one (2·J·di), attention
    reads the selected ``min(context, topk)`` in the absorbed form, the
    one a latent cache allows (score against [cKV ; kR] and the sum
    over cKV: 2·H·(2c + rope))."""
    d = dims(cfg)
    fixed = attention_params(cfg) + indexer_params(cfg)
    routed = d["per_tok"] * d["held"] / d["E"]
    per_token = (d["L"] * fixed + d["dense"] * dense_mlp_params(cfg)
                 + d["moe"] * (d["D"] * d["E"] + (d["shared"] + routed)
                               * expert_params(cfg))
                 + d["D"] * d["V"])
    ctx = list(contexts)
    index = 2.0 * d["J"] * d["di"] * sum(ctx)
    attn = 2.0 * d["H"] * (2 * d["c"] + d["rope"]) * sum(
        min(c, d["topk"]) for c in ctx)
    return 2.0 * per_token * n_tokens + d["L"] * (index + attn)


_WHY_NOT = ("benchmark: glm_moe_dsa is served, not trained: at 16 bytes "
            "a parameter no cut inside the guide's floors fits one chip "
            "(2.70B parameters = 43.2 GB), and the program trains "
            "neither latent attention nor a dropless expert layer")


def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise SystemExit(_WHY_NOT)


def flash_train_floor_s(cfg: dict, batch: int, seq: int, peaks: dict
                        ) -> dict:
    raise SystemExit(_WHY_NOT)

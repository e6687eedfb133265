"""Operations and bytes the axk1 layers need, counted from shapes
alone. A token's latent cache row costs 1,152 B a layer (the normed
latent and the one rotary key) and is read by EVERY later query of its
row: no indexer selects. ``cfg`` is a configuration file's dict (the
public ``config.json`` key names; ``n_routed_experts`` is what is held
here, ``published["n_routed_experts"]`` the router's width)."""

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
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "held": int(cfg["n_routed_experts"]),
        "E": int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        "per_tok": int(cfg["num_experts_per_tok"]),
        "groups": int(cfg["n_group"]), "kept": int(cfg["topk_group"]),
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
    n = attention_params(cfg) + 2 * d["D"] + d["qr"] + d["c"]
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
    ``E`` as if uniformly: held · (1 − (1 − per_tok/E)^rows). (The
    choice inside groups leaves an expert's chance at per_tok/E when
    the scores are exchangeable, as random weights make them; what it
    changes is how the hits of one token bunch.)"""
    d = dims(cfg)
    return d["held"] * (1.0 - (1.0 - d["per_tok"] / d["E"]) ** rows)


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes one token holds across all layers: the latent with its
    rotary key."""
    d = dims(cfg)
    return (d["c"] + d["rope"]) * itemsize * d["L"]


def latent_attention_work(cfg: dict, row_contexts, itemsize: int = 2
                          ) -> tuple[float, float]:
    """``(bytes, flops)`` of one decode iteration's latent attention
    alone, in the absorbed form a latent cache allows: every live
    row's every cached row read once a layer (``context × (c + rope)``
    values), scored by every head against [cKV ; kR] and summed over
    cKV (``2 · H · (2c + rope)`` a key)."""
    d = dims(cfg)
    ctx = float(sum(row_contexts))
    return (ctx * (d["c"] + d["rope"]) * itemsize * d["L"],
            ctx * 2.0 * d["H"] * (2 * d["c"] + d["rope"]) * d["L"])


def decode_needed_bytes(cfg: dict, row_contexts, shared_tokens: int = 0,
                        itemsize: int = 2) -> float:
    """HBM bytes one decode iteration must read. Weights: every layer's
    attention, router and shared or dense MLP once, the head once, and
    of the routed experts the expected number of distinct held ones
    the rows hit (:func:`held_hit_expected`; a miss reads nothing).
    Cache, per layer: the latent row of every token a live row holds
    (a cached prefix that several rows share is read once:
    ``shared_tokens``)."""
    d = dims(cfg)
    rows = len(row_contexts)
    w = (d["L"] * attention_params(cfg)
         + d["dense"] * dense_mlp_params(cfg)
         + d["moe"] * (d["D"] * d["E"] + (d["shared"]
                       + held_hit_expected(cfg, rows))
                       * expert_params(cfg))
         + d["D"] * d["V"])
    latents = float(sum(row_contexts) - shared_tokens) * (
        d["c"] + d["rope"])
    return (w + d["L"] * latents) * itemsize


def forward_flops(cfg: dict, n_tokens: int, contexts) -> float:
    """Forward FLOPs of ``n_tokens`` tokens, ``contexts`` holding for
    each the keys it attends to (itself included). Per token, 2 per
    matmul parameter it meets: attention, the dense MLP or the router,
    the shared expert and its ``per_tok`` choices' expected share held
    here (``per_tok × held / E`` experts), and the head. Per key, a
    layer, the absorbed form (the one a latent cache allows; the
    expanded form a prefill chunk could use costs less a key and is
    not what is counted): 2·H·(2c + rope)."""
    d = dims(cfg)
    routed = d["per_tok"] * d["held"] / d["E"]
    per_token = (d["L"] * attention_params(cfg)
                 + d["dense"] * dense_mlp_params(cfg)
                 + d["moe"] * (d["D"] * d["E"] + (d["shared"] + routed)
                               * expert_params(cfg))
                 + d["D"] * d["V"])
    attn = 2.0 * d["H"] * (2 * d["c"] + d["rope"]) * float(sum(contexts))
    return 2.0 * per_token * n_tokens + d["L"] * attn


_WHY_NOT = ("benchmark: axk1 is served, not trained: at 16 bytes a "
            "parameter no cut inside the guide's floors fits one chip "
            "(one expert layer's share alone, 675M parameters, is 10.8 "
            "GB), and the program trains neither latent attention nor "
            "a dropless expert layer")


def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise SystemExit(_WHY_NOT)


def flash_train_floor_s(cfg: dict, batch: int, seq: int, peaks: dict
                        ) -> dict:
    raise SystemExit(_WHY_NOT)

"""Operations and bytes the exaone_moe layers need, counted from shapes
alone: the yardstick's numerators for a stack whose keys do not all
cost the same. A full-attention layer reads every key a row holds; a
window layer's key costs nothing once it is ``sliding_window`` behind
the query, so a row's context counts there as ``min(context, window)``.
``cfg`` is a configuration file's dict (the public ``config.json`` key
names; ``num_experts`` is what is held here, ``published["num_experts"]``
the router's width)."""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    """The sizes every count uses, by short name."""
    pub = cfg.get("published", {})
    L = int(cfg["num_hidden_layers"])
    windows = [int(w) for w in cfg["sliding_windows"]]
    dense = sum(1 for k in cfg["mlp_layer_types"] if k == "dense")
    return {
        "L": L, "dense": dense, "moe": L - dense,
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "K": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "held": int(cfg["num_experts"]),
        "E": int(pub.get("num_experts", cfg["num_experts"])),
        "per_tok": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["num_shared_experts"]),
        "V": int(cfg["vocab_size"]),
        "windows": windows, "window": max(windows),
        "n_window": sum(1 for w in windows if w),
        "n_full": sum(1 for w in windows if not w),
    }


def attention_params(cfg: dict) -> int:
    """Wq, Wk, Wv, Wo."""
    d = dims(cfg)
    return d["D"] * d["Dh"] * (2 * d["H"] + 2 * d["K"])


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
    n = attention_params(cfg) + 2 * d["D"] + 2 * d["Dh"]
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
    """Bytes of K and V one more token of context adds to what a row
    holds: the full-attention layers' alone. A window layer's cost
    stops growing once a row is ``sliding_window`` long: it holds a
    constant ``window × 2·K·Dh`` a row (:func:`window_bytes_per_row`)
    however long the row is, so it is not a cost of a token."""
    d = dims(cfg)
    return 2 * d["K"] * d["Dh"] * itemsize * d["n_full"]


def window_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Bytes the window layers hold for one decoding row, at most."""
    d = dims(cfg)
    return 2 * d["K"] * d["Dh"] * itemsize * d["n_window"] * d["window"]


def decode_needed_bytes(cfg: dict, row_contexts, shared_tokens: int = 0,
                        itemsize: int = 2) -> float:
    """HBM bytes one decode iteration must read. Weights: every layer's
    attention, the dense MLP or the router and shared expert once, the
    head once, and of the routed experts the expected number of
    distinct held ones the rows hit (:func:`held_hit_expected`; a miss
    reads nothing). Cache, per row: in a full layer the K, V of every
    token it holds (a cached prefix several rows share once:
    ``shared_tokens``), in a window layer those of its last
    ``min(context, window)`` alone."""
    d = dims(cfg)
    rows = len(row_contexts)
    w = (d["L"] * attention_params(cfg)
         + d["dense"] * dense_mlp_params(cfg)
         + d["moe"] * (d["D"] * d["E"] + (d["shared"]
                       + held_hit_expected(cfg, rows))
                       * expert_params(cfg))
         + d["D"] * d["V"])
    kv = 2 * d["K"] * d["Dh"]
    full = float(sum(row_contexts) - shared_tokens) * d["n_full"]
    win = float(sum(min(c, d["window"]) for c in row_contexts)) * d["n_window"]
    return (w + kv * (full + win)) * itemsize


def forward_flops(cfg: dict, n_tokens: int, contexts) -> float:
    """Forward FLOPs of ``n_tokens`` tokens, ``contexts`` holding for
    each the keys it may attend to (itself included). Per token, 2 per
    matmul parameter it meets: attention, the dense MLP or the router,
    the shared expert and its ``per_tok`` choices' expected share held
    here (``per_tok × held / E`` experts), and the head. Per key, QK^T
    + PV = 4·H·Dh: every key in a full layer, ``min(context, window)``
    in a window layer."""
    d = dims(cfg)
    routed = d["per_tok"] * d["held"] / d["E"]
    per_token = (d["L"] * attention_params(cfg)
                 + d["dense"] * dense_mlp_params(cfg)
                 + d["moe"] * (d["D"] * d["E"] + (d["shared"] + routed)
                               * expert_params(cfg))
                 + d["D"] * d["V"])
    ctx = list(contexts)
    keys = (d["n_full"] * sum(ctx)
            + d["n_window"] * sum(min(c, d["window"]) for c in ctx))
    return 2.0 * per_token * n_tokens + 4.0 * d["H"] * d["Dh"] * keys


_WHY_NOT = ("benchmark: exaone_moe is served, not trained: at 16 bytes "
            "a parameter no cut inside the guide's floors fits one chip "
            "(the dense layer and four expert layers at the floor of 8 "
            "held experts are 2.27B parameters = 36 GB), and the "
            "program trains neither a stack with attention kinds nor a "
            "dropless expert layer")


def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise SystemExit(_WHY_NOT)


def flash_train_floor_s(cfg: dict, batch: int, seq: int, peaks: dict
                        ) -> dict:
    raise SystemExit(_WHY_NOT)

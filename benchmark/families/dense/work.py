"""Operations and bytes the dense block's algorithms need, counted from
shapes alone.

These are the yardstick's numerators: a roofline share divides what the
work *needs* by what the device took, so nothing here looks at how the
program moves data. ``cfg`` is a configuration file's dict (the public
``config.json`` key names). The serving counts take what the cell saw,
one context length per row or per token, and not a digest of it
(``benchmark/family.py``); for this block every key costs the same, so
they sum the lengths."""

from __future__ import annotations


def dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    """(L, D, H, K, Dh, F, V) of a configuration."""
    H = int(cfg["num_attention_heads"])
    Dh = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    return (int(cfg["num_hidden_layers"]), int(cfg["hidden_size"]), H,
            int(cfg.get("num_key_value_heads") or H), Dh,
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]))


def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters of one block: q, k, v, o and the SwiGLU three."""
    _, D, H, K, Dh, F, _ = dims(cfg)
    return D * Dh * (H + 2 * K) + H * Dh * D + 3 * D * F


def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied by: every block and the LM head
    (the embedding lookup is a gather, not a matmul; a tied head is the
    embedding used as a matmul, counted once as the head)."""
    L, D, *_, V = dims(cfg)
    return L * layer_matmul_params(cfg) + D * V


def total_params(cfg: dict) -> int:
    """Stored parameters: matmuls, embedding (once more if untied), norms."""
    L, D, *_, V = dims(cfg)
    n = L * (layer_matmul_params(cfg) + 2 * D) + D + D * V
    if not cfg.get("tie_word_embeddings", False):
        n += D * V
    return n


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token, PaLM appendix B:
    6 per matmul parameter plus 12·L·H·Dh·S for attention (causal not
    halved; recomputation not counted)."""
    L, _, H, _, Dh, _, _ = dims(cfg)
    return 6.0 * matmul_params(cfg) + 12.0 * L * H * Dh * seq


def forward_flops(cfg: dict, n_tokens: int, contexts) -> float:
    """Forward FLOPs of ``n_tokens`` tokens, ``contexts`` holding for
    each the keys it attends to (itself included): 2 per matmul
    parameter per token, and QK^T + PV = 4·H·Dh per key per layer."""
    L, _, H, _, Dh, _, _ = dims(cfg)
    return (2.0 * matmul_params(cfg) * n_tokens
            + 4.0 * L * H * Dh * sum(contexts))


def flash_train_flops(cfg: dict, batch: int, seq: int) -> dict:
    """FLOPs one layer's causal flash attention needs in a train step,
    per kernel family. A causal S×S matmul needs half the square:
    B·H·S²·Dh FLOPs each. Forward: QK^T, PV (2). Backward, counted
    once however the kernels split it: recompute QK^T, dV, dP, dQ, dK
    (5) — a kernel pair that recomputes QK^T and dP twice does 7, and
    the extra two are not needed work."""
    _, _, H, _, Dh, _, _ = dims(cfg)
    unit = float(batch) * H * seq * seq * Dh
    return {"fwd": 2 * unit, "bwd": 5 * unit}


def flash_train_bytes(cfg: dict, batch: int, seq: int,
                      itemsize: int = 2) -> dict:
    """HBM bytes the same attention must move at least: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq,
    dk, dv (the row statistics are 1/Dh of that and left out)."""
    _, _, H, K, Dh, _, _ = dims(cfg)
    q = batch * seq * H * Dh * itemsize
    kv = batch * seq * K * Dh * itemsize
    return {"fwd": 2 * q + 2 * kv, "bwd": 4 * q + 4 * kv}


def flash_train_floor_s(cfg: dict, batch: int, seq: int, peaks: dict
                        ) -> dict:
    """Least seconds the chip could take for one train step's flash
    work (all layers), and which peak binds."""
    L = dims(cfg)[0]
    fl = flash_train_flops(cfg, batch, seq)
    by = flash_train_bytes(cfg, batch, seq)
    t_fl = L * (fl["fwd"] + fl["bwd"]) / peaks["bf16_flops"]
    t_by = L * (by["fwd"] + by["bwd"]) / peaks["hbm_bytes_per_s"]
    return {"floor_s": max(t_fl, t_by),
            "bound": "flops" if t_fl >= t_by else "hbm"}


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of K and V one token holds across all layers."""
    L, _, _, K, Dh, _, _ = dims(cfg)
    return 2 * K * Dh * itemsize * L


def decode_needed_bytes(cfg: dict, row_contexts, shared_tokens: int = 0,
                        itemsize: int = 2) -> float:
    """HBM bytes one decode iteration must read: every matmul weight
    once (the embedding rows of a handful of tokens are nothing beside
    them) and the K, V of the tokens its rows hold (``row_contexts``,
    one length a live row) — a block shared by several rows once:
    ``shared_tokens`` are the tokens the lengths count again."""
    return (float(matmul_params(cfg)) * itemsize
            + float(sum(row_contexts) - shared_tokens)
            * cache_bytes_per_token(cfg, itemsize))

"""The glm_moe_dsa weights from ``--seed``: the program gets the whole
tree in one jitted call, in the layout ``ptype_tpu.models`` takes (one
stacked group of dense layers, one of expert layers); the plain
reference regenerates one layer at a time from the same keys.

N(0, ``initializer_range``), the projections back into the residual
stream (``wo``, ``w_down``, ``ws_down``) scaled by 1/sqrt(2L); norm
scales 1; the indexer LayerNorm's bias and the router's correction bias
drawn from the seed (N(0, 0.02) and N(0, 0.05)), so that neither is a
term the comparison cannot see."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.families.glm_moe_dsa import work
from benchmark.weights import DTYPES, normal as _normal, seed_key

ROUTER_BIAS_STD = 0.05


def kind_of(cfg: dict, l: int) -> str:
    return "dense" if l < int(cfg["first_k_dense_replace"]) else "experts"


def layer(key: jax.Array, cfg: dict, l, dtype, kind: str) -> dict:
    """Layer ``l``'s weights, under the program's names."""
    d = work.dims(cfg)
    D, H, qk = d["D"], d["H"], d["nope"] + d["rope"]
    std = float(cfg.get("initializer_range", 0.02))
    resid = std / (2.0 * d["L"]) ** 0.5
    ks = jax.random.split(jax.random.fold_in(key, l + 1), 20)
    w = {
        "attn_norm": jnp.ones((D,), dtype),
        "w_dq": _normal(ks[0], (D, d["qr"]), std, dtype),
        "q_norm": jnp.ones((d["qr"],), dtype),
        "w_uq": _normal(ks[1], (d["qr"], H, qk), std, dtype),
        "w_dkv": _normal(ks[2], (D, d["c"] + d["rope"]), std, dtype),
        "kv_norm": jnp.ones((d["c"],), dtype),
        "w_uk": _normal(ks[3], (d["c"], H, d["nope"]), std, dtype),
        "w_uv": _normal(ks[4], (d["c"], H, d["v"]), std, dtype),
        "wo": _normal(ks[5], (H, d["v"], D), resid, dtype),
        "w_iq": _normal(ks[6], (d["qr"], d["J"], d["di"]), std, dtype),
        "w_ik": _normal(ks[7], (D, d["di"]), std, dtype),
        "ik_norm": jnp.ones((d["di"],), dtype),
        "ik_norm_b": _normal(ks[8], (d["di"],), std, dtype),
        "w_iw": _normal(ks[9], (D, d["J"]), std, dtype),
        "mlp_norm": jnp.ones((D,), dtype),
    }
    if kind == "dense":
        F = d["F"]
        w.update(w_gate=_normal(ks[10], (D, F), std, dtype),
                 w_up=_normal(ks[11], (D, F), std, dtype),
                 w_down=_normal(ks[12], (F, D), resid, dtype))
        return w
    Fe, held, Fs = d["Fe"], d["held"], d["Fe"] * d["shared"]
    w.update(
        router=_normal(ks[13], (D, d["E"]), std, dtype),
        router_bias=_normal(ks[14], (d["E"],), ROUTER_BIAS_STD,
                            jnp.float32),
        w_gate=_normal(ks[10], (held, D, Fe), std, dtype),
        w_up=_normal(ks[11], (held, D, Fe), std, dtype),
        w_down=_normal(ks[12], (held, Fe, D), resid, dtype),
        ws_gate=_normal(ks[15], (D, Fs), std, dtype),
        ws_up=_normal(ks[16], (D, Fs), std, dtype),
        ws_down=_normal(ks[17], (Fs, D), resid, dtype))
    return w


def outer(key: jax.Array, cfg: dict, dtype) -> dict:
    """Embedding, final norm and the untied head, over the vocabulary
    slice held here."""
    d = work.dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    ks = jax.random.split(jax.random.fold_in(key, 0), 2)
    return {"embed": _normal(ks[0], (d["V"], d["D"]), std, dtype),
            "final_norm": jnp.ones((d["D"],), dtype),
            "lm_head": _normal(ks[1], (d["D"], d["V"]), std, dtype)}


_KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "index_n_heads", "index_head_dim", "index_topk",
         "intermediate_size", "moe_intermediate_size", "n_routed_experts",
         "num_experts_per_tok", "n_shared_experts", "vocab_size",
         "initializer_range")


def _freeze(cfg: dict) -> str:
    """The keys the weights depend on, hashable."""
    return json.dumps({**{k: cfg.get(k) for k in _KEYS},
                       "published": {"n_routed_experts":
                                     work.dims(cfg)["E"]}}, sort_keys=True)


_thaw = json.loads


@functools.lru_cache(maxsize=None)
def _tree_fn(frozen: str, dtype_name: str, sharding):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    d = work.dims(cfg)

    def make(key):
        groups = []
        for kind, first, n in (("dense", 0, d["dense"]),
                               ("experts", d["dense"], d["moe"])):
            if n:
                groups.append(jax.lax.map(
                    lambda l, kind=kind: layer(key, cfg, l, dtype, kind),
                    jnp.arange(first, first + n)))
        return {**outer(key, cfg, dtype),
                "blocks": groups[0] if len(groups) == 1 else tuple(groups)}

    return jax.jit(make, out_shardings=sharding)


def tree(cfg: dict, seed: int, dtype_name: str, sharding=None) -> dict:
    """The whole model on the device, in one jitted call."""
    from benchmark import harness

    harness.log(f"glm_moe_dsa: {work.total_params(cfg) / 1e9:.4f}B "
                f"parameters held ({work.total_params(cfg)})")
    return _tree_fn(_freeze(cfg), dtype_name, sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: str, dtype_name: str, kind: str):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key, l: layer(key, cfg, l, dtype, kind))


def one_layer(cfg: dict, seed: int, l: int, dtype_name: str) -> dict:
    return _layer_fn(_freeze(cfg), dtype_name, kind_of(cfg, l))(
        seed_key(seed), jnp.int32(l))


@functools.lru_cache(maxsize=None)
def _outer_fn(frozen: str, dtype_name: str):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key: outer(key, cfg, dtype))


def outer_only(cfg: dict, seed: int, dtype_name: str) -> dict:
    return _outer_fn(_freeze(cfg), dtype_name)(seed_key(seed))

"""The exaone_moe weights from ``--seed``: the program gets the tree in
the layout ``ptype_tpu.models`` takes (one stacked group a run of
identical layers, ``transformer.layer_groups``), a group a jitted call;
the plain reference regenerates one layer at a time from the same keys.

N(0, ``initializer_range``), the projections back into the residual
stream (``wo``, ``w_down``, ``ws_down``) scaled by 1/sqrt(2L); the
sub-layer and final norm scales 1; the per-head q/k norm scales
1 + N(0, 0.1) and the router's correction bias N(0, 0.05), drawn from
the seed, so that neither is a term the comparison cannot see."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.families.exaone_moe import work
from benchmark.weights import DTYPES, normal as _normal, seed_key

ROUTER_BIAS_STD = 0.05
QK_NORM_STD = 0.1


def kind_of(cfg: dict, l: int) -> str:
    return "dense" if cfg["mlp_layer_types"][l] == "dense" else "experts"


def runs(cfg: dict) -> list[tuple[str, int, int]]:
    """The stack as runs of identical layers, ``(MLP kind, first layer,
    count)``: a run ends where the MLP kind or the attention kind
    changes, as ``transformer.layer_groups`` cuts it."""
    d = work.dims(cfg)
    out: list[list] = []
    for l in range(d["L"]):
        key = (kind_of(cfg, l), bool(d["windows"][l]))
        if out and out[-1][0] == key:
            out[-1][2] += 1
        else:
            out.append([key, l, 1])
    return [(key[0], first, n) for key, first, n in out]


def layer(key: jax.Array, cfg: dict, l, dtype, kind: str) -> dict:
    """Layer ``l``'s weights, under the program's names."""
    d = work.dims(cfg)
    D, H, K, Dh = d["D"], d["H"], d["K"], d["Dh"]
    std = float(cfg.get("initializer_range", 0.02))
    resid = std / (2.0 * d["L"]) ** 0.5
    ks = jax.random.split(jax.random.fold_in(key, l + 1), 16)
    w = {
        "attn_norm": jnp.ones((D,), dtype),
        "wq": _normal(ks[0], (D, H, Dh), std, dtype),
        "wk": _normal(ks[1], (D, K, Dh), std, dtype),
        "wv": _normal(ks[2], (D, K, Dh), std, dtype),
        "wo": _normal(ks[3], (H, Dh, D), resid, dtype),
        "q_norm": (1.0 + _normal(ks[4], (Dh,), QK_NORM_STD,
                                 jnp.float32)).astype(dtype),
        "k_norm": (1.0 + _normal(ks[5], (Dh,), QK_NORM_STD,
                                 jnp.float32)).astype(dtype),
        "mlp_norm": jnp.ones((D,), dtype),
    }
    if kind == "dense":
        F = d["F"]
        w.update(w_gate=_normal(ks[6], (D, F), std, dtype),
                 w_up=_normal(ks[7], (D, F), std, dtype),
                 w_down=_normal(ks[8], (F, D), resid, dtype))
        return w
    Fe, held, Fs = d["Fe"], d["held"], d["Fe"] * d["shared"]
    w.update(
        router=_normal(ks[9], (D, d["E"]), std, dtype),
        router_bias=_normal(ks[10], (d["E"],), ROUTER_BIAS_STD,
                            jnp.float32),
        w_gate=_normal(ks[6], (held, D, Fe), std, dtype),
        w_up=_normal(ks[7], (held, D, Fe), std, dtype),
        w_down=_normal(ks[8], (held, Fe, D), resid, dtype),
        ws_gate=_normal(ks[11], (D, Fs), std, dtype),
        ws_up=_normal(ks[12], (D, Fs), std, dtype),
        ws_down=_normal(ks[13], (Fs, D), resid, dtype))
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


_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok",
         "num_shared_experts", "vocab_size", "initializer_range",
         "mlp_layer_types", "sliding_windows")


def _freeze(cfg: dict) -> str:
    """The keys the weights depend on, hashable."""
    return json.dumps({**{k: cfg.get(k) for k in _KEYS},
                       "published": {"num_experts": work.dims(cfg)["E"]}},
                      sort_keys=True)


_thaw = json.loads


@functools.lru_cache(maxsize=None)
def _group_fn(frozen: str, dtype_name: str, kind: str, n: int, sharding):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(
        lambda key, first: jax.lax.map(
            lambda l: layer(key, cfg, l, dtype, kind),
            first + jnp.arange(n)), out_shardings=sharding)


def tree(cfg: dict, seed: int, dtype_name: str, sharding=None) -> dict:
    """The whole model on the device: a jitted call a group (a group's
    draws are then the only temporaries beside what is already made)."""
    from benchmark import harness

    harness.log(f"exaone_moe: {work.total_params(cfg) / 1e9:.4f}B "
                f"parameters held ({work.total_params(cfg)})")
    key, frozen = seed_key(seed), _freeze(cfg)
    groups = tuple(
        _group_fn(frozen, dtype_name, kind, n, sharding)(
            key, jnp.int32(first))
        for kind, first, n in runs(cfg))
    return {**_outer_fn(frozen, dtype_name, sharding)(key),
            "blocks": groups[0] if len(groups) == 1 else groups}


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: str, dtype_name: str, kind: str):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key, l: layer(key, cfg, l, dtype, kind))


def one_layer(cfg: dict, seed: int, l: int, dtype_name: str) -> dict:
    return _layer_fn(_freeze(cfg), dtype_name, kind_of(cfg, l))(
        seed_key(seed), jnp.int32(l))


@functools.lru_cache(maxsize=None)
def _outer_fn(frozen: str, dtype_name: str, sharding=None):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key: outer(key, cfg, dtype),
                   out_shardings=sharding)


def outer_only(cfg: dict, seed: int, dtype_name: str) -> dict:
    return _outer_fn(_freeze(cfg), dtype_name)(seed_key(seed))

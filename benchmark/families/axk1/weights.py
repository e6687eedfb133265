"""The axk1 weights from ``--seed``: the program gets the whole tree in
one jitted call, in the layout ``ptype_tpu.models`` takes (one stacked
group of dense layers, one of expert layers); the plain reference
regenerates one layer at a time from the same keys.

N(0, ``initializer_range``), the projections back into the residual
stream (``wo``, ``w_down``, ``ws_down``) scaled by 1/sqrt(2L); norm
scales 1. ``topk_method`` "none": the router has no correction bias;
the program's ``router_bias`` is zeros. ``uncut_layer`` makes an expert
layer with every routed expert, for the test that adds the members'
shares up."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.families.axk1 import work
from benchmark.weights import DTYPES, normal as _normal, seed_key


def kind_of(cfg: dict, l: int) -> str:
    return "dense" if l < int(cfg["first_k_dense_replace"]) else "experts"


def layer(key: jax.Array, cfg: dict, l, dtype, kind: str) -> dict:
    """Layer ``l``'s weights, under the program's names. An expert of
    the router's width has its own key (``fold_in`` of its index), so
    that a member's held experts are the same matrices whatever the
    member holds beside them."""
    d = work.dims(cfg)
    D, H, qk = d["D"], d["H"], d["nope"] + d["rope"]
    std = float(cfg.get("initializer_range", 0.02))
    resid = std / (2.0 * d["L"]) ** 0.5
    ks = jax.random.split(jax.random.fold_in(key, l + 1), 16)
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
        "mlp_norm": jnp.ones((D,), dtype),
    }
    if kind == "dense":
        F = d["F"]
        w.update(w_gate=_normal(ks[6], (D, F), std, dtype),
                 w_up=_normal(ks[7], (D, F), std, dtype),
                 w_down=_normal(ks[8], (F, D), resid, dtype))
        return w
    Fe, Fs = d["Fe"], d["Fe"] * d["shared"]
    first = int(cfg.get("experts_held_first", 0))

    def expert(e):
        k = jax.random.split(jax.random.fold_in(ks[9], e), 3)
        return (_normal(k[0], (D, Fe), std, dtype),
                _normal(k[1], (D, Fe), std, dtype),
                _normal(k[2], (Fe, D), resid, dtype))

    wg, wu, wd = jax.lax.map(expert, first + jnp.arange(d["held"]))
    w.update(
        router=_normal(ks[10], (D, d["E"]), std, dtype),
        router_bias=jnp.zeros((d["E"],), jnp.float32),
        w_gate=wg, w_up=wu, w_down=wd,
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


_KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "intermediate_size", "moe_intermediate_size", "n_routed_experts",
         "num_experts_per_tok", "n_group", "topk_group",
         "n_shared_experts", "vocab_size", "initializer_range",
         "experts_held_first")


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

    harness.log(f"axk1: {work.total_params(cfg) / 1e9:.4f}B "
                f"parameters held ({work.total_params(cfg)})")
    return _tree_fn(_freeze(cfg), dtype_name, sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: str, dtype_name: str, kind: str):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key, l: layer(key, cfg, l, dtype, kind))


def one_layer(cfg: dict, seed: int, l: int, dtype_name: str) -> dict:
    return _layer_fn(_freeze(cfg), dtype_name, kind_of(cfg, l))(
        seed_key(seed), jnp.int32(l))


def uncut_layer(cfg: dict, seed: int, l: int, dtype_name: str) -> dict:
    """Expert layer ``l`` with all ``E`` routed experts: what the
    members of the deployment hold between them."""
    whole = {**cfg, "n_routed_experts": work.dims(cfg)["E"],
             "experts_held_first": 0}
    return one_layer(whole, seed, l, dtype_name)


@functools.lru_cache(maxsize=None)
def _outer_fn(frozen: str, dtype_name: str):
    cfg, dtype = _thaw(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key: outer(key, cfg, dtype))


def outer_only(cfg: dict, seed: int, dtype_name: str) -> dict:
    return _outer_fn(_freeze(cfg), dtype_name)(seed_key(seed))

"""The dense family's weights from ``--seed``, made by the benchmark and
handed to both sides: the program gets the whole tree in one jitted
call, in the type it serves or trains in; the plain reference
regenerates one layer at a time from the same keys, so it never needs
the program's copy (or room for a second whole model).

The tree is laid out the way the program's checkpoints are (stacked
blocks with a leading layer axis): that layout is the program's input
format, the values are the benchmark's."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.families.dense import work
from benchmark.weights import DTYPES, normal as _normal, seed_key


def layer(key: jax.Array, cfg: dict, l, dtype) -> dict:
    """Block ``l``'s weights. N(0, initializer_range), the output
    projections scaled by 1/sqrt(2L) (GPT-2's residual scaling)."""
    L, D, H, K, Dh, F, _ = work.dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    resid = std / (2.0 * L) ** 0.5
    ks = jax.random.split(jax.random.fold_in(key, l + 1), 7)
    return {
        "attn_norm": jnp.ones((D,), dtype),
        "wq": _normal(ks[0], (D, H, Dh), std, dtype),
        "wk": _normal(ks[1], (D, K, Dh), std, dtype),
        "wv": _normal(ks[2], (D, K, Dh), std, dtype),
        "wo": _normal(ks[3], (H, Dh, D), resid, dtype),
        "mlp_norm": jnp.ones((D,), dtype),
        "w_gate": _normal(ks[4], (D, F), std, dtype),
        "w_up": _normal(ks[5], (D, F), std, dtype),
        "w_down": _normal(ks[6], (F, D), resid, dtype),
    }


def outer(key: jax.Array, cfg: dict, dtype) -> dict:
    """Embedding, final norm and (untied) head."""
    _, D, *_, V = work.dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    ks = jax.random.split(jax.random.fold_in(key, 0), 2)
    out = {"embed": _normal(ks[0], (V, D), std, dtype),
           "final_norm": jnp.ones((D,), dtype)}
    if not cfg.get("tie_word_embeddings", False):
        out["lm_head"] = _normal(ks[1], (D, V), std, dtype)
    return out


def _freeze(cfg: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "tie_word_embeddings", "initializer_range")
    return tuple((k, cfg.get(k)) for k in keys)


@functools.lru_cache(maxsize=None)
def _tree_fn(frozen: tuple, dtype_name: str, sharding):
    cfg, dtype = dict(frozen), DTYPES[dtype_name]

    def make(key):
        L = work.dims(cfg)[0]
        blocks = jax.lax.map(lambda l: layer(key, cfg, l, dtype),
                             jnp.arange(L))
        return {**outer(key, cfg, dtype), "blocks": blocks}

    return jax.jit(make, out_shardings=sharding)


def tree(cfg: dict, seed: int, dtype_name: str, sharding=None) -> dict:
    """The whole model on the device, in one jitted call."""
    return _tree_fn(_freeze(cfg), dtype_name, sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: tuple, dtype_name: str):
    cfg, dtype = dict(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key, l: layer(key, cfg, l, dtype))


def one_layer(cfg: dict, seed: int, l: int, dtype_name: str) -> dict:
    return _layer_fn(_freeze(cfg), dtype_name)(seed_key(seed), jnp.int32(l))


@functools.lru_cache(maxsize=None)
def _outer_fn(frozen: tuple, dtype_name: str):
    cfg, dtype = dict(frozen), DTYPES[dtype_name]
    return jax.jit(lambda key: outer(key, cfg, dtype))


def outer_only(cfg: dict, seed: int, dtype_name: str) -> dict:
    return _outer_fn(_freeze(cfg), dtype_name)(seed_key(seed))

"""The exaone_moe family's plain reference: K-EXAONE's decoder — GQA
with a per-head q/k norm, layers of a sliding window among full ones,
a leading dense layer and then sigmoid-routed experts with a shared one
— in straightforward ``jax.numpy``. Float32 at ``highest``, no cache,
no kernels, no batching, a dense loop over the held experts, weights
made from the seed by itself, nothing imported from the program.
Computed in blocks of queries and of tokens so that a 33k-token row
fits.

The layer equations. ``x`` is ``(T, hidden_size)``; ``RMS`` is RMSNorm
with ``rms_norm_eps``; no projection has a bias.

*Attention of layer l.* ``h = RMS(x)``; ``q = h Wq`` as
``num_attention_heads`` heads of ``head_dim``, ``k = h Wk``, ``v = h
Wv`` as ``num_key_value_heads`` heads; ``q <- RMS_head(q; g_q)``, ``k
<- RMS_head(k; g_k)`` over each head's ``head_dim`` values (one scale
vector a layer, shared by the heads). Where ``sliding_windows[l]`` is a
window ``W`` (``layer_types[l]`` ``sliding_attention``): q and k rotate
(``rope_theta``, half-split pairs) and query ``i`` sees keys ``j`` with
``j <= i`` and ``i - j < W``. Where it is 0 (``full_attention``): NO
rotation, and ``j <= i``. Then ``softmax(q k^T / sqrt(head_dim)) v``,
``heads / kv_heads`` query heads a K,V head, ``o Wo``, ``x <- x + o``.

*MLP.* ``h = RMS(x)``. A ``dense`` layer: SwiGLU, ``intermediate_size``
wide. A ``sparse`` layer: ``s = sigmoid(h Wr)`` (the published router
width); the ``num_experts_per_tok`` largest of ``s + b`` are chosen
(``b`` the correction bias; ``n_group`` 1: no group limit); gates ``g =
s_chosen / sum(s_chosen) * routed_scaling_factor``; ``y = sum_chosen
g_e SwiGLU_e(h) + SwiGLU_shared(h)`` (``moe_intermediate_size`` wide).
No token is dropped. Of the chosen experts only those held here
(``experts_held_first``, ``num_experts``) are summed: what the absent
ones would add is left out, as in the program, and the partial result
goes on. ``x <- x + y``. After the last layer: ``RMS``, then the untied
head over the vocabulary slice held.

Assumptions (the configuration file lists them under ``assumed``, each
with what it rules out): the norms sit before their sub-layer; the q/k
norm and rotation on window layers only are the EXAONE 4.0 hybrid
convention; the multi-token-prediction layer is left out.

``mode`` picks what stands in the reference's place: ``f32`` (the
reference), ``bf16`` and ``fp8`` (every matmul's operands rounded, as
in ``benchmark.families.dense.reference``), and two planted departures
computed in float32, which the controls show are not rounding:
``window_as_full`` (a window layer attends to every earlier key) and
``rope_on_full`` (the full layers rotate too)."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dense.reference import mm, rms, rope
from benchmark.families.exaone_moe import weights, work

QBLOCK = 128
TBLOCK = 2048
_NEG = -1e30
#: mode -> (arithmetic of the matmuls, planted departure)
MODES = {"f32": ("f32", None), "bf16": ("bf16", None), "fp8": ("fp8", None),
         "window_as_full": ("f32", "window_as_full"),
         "rope_on_full": ("f32", "rope_on_full")}


def _blocks(a, size: int):
    """(T, ...) → (ceil(T / size), size, ...), zero-padded."""
    pad = -a.shape[0] % size
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape((-1, size) + a.shape[1:])


def attention(h, w, cfg: dict, window: int, rotate: bool, mode: str):
    """One layer's attention over one sequence. h: (T, D) normed input
    → (T, D). ``window`` 0: causal over every key; else over the last
    ``window`` positions, the query's own included."""
    d = work.dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    T = h.shape[0]
    G = d["H"] // d["K"]
    k = rms(mm("td,dkh->tkh", h, w["wk"], mode), w["k_norm"], eps)
    v = mm("td,dkh->tkh", h, w["wv"], mode)
    if rotate:
        k = rope(k, jnp.arange(T), theta)
    # A window layer's query block reads only the keys it can see: the
    # ``window`` before its first query through its last.
    span = T if not window else QBLOCK + window
    if window:
        k = jnp.pad(k, ((window, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((window, 0), (0, 0), (0, 0)))
        pad_t = -T % QBLOCK
        k = jnp.pad(k, ((0, pad_t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad_t), (0, 0), (0, 0)))

    def one(args):
        i, hb = args
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        q = rms(mm("td,dnh->tnh", hb, w["wq"], mode), w["q_norm"], eps)
        if rotate:
            q = rope(q, qpos, theta)
        if window:
            kb = jax.lax.dynamic_slice_in_dim(k, i * QBLOCK, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, i * QBLOCK, span, 0)
            kpos = i * QBLOCK - window + jnp.arange(span)
        else:
            kb, vb, kpos = k, v, jnp.arange(T)
        see = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window:
            see &= qpos[:, None] - kpos[None, :] < window
        qg = q.reshape(QBLOCK, d["K"], G, d["Dh"])
        s = mm("qkgd,skd->kgqs", qg, kb, mode) / math.sqrt(d["Dh"])
        p = jax.nn.softmax(jnp.where(see[None, None], s, _NEG), axis=-1)
        o = mm("kgqs,skd->qkgd", p, vb, mode)
        return mm("qnh,nhd->qd", o.reshape(QBLOCK, d["H"], d["Dh"]),
                  w["wo"], mode)

    hb = _blocks(h, QBLOCK)
    out = jax.lax.map(one, (jnp.arange(hb.shape[0]), hb))
    return out.reshape(-1, h.shape[1])[:T]


def swiglu(h, w_gate, w_up, w_down, mode: str):
    gate = mm("td,df->tf", h, w_gate, mode)
    up = mm("td,df->tf", h, w_up, mode)
    return mm("tf,fd->td", jax.nn.silu(gate) * up, w_down, mode)


def route(h, w, cfg: dict, mode: str):
    """→ (chosen experts (T, per_tok), their gates (T, per_tok))."""
    s = jax.nn.sigmoid(mm("td,de->te", h, w["router"], mode))
    _, idx = jax.lax.top_k(s + w["router_bias"].astype(jnp.float32),
                           int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    g = float(cfg["routed_scaling_factor"]) * chosen / jnp.sum(
        chosen, axis=-1, keepdims=True)
    return idx, g


def experts(h, w, cfg: dict, mode: str, held=None, shared: bool = True):
    """The expert layer's MLP over a block of tokens: the routed part
    of the experts held (``held`` = (first, count) of the router's
    width; the weights ``w`` hold exactly those), and the shared
    expert (``shared`` False leaves it out: the test that adds the
    shares up counts it once)."""
    first, count = held if held is not None else (
        int(cfg.get("experts_held_first", 0)), work.dims(cfg)["held"])
    idx, g = route(h, w, cfg, mode)

    def add(y, e):
        ge = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        out = swiglu(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], mode)
        return y + ge[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(count))
    if shared:
        y = y + swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mode)
    return y


def mlp(h, w, cfg: dict, mode: str):
    """The layer's MLP over one sequence, a block of tokens at a time
    (a 33k-token row's gate and up in the dense layer are 2.4 GB each
    in float32)."""
    T = h.shape[0]

    def one(hb):
        if "router" in w:
            return experts(hb, w, cfg, mode)
        return swiglu(hb, w["w_gate"], w["w_up"], w["w_down"], mode)

    return jax.lax.map(one, _blocks(h, min(TBLOCK, T))).reshape(
        -1, h.shape[1])[:T]


def attention_kind(cfg: dict, l: int, mode: str) -> tuple[int, bool]:
    """(window, rotate) of layer ``l`` as ``mode`` runs it."""
    depart = MODES[mode][1]
    window = int(cfg["sliding_windows"][l])
    rotate = bool(window) or depart == "rope_on_full"
    return (0 if depart == "window_as_full" else window), rotate


def block(x, w, cfg: dict, window: int, rotate: bool, arith: str):
    """One layer over one sequence, its attention kind given (the
    weights say which MLP). x: (T, D) float32."""
    eps = float(cfg["rms_norm_eps"])
    x = x + attention(rms(x, w["attn_norm"], eps), w, cfg, window, rotate,
                      arith)
    return x + mlp(rms(x, w["mlp_norm"], eps), w, cfg, arith)


# ------------------------------------------------------------- serving


def _freeze_all(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _serve_fns(frozen: str, mode: str):
    cfg = json.loads(frozen)

    @functools.partial(jax.jit, donate_argnums=(0,),
                       static_argnums=(2, 3))
    def block_of_row(x, w, window, rotate):
        """One layer over one row (T, D); the row's buffer is reused."""
        return block(x, w, cfg, window, rotate, MODES[mode][0])

    @jax.jit
    def logits_at(x, outer, idx):
        """x: (T, D); idx: (n,) positions → (n, V) logits."""
        h = rms(x[idx], outer["final_norm"], float(cfg["rms_norm_eps"]))
        return mm("nd,dv->nv", h, outer["lm_head"], MODES[mode][0])

    return block_of_row, logits_at


def _row_length(last: int, cap: int) -> int:
    """Tokens of a row the reference runs: through its last compared
    position (causal: what follows cannot matter), rounded up to a
    power of two from 1,024 so that a run compiles few shapes."""
    n = 1024
    while n < last + 1:
        n *= 2
    return min(n, cap)


def served_logits(cfg: dict, seed: int, dtype_name: str, tokens, idx,
                  modes=("f32",)) -> dict:
    """Full forward of each row of ``tokens`` (R, T) — prompts with
    their served tokens, right-padded — returning for each mode the
    logits (R, n, V) at positions ``idx`` (R, n). A mode at a time and
    within it layer by layer: one layer's weights are regenerated from
    the seed at a time and shared by the rows, each cut to its own
    length, so neither 12 GB of weights nor every mode's rows stand
    side by side."""
    frozen = _freeze_all(cfg)
    outer = weights.outer_only(cfg, seed, dtype_name)
    idx_host = np.asarray(idx)
    lens = [_row_length(int(idx_host[r].max()), tokens.shape[1])
            for r in range(tokens.shape[0])]
    out = {}
    for m in modes:
        step, logits_at = _serve_fns(frozen, m)
        xs = [outer["embed"][row[:n]].astype(jnp.float32)
              for row, n in zip(tokens, lens)]
        for l in range(work.dims(cfg)["L"]):
            w = weights.one_layer(cfg, seed, l, dtype_name)
            xs = [step(x, w, *attention_kind(cfg, l, m)) for x in xs]
        out[m] = jnp.stack([logits_at(x, outer, idx[r])
                            for r, x in enumerate(xs)])
    return out


def train_steps(cfg: dict, hp: dict, params, batches: list, mode: str,
                micro_rows: int, rows=None, frozen_state: bool = False):
    raise SystemExit(work._WHY_NOT)

"""The glm_moe_dsa family's plain reference: GLM-5's decoder (the
DeepSeek-V2/V3 latent attention and expert layer with the DeepSeek-V3.2
sparse-attention indexer, at GLM's numbers) in straightforward
``jax.numpy``. Float32 at ``highest``, expanded attention only, no
cache, no kernels, a dense loop over the held experts, weights made from
the seed by itself, nothing imported from the program. Computed in query
blocks so that a 19k-token row fits.

The layer equations. ``x`` is the RMSNorm'd input of a sub-layer (eps
``rms_norm_eps``); no projection has a bias.

*Attention (every layer).* ``cQ = RMSNorm(W_DQ x)`` (q_lora_rank).
``q_h = W_UQ,h cQ`` (heads × qk_head_dim), split into ``q_h^nope``
(qk_nope_head_dim) and ``q_h^rope`` (qk_rope_head_dim), RoPE on the
latter (adjacent pairs: ``rope_interleave``; theta ``rope_theta``).
``[cKV ; kR] = W_DKV x`` (kv_lora_rank + rope); ``cKV ← RMSNorm(cKV)``;
``kR ← RoPE(kR)``, one key for all heads. ``[k_h^nope ; v_h] = W_UKV,h
cKV``. Score of query ``t`` on key ``s``: ``(q_t,h^nope · k_s,h^nope +
q_t,h^rope · kR_s) / sqrt(qk_head_dim)``, softmax over the selected set
``S_t``, ``o_h = Σ a v_s,h``, output ``W_O [o_1 .. o_H]``.

*Indexer (every layer).* ``qI_t,j = W_IQ,j cQ_t`` (index_n_heads ×
index_head_dim), ``kI_s = LayerNorm(W_IK x_s)``, RoPE on the leading
``qk_rope_head_dim`` dims of both, ``w_t = W_Iw x_t``. ``I_t,s = Σ_j
w_t,j · ReLU(qI_t,j · kI_s)``. ``S_t`` = the ``index_topk`` keys ``s ≤
t`` with the largest ``I_t,s``; all of them while ``t < index_topk``.

*Expert layer.* ``s = sigmoid(W_G x)`` (the published router width).
The ``num_experts_per_tok`` experts with the largest ``s_e + b_e`` are
chosen (``b`` the correction bias; ``n_group`` 1: no group limit);
gates ``g_e = routed_scaling_factor · s_e / Σ_chosen s``. ``y =
Σ_chosen g_e · SwiGLU_e(x) + SwiGLU_shared(x)``. No token is dropped.
Of the chosen experts only those held here (``experts_held``) are
summed: what the absent ones would add is left out, as in the program,
and the partial result goes on.

*Dense layer.* SwiGLU. Final RMSNorm, untied head over the vocabulary
slice held.

Departures and assumptions (the configuration file lists them under
``assumed``): the indexer follows the public DeepSeek-V3.2 inference
code — the *leading* 64 dims of qI and kI rotate, the LayerNorm has a
bias and eps 1e-6, ``w`` is scaled by ``index_n_heads^-0.5 ·
index_head_dim^-0.5`` (positive: no effect on ``S_t``); its Hadamard
rotation of qI and kI (orthogonal, there for float8) and its float8
quantisation are left out. The multi-token-prediction module is left
out: the served logits do not depend on it.

``mode`` picks the arithmetic of every matmul (``f32`` the reference;
``bf16``, ``fp8`` what the controls put in the program's place), as in
``benchmark.families.dense.reference``."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.families.dense.reference import mm, rms
from benchmark.families.glm_moe_dsa import weights, work

QBLOCK = 128
INDEX_NORM_EPS = 1e-6
_NEG = -1e30


def rope_pairs(x, positions, theta):
    """Rotate adjacent pairs (x[2i], x[2i+1]). x: (T, d) or (T, n, d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if x.ndim == 3:
        sin, cos = sin[:, None], cos[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def rope_leading(x, positions, theta, n):
    return jnp.concatenate(
        [rope_pairs(x[..., :n], positions, theta), x[..., n:]], axis=-1)


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def attention(h, w, cfg: dict, mode: str, select: str = "indexer"):
    """One layer's attention over one sequence. h: (T, D) normed input
    → (T, D). ``select``: ``indexer`` (the model), or ``recent`` — the
    most recent ``index_topk`` keys, which a test puts in the program's
    place to show that leaving the indexer out is not rounding."""
    d = work.dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    T = h.shape[0]
    pos = jnp.arange(T)
    cq = rms(mm("td,dr->tr", h, w["w_dq"], mode), w["q_norm"], eps)
    kv = mm("td,dc->tc", h, w["w_dkv"], mode)
    c_kv = rms(kv[:, :d["c"]], w["kv_norm"], eps)
    k_r = rope_pairs(kv[:, d["c"]:], pos, theta)
    k_nope = mm("tc,chn->thn", c_kv, w["w_uk"], mode)
    v = mm("tc,chv->thv", c_kv, w["w_uv"], mode)
    ki = rope_leading(
        layer_norm(mm("td,dk->tk", h, w["w_ik"], mode), w["ik_norm"],
                   w["ik_norm_b"], INDEX_NORM_EPS), pos, theta, d["rope"])
    wi = mm("td,dj->tj", h, w["w_iw"], mode) * (
        d["J"] ** -0.5 * d["di"] ** -0.5)

    nblk = -(-T // QBLOCK)
    pad = nblk * QBLOCK - T

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nblk, QBLOCK) + a.shape[1:])

    kpos = jnp.arange(T)
    k_sel = min(d["topk"], T)

    def one(args):
        # A block of queries: their projections are made here, from the
        # block's rows of cQ, so that only the keys and values of the
        # whole row are held throughout (19k tokens x 64 heads x 256
        # in float32 is 1.3 GB a tensor).
        i, cqb, wib = args
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        causal = kpos[None, :] <= qpos[:, None]
        q = mm("tr,rhk->thk", cqb, w["w_uq"], mode)
        qn = q[..., :d["nope"]]
        qr = rope_pairs(q[..., d["nope"]:], qpos, theta)
        if select == "recent":
            chosen = kpos[None, :] > qpos[:, None] - k_sel
        else:
            qib = rope_leading(mm("tr,rjk->tjk", cqb, w["w_iq"], mode),
                               qpos, theta, d["rope"])
            dots = mm("qjd,sd->qjs", qib, ki, mode)
            I = jnp.sum(wib[:, :, None] * jax.nn.relu(dots), axis=1)
            I = jnp.where(causal, I, _NEG)
            _, idx = jax.lax.top_k(I, k_sel)
            chosen = jnp.zeros((QBLOCK, T), bool).at[
                jnp.arange(QBLOCK)[:, None], idx].set(True)
        s = (mm("qhn,shn->hqs", qn, k_nope, mode)
             + mm("qhr,sr->hqs", qr, k_r, mode)) / math.sqrt(
                 d["nope"] + d["rope"])
        s = jnp.where((chosen & causal)[None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = mm("hqs,shv->qhv", p, v, mode)
        return mm("thv,hvd->td", o, w["wo"], mode)

    out = jax.lax.map(one, (jnp.arange(nblk), blocks(cq), blocks(wi)))
    return out.reshape(nblk * QBLOCK, -1)[:T]


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


def experts(h, w, cfg: dict, mode: str, held=None):
    """The expert layer's MLP over one sequence: the routed part of the
    experts held (``held`` = (first, count) of the router's width; the
    weights ``w`` hold exactly those), and the shared expert."""
    first, count = held if held is not None else (
        int(cfg.get("experts_held_first", 0)), work.dims(cfg)["held"])
    idx, g = route(h, w, cfg, mode)

    def add(y, e):
        ge = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        out = swiglu(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], mode)
        return y + ge[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(count))
    return y + swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mode)


def block(x, w, cfg: dict, mode: str, select: str = "indexer"):
    """One layer over one sequence. x: (T, D) float32."""
    eps = float(cfg["rms_norm_eps"])
    x = x + attention(rms(x, w["attn_norm"], eps), w, cfg, mode, select)
    h = rms(x, w["mlp_norm"], eps)
    if "router" in w:
        return x + experts(h, w, cfg, mode)
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mode)


# ------------------------------------------------------------- serving


def _freeze_all(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _serve_fns(frozen: str, mode: str, select: str):
    cfg = json.loads(frozen)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def block_of_row(x, w):
        """One layer over one row (T, D); the row's buffer is reused."""
        return block(x, w, cfg, mode, select)

    @jax.jit
    def logits_at(x, outer, idx):
        """x: (R, T, D); idx: (R, n) positions → (R, n, V) logits."""
        rows = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        h = rms(rows, outer["final_norm"], float(cfg["rms_norm_eps"]))
        return mm("rnd,dv->rnv", h, outer["lm_head"], mode)

    return block_of_row, logits_at


def served_logits(cfg: dict, seed: int, dtype_name: str, tokens, idx,
                  modes=("f32",), select: str = "indexer") -> dict:
    """Full forward of each row of ``tokens`` (R, T) — prompts with
    their served tokens, right-padded — returning for each mode the
    logits (R, n, V) at positions ``idx`` (R, n). Layer by layer: one
    layer's weights are regenerated from the seed at a time, shared by
    every mode and row."""
    frozen = _freeze_all(cfg)
    outer = weights.outer_only(cfg, seed, dtype_name)
    # A row at a time (a 19k-token row's keys and values are 2 GB in
    # float32): each mode keeps its rows apart.
    xs = {m: [outer["embed"][row].astype(jnp.float32) for row in tokens]
          for m in modes}
    for l in range(work.dims(cfg)["L"]):
        w = weights.one_layer(cfg, seed, l, dtype_name)
        for m in modes:
            step = _serve_fns(frozen, m, select)[0]
            xs[m] = [step(x, w) for x in xs[m]]
    return {m: _serve_fns(frozen, m, select)[1](jnp.stack(xs[m]), outer,
                                                idx)
            for m in modes}


def train_steps(cfg: dict, hp: dict, params, batches: list, mode: str,
                micro_rows: int, rows=None, frozen_state: bool = False):
    raise SystemExit(work._WHY_NOT)

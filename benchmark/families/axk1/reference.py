"""The axk1 family's plain reference: A.X-K1's decoder (DeepSeek-V3's
latent attention with YaRN positions, a leading dense layer, then
sigmoid-routed experts chosen inside groups with a shared one) in
straightforward ``jax.numpy``. Float32 at ``highest``, attention in the
expanded form over the whole sequence, no cache, no kernels, no
batching, a dense loop over the held experts, weights made from the
seed by itself, nothing imported from the program. Computed in query
blocks so that a 19k-token row fits.

The layer equations. Pre-norm blocks: ``h = x + Attn(RMSNorm(x))``,
``h + MLP(RMSNorm(h))`` (eps ``rms_norm_eps``); no projection has a
bias.

*Attention (every layer).* ``cQ = RMSNorm(x W_DQ)`` (q_lora_rank).
``q_h = cQ W_UQ,h`` (heads × 192), split into ``q_h^nope`` (128) and
``q_h^rope`` (64), rotated. ``[c ; kR] = x W_DKV`` (512 + 64); ``c ←
RMSNorm(c)``; ``kR ← rot(kR)``, one rotary key for all heads. ``k_h =
[c W_UK,h ; kR]``, ``v_h = c W_UV,h``. ``softmax(q_h k_h^T · 192^-0.5 ·
m² + causal)``, every key ``s ≤ t``: there is no indexer. ``o W_O``.

*Positions: YaRN* (DeepSeek-V3's ``modeling_deepseek.py``). Pair ``i``
of the 32 has the frequency ``θ^(-2i/64)``, divided by ``factor`` where
the ramp says so: with ``dim(r) = 64 · ln(original_max / (2π r)) / (2
ln θ)``, ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``
(clamped to 0 .. 63), ``ramp_i = clip((i − low) / (high − low), 0, 1)``,
``f_i = θ^(-2i/64) · ((1 − ramp_i) + ramp_i / factor)``. Adjacent pairs
``(x[2i], x[2i+1])`` rotate by ``t · f_i``; sin and cos are multiplied
by ``ms(factor, mscale) / ms(factor, mscale_all_dim)`` with ``ms(s, m) =
0.1 m ln s + 1`` (1 here), and the scores by ``m² = ms(factor,
mscale_all_dim)²`` (1.3466² = 1.8133).

*Expert layer.* ``s = sigmoid(x W_R)`` (the published router width,
192). The scores form ``n_group`` (8) runs of 24; a group's standing is
the sum of its two largest; the ``topk_group`` (4) best groups stay;
the ``num_experts_per_tok`` (8) largest scores among their 96 experts
are chosen (``topk_method`` "none": no correction bias); gates ``g_e =
routed_scaling_factor · s_e / Σ_chosen s``. ``y = shared(x) + Σ_{chosen
∩ held} g_e · expert_e(x)``. No token is dropped; what the absent
experts would add is left out, as in the program, and the partial
result goes on.

*Dense layer.* SwiGLU. Final RMSNorm, untied head over the vocabulary
slice held.

``mode`` picks the arithmetic of every matmul (``f32`` the reference;
``bf16``, ``fp8`` what the controls put in the program's place), as in
``benchmark.families.dense.reference``. Three more modes plant one
departure each for the controls, float32 otherwise: ``no_yarn`` (plain
rotary tables, plain scale), ``no_mscale`` (YaRN's tables, the scores
without ``m²``), ``no_groups`` (the 8 largest of all 192 scores)."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.families.axk1 import weights, work
from benchmark.families.dense.reference import mm, rms

QBLOCK = 128
DEPARTURES = ("no_yarn", "no_mscale", "no_groups")
_NEG = -1e30


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_frequencies(dim: int, theta: float, y: dict | None):
    """→ (the ``dim/2`` pair frequencies, the factor on sin and cos)."""
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if not y:
        return freq, 1.0

    def pair_of(turns):
        return (dim * math.log(int(y["original_max_position_embeddings"])
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(float(y["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(y["beta_slow"]))), dim - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    factor = float(y["factor"])
    return (freq * ((1.0 - ramp) + ramp / factor),
            yarn_mscale(factor, float(y.get("mscale", 1)))
            / yarn_mscale(factor, float(y.get("mscale_all_dim", 0))))


def score_scale(cfg: dict, depart=None) -> float:
    d = work.dims(cfg)
    y = cfg.get("rope_scaling")
    m = 1.0
    if y and y.get("mscale_all_dim") and depart not in ("no_yarn",
                                                        "no_mscale"):
        m = yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    return (d["nope"] + d["rope"]) ** -0.5 * m * m


def rot_pairs(x, positions, cfg: dict, depart=None):
    """Rotate adjacent pairs (x[2i], x[2i+1]) by YaRN's tables.
    x: (T, d) or (T, n, d)."""
    y = None if depart == "no_yarn" else cfg.get("rope_scaling")
    freq, m = yarn_frequencies(x.shape[-1], float(cfg["rope_theta"]), y)
    ang = positions.astype(jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang) * m, jnp.cos(ang) * m
    if x.ndim == 3:
        sin, cos = sin[:, None], cos[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(h, w, cfg: dict, mode: str, depart=None):
    """One layer's attention over one sequence. h: (T, D) normed input
    → (T, D)."""
    d = work.dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    T = h.shape[0]
    pos = jnp.arange(T)
    cq = rms(mm("td,dr->tr", h, w["w_dq"], mode), w["q_norm"], eps)
    kv = mm("td,dc->tc", h, w["w_dkv"], mode)
    c = rms(kv[:, :d["c"]], w["kv_norm"], eps)
    k_r = rot_pairs(kv[:, d["c"]:], pos, cfg, depart)
    k_nope = mm("tc,chn->thn", c, w["w_uk"], mode)
    v = mm("tc,chv->thv", c, w["w_uv"], mode)
    scale = score_scale(cfg, depart)

    nblk = -(-T // QBLOCK)
    pad = nblk * QBLOCK - T
    cqb = jnp.pad(cq, ((0, pad), (0, 0))).reshape(nblk, QBLOCK, -1)

    def one(args):
        # A block of queries: their projections are made here, from the
        # block's rows of cQ, so that only the keys and values of the
        # whole row are held throughout.
        i, cqi = args
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        q = mm("tr,rhk->thk", cqi, w["w_uq"], mode)
        qn = q[..., :d["nope"]]
        qr = rot_pairs(q[..., d["nope"]:], qpos, cfg, depart)
        s = (mm("qhn,shn->hqs", qn, k_nope, mode)
             + mm("qhr,sr->hqs", qr, k_r, mode)) * scale
        s = jnp.where((pos[None, :] <= qpos[:, None])[None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = mm("hqs,shv->qhv", p, v, mode)
        return mm("thv,hvd->td", o, w["wo"], mode)

    out = jax.lax.map(one, (jnp.arange(nblk), cqb))
    return out.reshape(nblk * QBLOCK, -1)[:T]


def swiglu(h, w_gate, w_up, w_down, mode: str):
    gate = mm("td,df->tf", h, w_gate, mode)
    up = mm("td,df->tf", h, w_up, mode)
    return mm("tf,fd->td", jax.nn.silu(gate) * up, w_down, mode)


def route(h, w, cfg: dict, mode: str, depart=None):
    """→ (chosen experts (T, per_tok), their gates (T, per_tok))."""
    d = work.dims(cfg)
    s = jax.nn.sigmoid(mm("td,de->te", h, w["router"], mode))
    sel = s
    if d["groups"] > 1 and depart != "no_groups":
        by_group = s.reshape(s.shape[0], d["groups"], -1)
        standing = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, best = jax.lax.top_k(standing, d["kept"])
        stays = jnp.zeros(standing.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], best].set(True)
        sel = jnp.where(jnp.repeat(stays, d["E"] // d["groups"], axis=1),
                        s, -jnp.inf)
    _, idx = jax.lax.top_k(sel, d["per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    g = float(cfg["routed_scaling_factor"]) * chosen / jnp.sum(
        chosen, axis=-1, keepdims=True)
    return idx, g


def routed(h, w, cfg: dict, mode: str, held, depart=None):
    """The routed part of the experts ``held`` = (first, count) of the
    router's width; ``w`` holds exactly those."""
    first, count = held
    idx, g = route(h, w, cfg, mode, depart)

    def add(y, e):
        ge = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)
        out = swiglu(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], mode)
        return y + ge[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(count))
    return y


def shared(h, w, mode: str):
    return swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mode)


def experts(h, w, cfg: dict, mode: str, depart=None):
    """The expert layer's MLP over one sequence: the routed part of
    the experts held here, and the shared expert."""
    held = (int(cfg.get("experts_held_first", 0)), work.dims(cfg)["held"])
    return routed(h, w, cfg, mode, held, depart) + shared(h, w, mode)


def block(x, w, cfg: dict, mode: str, depart=None):
    """One layer over one sequence. x: (T, D) float32."""
    eps = float(cfg["rms_norm_eps"])
    x = x + attention(rms(x, w["attn_norm"], eps), w, cfg, mode, depart)
    h = rms(x, w["mlp_norm"], eps)
    if "router" in w:
        return x + experts(h, w, cfg, mode, depart)
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mode)


# ------------------------------------------------------------- serving


@functools.lru_cache(maxsize=None)
def _serve_fns(frozen: str, mode: str):
    cfg = json.loads(frozen)
    mode, depart = ("f32", mode) if mode in DEPARTURES else (mode, None)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def block_of_row(x, w):
        """One layer over one row (T, D); the row's buffer is reused."""
        return block(x, w, cfg, mode, depart)

    @jax.jit
    def logits_at(x, outer, idx):
        """x: (R, T, D); idx: (R, n) positions → (R, n, V) logits."""
        rows = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        h = rms(rows, outer["final_norm"], float(cfg["rms_norm_eps"]))
        return mm("rnd,dv->rnv", h, outer["lm_head"], mode)

    return block_of_row, logits_at


def served_logits(cfg: dict, seed: int, dtype_name: str, tokens, idx,
                  modes=("f32",)) -> dict:
    """Full forward of each row of ``tokens`` (R, T) — prompts with
    their served tokens, right-padded — returning for each mode the
    logits (R, n, V) at positions ``idx`` (R, n). Layer by layer: one
    layer's weights are regenerated from the seed at a time, shared by
    every mode and row."""
    frozen = json.dumps(cfg, sort_keys=True)
    outer = weights.outer_only(cfg, seed, dtype_name)
    # A row at a time (a 19k-token row's keys and values are 1.6 GB in
    # float32): each mode keeps its rows apart.
    xs = {m: [outer["embed"][row].astype(jnp.float32) for row in tokens]
          for m in modes}
    for l in range(work.dims(cfg)["L"]):
        w = weights.one_layer(cfg, seed, l, dtype_name)
        for m in modes:
            step = _serve_fns(frozen, m)[0]
            xs[m] = [step(x, w) for x in xs[m]]
    return {m: _serve_fns(frozen, m)[1](jnp.stack(xs[m]), outer,
                                                idx)
            for m in modes}


def train_steps(cfg: dict, hp: dict, params, batches: list, mode: str,
                micro_rows: int, rows=None, frozen_state: bool = False):
    raise SystemExit(work._WHY_NOT)

"""The dense family's plain reference: a decoder-only transformer
(RMSNorm, rotary positions in the half-split convention, grouped-query
causal attention, SwiGLU), its loss, gradients and AdamW, in
straightforward ``jax.numpy``. No kernels, no cache, no batching tricks,
nothing imported from the program. ``mode`` picks the arithmetic of
every matmul:

- ``f32``  float32 operands at ``highest`` precision — the reference;
- ``bf16`` operands rounded to bfloat16 — what the configurations state;
- ``fp8``  operands rounded to float8_e4m3 after per-tensor scaling — the
  nearest precision below, which the controls put in the program's place.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.families.dense import weights, work

QBLOCK = 512


def _round(a, mode: str):
    """``a`` at the mode's precision. The rounding is straight-through
    for gradients: a backward pass sees the rounded forward values, and
    its own cotangents stay float32 (unscaled float8 would flush a
    gradient of 1e-5 to zero and fail the control for the wrong reason)."""
    a = a.astype(jnp.float32)
    if mode == "f32":
        return a
    # ``reduce_precision`` and not a cast there and back: XLA may drop a
    # pair of converts as excess precision (on the TPU it does, and the
    # "rounded" operand stays float32), never this.
    if mode == "bf16":
        q = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    elif mode == "fp8":
        # float8_e4m3 after per-tensor scaling: the largest magnitude
        # lands on 224, inside what 4 exponent bits hold.
        amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        scale = 224.0 / amax
        q = jax.lax.reduce_precision(a * scale, exponent_bits=4,
                                     mantissa_bits=3) / scale
    else:
        raise ValueError(f"reference: unknown mode {mode!r}")
    return a + jax.lax.stop_gradient(q - a)


def mm(spec: str, a, b, mode: str):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision="highest",
                      preferred_element_type=jnp.float32)


def rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope(x, positions, theta):
    """x: (T, heads, Dh); rotates (first half, second half) pairs."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode: str):
    """Causal GQA over one sequence. q: (T, H, Dh); k, v: (T, K, Dh).
    Query blocks keep the (heads, block, T) scores small."""
    T, H, Dh = q.shape
    K = k.shape[1]
    qg = q.reshape(T, K, H // K, Dh)
    nblk = -(-T // QBLOCK)
    pad = nblk * QBLOCK - T
    qg = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0)))
    qb = qg.reshape(nblk, QBLOCK, K, H // K, Dh)
    kpos = jnp.arange(T)

    def one(args):
        i, qblk = args
        s = mm("qngd,snd->ngqs", qblk, k, mode) / math.sqrt(Dh)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return mm("ngqs,snd->qngd", p, v, mode)

    o = jax.lax.map(one, (jnp.arange(nblk), qb))
    return o.reshape(nblk * QBLOCK, H, Dh)[:T]


def block(x, w, cfg: dict, mode: str):
    """One block over one sequence. x: (T, D) float32."""
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    pos = jnp.arange(x.shape[0])
    h = rms(x, w["attn_norm"], eps)
    q = rope(mm("td,dhk->thk", h, w["wq"], mode), pos, theta)
    k = rope(mm("td,dhk->thk", h, w["wk"], mode), pos, theta)
    v = mm("td,dhk->thk", h, w["wv"], mode)
    x = x + mm("thk,hkd->td", attention(q, k, v, mode), w["wo"], mode)
    h = rms(x, w["mlp_norm"], eps)
    gate = mm("td,df->tf", h, w["w_gate"], mode)
    up = mm("td,df->tf", h, w["w_up"], mode)
    return x + mm("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"], mode)


def head_of(outer: dict, cfg: dict):
    return (outer["embed"].T if cfg.get("tie_word_embeddings", False)
            else outer["lm_head"])


# ------------------------------------------------------------- serving


@functools.lru_cache(maxsize=None)
def _serve_fns(frozen: tuple, mode: str):
    cfg = dict(frozen)

    @jax.jit
    def blocks_over_rows(x, w):
        return jax.lax.map(lambda row: block(row, w, cfg, mode), x)

    @jax.jit
    def logits_at(x, outer, idx):
        """x: (R, T, D); idx: (R, n) positions → (R, n, V) logits."""
        rows = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        h = rms(rows, outer["final_norm"], float(cfg["rms_norm_eps"]))
        return mm("rnd,dv->rnv", h, head_of(outer, cfg), mode)

    return blocks_over_rows, logits_at


def _freeze_all(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))
                        or v is None))


def served_logits(cfg: dict, seed: int, dtype_name: str, tokens, idx,
                  modes=("f32",)) -> dict:
    """Full forward of each row of ``tokens`` (R, T) — prompts with
    their served tokens, right-padded — returning for each mode the
    logits (R, n, V) at positions ``idx`` (R, n). Layer by layer: one
    block's weights are regenerated from the seed at a time, shared by
    every mode."""
    frozen = _freeze_all(cfg)
    outer = weights.outer_only(cfg, seed, dtype_name)
    xs = {m: outer["embed"][tokens].astype(jnp.float32) for m in modes}
    for l in range(work.dims(cfg)[0]):
        w = weights.one_layer(cfg, seed, l, dtype_name)
        for m in modes:
            xs[m] = _serve_fns(frozen, m)[0](xs[m], w)
    return {m: _serve_fns(frozen, m)[1](xs[m], outer, idx) for m in modes}


# ------------------------------------------------------------ training


def nll_sum(params: dict, tokens, targets, cfg: dict, mode: str):
    """Summed next-token cross-entropy of a (rows, S) micro-batch."""
    L = work.dims(cfg)[0]
    head = head_of(params, cfg)

    def one(args):
        toks, tgts = args
        x = params["embed"][toks].astype(jnp.float32)
        for l in range(L):
            w = jax.tree.map(lambda a: a[l], params["blocks"])
            x = block(x, w, cfg, mode)
        h = rms(x, params["final_norm"], float(cfg["rms_norm_eps"]))
        logits = mm("td,dv->tv", h, head, mode)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgts[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold)

    return jnp.sum(jax.lax.map(one, (tokens, targets)))


@functools.lru_cache(maxsize=None)
def _grad_fn(frozen: tuple, mode: str):
    cfg = dict(frozen)
    return jax.jit(jax.value_and_grad(
        lambda p, t, y: nll_sum(p, t, y, cfg, mode)))


def loss_and_grads(params, batch: dict, cfg: dict, mode: str,
                   micro_rows: int, rows=None):
    """Mean loss and its gradient over ``rows`` of the batch (default
    all), accumulated over micro-batches so that it fits beside nothing."""
    fn = _grad_fn(_freeze_all(cfg), mode)
    toks, tgts = batch["tokens"], batch["targets"]
    if rows is not None:
        rows = jnp.asarray(rows, jnp.int32)
        toks, tgts = toks[rows], tgts[rows]
    n = toks.shape[0]
    total, grads = 0.0, None
    for i in range(0, n, micro_rows):
        v, g = fn(params, toks[i:i + micro_rows], tgts[i:i + micro_rows])
        total = total + v
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    denom = float(toks.size)
    return total / denom, jax.tree.map(lambda g: g / denom, grads)


def lr_at(count, hp: dict):
    """Linear warm-up from 0 to ``lr``, then cosine decay to 10% of it
    at ``decay_steps`` (optax's warmup_cosine_decay_schedule)."""
    count = jnp.asarray(count, jnp.float32)
    warm, lr = float(hp["warmup"]), float(hp["lr"])
    frac = jnp.clip((count - warm) / max(hp["decay_steps"] - warm, 1.0),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    decayed = lr * (0.1 + 0.9 * cos)
    return jnp.where(count < warm, lr * count / max(warm, 1.0), decayed)


def decays(path) -> bool:
    """AdamW's mask: matmul weights decay, norm scales do not."""
    return "norm" not in jax.tree_util.keystr(path)


@functools.partial(jax.jit, static_argnames=("hp_items",))
def adamw_step(params, grads, mu, nu, count, hp_items: tuple):
    """Clip by global norm, Adam moments with bias correction, decoupled
    weight decay, all scaled by the schedule. → (params, mu, nu, clipped)."""
    hp = dict(hp_items)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["clip"] / jnp.maximum(gnorm, 1e-30))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    t = count + 1
    lr = lr_at(count, hp)

    def upd(path, p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["eps"])
        if decays(path):
            u = u + hp["weight_decay"] * p
        return p - lr * u

    params = jax.tree_util.tree_map_with_path(upd, params, mu, nu)
    return params, mu, nu, grads


def train_steps(cfg: dict, hp: dict, params, batches: list, mode: str,
                micro_rows: int, rows=None, frozen_state: bool = False):
    """Follow the first ``len(batches)`` steps. → losses, the first
    clipped gradient, and the parameters after the last step.
    ``rows`` and ``frozen_state`` plant the faults the controls need
    (part of the batch left out; the state returned unchanged)."""
    hp_items = tuple(sorted(hp.items()))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        loss, grads = loss_and_grads(params, batch, cfg, mode, micro_rows,
                                     rows)
        losses.append(loss)
        new, mu, nu, clipped = adamw_step(params, grads, mu, nu,
                                          jnp.int32(i), hp_items)
        if first is None:
            first = clipped
        if not frozen_state:
            params = new
    return losses, first, params

"""Pipeline parallelism over the ``stage`` mesh axis — compiled SPMD.

The reference's closest analog was actor-per-service topology
(cluster/registry.go:17-21; SURVEY.md §2 parallelism table "PP"). The
TPU-native lowering is NOT per-layer RPC: all stages run ONE compiled
SPMD program; microbatches flow around the ``stage`` ring via
``lax.ppermute`` inside a ``lax.scan`` over pipeline ticks (GPipe-style
schedule, bubble = (S-1)/(M+S-1)). Autodiff through the scan+ppermute
gives the reverse pipeline for free — ppermute's transpose is the
reverse rotation, so one ``jax.grad`` yields forward AND backward
pipelining with no hand-written schedule.

Layer split: the transformer's stacked blocks (leading ``n_layers`` dim,
models/transformer.py init_params) reshape to ``(S, L/S, ...)`` and
shard dim 0 over ``stage`` — each device holds only its stage's layers,
the actor-per-layer memory model without the RPC hops.

(The registry-driven actor pipeline — PID→stage over real RPC — lives in
ptype_tpu/train/actor_pipeline.py; this module is the throughput path.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ptype_tpu.errors import ClusterError


def split_stages(blocks: dict, n_stages: int) -> dict:
    """Reshape stacked block params (L, ...) → (S, L/S, ...)."""

    def resh(x):
        L = x.shape[0]
        if L % n_stages:
            raise ClusterError(
                f"pipeline: {L} layers not divisible into {n_stages} stages"
            )
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree.map(resh, blocks)


def merge_stages(blocks: dict) -> dict:
    """Inverse of :func:`split_stages`: (S, L/S, ...) → (L, ...)."""
    return jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]), blocks
    )


def _spmd_pipeline(stage_fn, stage_params, x_mb, *, axis: str,
                   n_stages: int, n_microbatches: int):
    """Run the pipeline on one device (inside shard_map over ``axis``).

    ``stage_params``: (1, L/S, ...) — this stage's layers (leading stage
    shard dim of size 1). ``x_mb``: (M, mb, ...) microbatched activations
    (replicated over the stage axis). Returns (M, mb, ...) outputs of the
    LAST stage (replicated via collective broadcast at the end).
    """
    stage = lax.axis_index(axis)
    S, M = n_stages, n_microbatches
    params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), stage_params)
    mb_shape = x_mb.shape[1:]

    state = jnp.zeros(mb_shape, x_mb.dtype)  # activation in flight
    outputs = jnp.zeros_like(x_mb)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        state, outputs = carry
        # Stage 0 ingests microbatch t (while t < M); other stages keep
        # the activation that just arrived from their predecessor.
        inject = x_mb[jnp.minimum(t, M - 1) % M]
        state = jnp.where(stage == 0, jnp.where(t < M, inject, state),
                          state)
        state = stage_fn(params, state)
        # The LAST stage has just finished microbatch t-(S-1).
        out_t = t - (S - 1)
        is_out = (stage == S - 1) & (out_t >= 0)
        outputs = jnp.where(
            is_out,
            jax.lax.dynamic_update_index_in_dim(
                outputs, state.astype(outputs.dtype),
                jnp.maximum(out_t, 0) % M, 0),
            outputs,
        )
        state = lax.ppermute(state, axis, perm)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(
        tick, (state, outputs), jnp.arange(M + S - 1)
    )
    # Outputs live on the last stage only; broadcast around the ring so
    # every stage returns the same (replicated out_spec).
    outputs = lax.psum(
        jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)), axis
    )
    return outputs


def pipeline_apply(stage_fn, stage_params, x, mesh: Mesh,
                   n_microbatches: int, axis: str = "stage"):
    """Apply a stage-sharded layer stack to ``x`` through the pipeline.

    ``stage_fn(params_one_stage, x_mb) -> y_mb`` runs this stage's layer
    chunk on one microbatch. ``stage_params`` leaves carry a leading
    ``n_stages`` dim (from :func:`split_stages`), sharded over ``axis``.
    ``x``: (B, ...) with B divisible by ``n_microbatches``.
    """
    S = int(mesh.shape[axis])
    B = x.shape[0]
    if B % n_microbatches:
        raise ClusterError(
            f"pipeline: batch {B} not divisible into {n_microbatches} "
            "microbatches"
        )
    x_mb = x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])

    param_specs = jax.tree.map(
        lambda p: P(axis, *(None,) * (p.ndim - 1)), stage_params
    )
    fn = shard_map(
        partial(_spmd_pipeline, stage_fn, axis=axis, n_stages=S,
                n_microbatches=n_microbatches),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    y_mb = fn(stage_params, x_mb)
    return y_mb.reshape(B, *y_mb.shape[2:])


def _stage_attn(cfg):
    """Attention for blocks INSIDE the stage ring: the resolved impl
    (flash kernel on TPU under "auto" — a pipelined model shouldn't
    pay dense B·H·S² scores just because its layers are staged; the
    kernel's custom VJP differentiates under shard_map). Seq-parallel
    impls can't nest inside the stage ring — refuse rather than
    silently running dense."""
    from ptype_tpu.models import transformer as tfm

    if cfg.attn_impl in ("ring", "ulysses"):
        raise ClusterError(
            f"pipeline stages cannot nest seq-parallel attention "
            f"(attn_impl={cfg.attn_impl!r}); use auto/flash/xla")
    return tfm.resolve_attn_fn(cfg)


def schedule_info(n_stages: int, n_microbatches: int,
                  schedule: str = "gpipe") -> dict:
    """Tick/stash/bubble accounting for a schedule — the numbers the
    1F1B-vs-GPipe tradeoff is made of.

    One *tick* is one scan iteration of the compiled SPMD program.
    GPipe runs two uniform phases (a forward scan then, via autodiff,
    a reversed backward scan): every stage stashes ALL M microbatch
    activations for the backward. 1F1B runs ONE combined scan whose
    steady-state ticks each do one real forward AND one real backward
    microbatch — the live stash is bounded by the schedule depth
    (2S-1), NOT by M. That bound is the whole point: at a fixed
    activation budget, 1F1B can raise M until the bubble fraction
    (idle ticks / total ticks) is driven down, where GPipe's stash
    grows linearly with M and caps it first.
    """
    S, M = n_stages, n_microbatches
    if schedule == "gpipe":
        return {
            "ticks": 2 * (M + S - 1),
            "stash_microbatches": M,
            "bubble_fraction": (S - 1) / (M + S - 1),
        }
    if schedule == "1f1b":
        ticks = M + 2 * S - 1
        return {
            "ticks": ticks,
            "stash_microbatches": 2 * S - 1,
            "bubble_fraction": (2 * S - 2) / ticks,
        }
    raise ClusterError(f"unknown pipeline schedule {schedule!r}")


def _spmd_pipeline_1f1b(stage_fn, tail_fn, stage_params, wnorm, head,
                        x_mb, tgt_mb, mask_mb, *, axis: str,
                        n_stages: int, n_microbatches: int):
    """Hand-scheduled 1F1B inside shard_map: one scan, each tick runs
    one forward microbatch AND one (rematerialized-VJP) backward
    microbatch where the schedule has work for this stage.

    Schedule (0-based tick t, stage s):
    - forward of microbatch m at  t = m + s,
    - stage S-1 computes the tail (final-norm + LM head + loss) VJP in
      the same tick its forward finishes, carrying the cotangent one
      tick to its own backward,
    - backward of microbatch m at t = m + S + (S-1-s)  — so a stage's
      gap between fwd(m) and bwd(m) is 2S-1-2s ticks, which bounds the
      live input stash at 2S-1 (vs GPipe's M).

    Backward is recomputed from the stashed INPUT (``jax.vjp`` on the
    stage at backward time) — the per-stage rematerialization
    jax.checkpoint would do anyway, which is what keeps the stash to
    inputs instead of full VJP residuals.

    Returns per-stage block grads (leading singleton stage dim), the
    psum'd tail grads (norm/head), the input cotangents (stage 0), and
    unnormalized (nll_sum, denom) accumulators from stage S-1.
    """
    stage = lax.axis_index(axis)
    S, M = n_stages, n_microbatches
    params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), stage_params)
    mb_shape = x_mb.shape[1:]
    K = 2 * S  # stash ring slots (schedule bound is 2S-1)
    is_last = stage == S - 1
    is_first = stage == 0

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    zeros_mb = jnp.zeros(mb_shape, x_mb.dtype)
    carry0 = {
        "fwd_in": zeros_mb,
        "bwd_ct": zeros_mb,
        "self_ct": zeros_mb,
        "stash": jnp.zeros((K, *mb_shape), x_mb.dtype),
        "gblocks": jax.tree.map(jnp.zeros_like, params),
        "gnorm": jnp.zeros_like(wnorm),
        "ghead": jnp.zeros_like(head),
        "xct": jnp.zeros_like(x_mb),
        "nll": jnp.float32(0.0),
        "den": jnp.float32(0.0),
    }

    def tick(c, t):
        # ---------------- forward op: microbatch m_f = t - stage
        m_f = t - stage
        fwd_valid = (m_f >= 0) & (m_f < M)
        m_f_c = jnp.clip(m_f, 0, M - 1)
        x_in = jnp.where(is_first, x_mb[m_f_c], c["fwd_in"])
        y = stage_fn(params, x_in)
        stash = jnp.where(
            fwd_valid,
            lax.dynamic_update_index_in_dim(c["stash"], x_in, t % K, 0),
            c["stash"])
        # Tail (norm+head+loss) VJP on the stage that just produced
        # final activations; its cotangent seeds this stage's OWN
        # backward next tick. Guarded by lax.cond — XLA's conditional
        # IS per-device control flow under manual shard_map, so only
        # stage S-1 pays the vocab matmul; a masked-but-computed tail
        # would burn (S-1)/S of the head FLOPs on results it discards
        # (advisor round-5 finding).
        tail_valid = is_last & fwd_valid

        def run_tail(y_in):
            (nll_m, den_m), tail_vjp = jax.vjp(
                lambda wn, hd, yy: tail_fn(wn, hd, yy, tgt_mb[m_f_c],
                                           mask_mb[m_f_c]),
                wnorm, head, y_in)
            dwn, dhd, dy = tail_vjp((jnp.float32(1.0), jnp.float32(0.0)))
            return (nll_m.astype(jnp.float32), den_m.astype(jnp.float32),
                    dwn, dhd, dy.astype(x_mb.dtype))

        def skip_tail(y_in):
            del y_in
            return (jnp.float32(0.0), jnp.float32(0.0),
                    jnp.zeros_like(wnorm), jnp.zeros_like(head),
                    zeros_mb)

        nll_m, den_m, dwn, dhd, self_ct = lax.cond(
            tail_valid, run_tail, skip_tail, y)
        nll = c["nll"] + nll_m
        den = c["den"] + den_m
        gnorm = c["gnorm"] + dwn
        ghead = c["ghead"] + dhd

        # --------------- backward op: microbatch m_b = t-(2S-1)+stage
        m_b = t - (2 * S - 1) + stage
        bwd_valid = (m_b >= 0) & (m_b < M)
        x_saved = c["stash"][(m_b + stage) % K]
        ct_in = jnp.where(is_last, c["self_ct"], c["bwd_ct"])
        _, stage_vjp = jax.vjp(stage_fn, params, x_saved)
        dparams, dx = stage_vjp(ct_in)
        gblocks = jax.tree.map(
            lambda acc, g: acc + jnp.where(bwd_valid, g, 0.0),
            c["gblocks"], dparams)
        m_b_c = jnp.clip(m_b, 0, M - 1)
        xct = jnp.where(
            is_first & bwd_valid,
            lax.dynamic_update_index_in_dim(c["xct"], dx, m_b_c, 0),
            c["xct"])

        # --------------- ring communication for the NEXT tick
        nxt = {
            "fwd_in": lax.ppermute(y, axis, fwd_perm),
            "bwd_ct": lax.ppermute(dx, axis, bwd_perm),
            "self_ct": self_ct,
            "stash": stash,
            "gblocks": gblocks,
            "gnorm": gnorm,
            "ghead": ghead,
            "xct": xct,
            "nll": nll,
            "den": den,
        }
        return nxt, None

    ticks = M + 2 * S - 1
    c, _ = lax.scan(tick, carry0, jnp.arange(ticks))

    # Stage-local accumulators → the global values each out_spec wants.
    last = is_last.astype(jnp.float32)
    first = is_first
    gblocks = jax.tree.map(lambda g: g[None], c["gblocks"])
    return (
        gblocks,
        lax.psum(c["gnorm"] * last, axis),
        lax.psum(c["ghead"] * last, axis),
        lax.psum(jnp.where(first, c["xct"],
                           jnp.zeros_like(c["xct"])), axis),
        lax.psum(c["nll"] * last, axis),
        lax.psum(c["den"] * last, axis),
    )


def pipeline_loss_and_grads_1f1b(params: dict, batch: dict, cfg,
                                 mesh: Mesh, n_microbatches: int,
                                 axis: str = "stage"):
    """(loss, grads) for the transformer with the block stack pipelined
    under the 1F1B schedule — the hand-written counterpart of
    ``jax.value_and_grad`` over :func:`transformer_pipeline_forward`
    (which autodiff turns into GPipe: full forward scan, then reversed
    backward scan, stashing all M microbatch activations per stage).
    Embedding lookup and its scatter-add gradient stay outside the
    ring, fed by the stage-0 input cotangents."""
    from ptype_tpu.models import transformer as tfm

    if cfg.n_experts:
        raise ClusterError(
            "pipeline parallelism does not support MoE configs yet "
            "(router aux loss would be dropped); use dp/fsdp/tp/ep")
    S = int(mesh.shape[axis])
    M = n_microbatches
    B, T = batch["tokens"].shape
    if B % M:
        raise ClusterError(
            f"pipeline: batch {B} not divisible into {M} microbatches")
    mb = B // M
    tokens_mb = batch["tokens"].reshape(M, mb, T)
    tgt_mb = batch["targets"].reshape(M, mb, T)
    # An all-ones mask is numerically identical to no mask (denom =
    # token count) and keeps the shard_map arg tree static.
    mask_mb = (jnp.ones((M, mb, T), jnp.float32)
               if batch.get("loss_mask") is None
               else batch["loss_mask"].reshape(M, mb, T))
    x_mb = params["embed"][tokens_mb].astype(cfg.dtype)
    sin, cos = tfm.rope_tables(cfg, T)
    stage_blocks = split_stages(params["blocks"], S)
    head = tfm._head_weight(params, cfg)
    wnorm = params["final_norm"]

    attn = _stage_attn(cfg)

    def stage_fn(blocks, x):
        def body(x, layer):
            x, _aux = tfm._block(x, layer, sin, cos, cfg, attn)
            return x, None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = lax.scan(body, x, blocks)
        return x

    def tail_fn(wn, hd, y, tgt, mask):
        x = tfm.rms_norm(y, wn)
        logits = tfm.head_logits(x, hd, cfg)
        return tfm.nll_terms_from_logits(
            logits, {"targets": tgt, "loss_mask": mask})

    param_specs = jax.tree.map(
        lambda p: P(axis, *(None,) * (p.ndim - 1)), stage_blocks)
    fn = shard_map(
        partial(_spmd_pipeline_1f1b, stage_fn, tail_fn, axis=axis,
                n_stages=S, n_microbatches=M),
        mesh=mesh,
        in_specs=(param_specs, P(), P(), P(), P(), P()),
        out_specs=(param_specs, P(), P(), P(), P(), P()),
        check_vma=False,
    )
    gblocks, gnorm, ghead, xct, nll, den = fn(
        stage_blocks, wnorm, head, x_mb, tgt_mb, mask_mb)

    # Unnormalized sums accumulate in-ring; normalize ONCE here so the
    # loss/grads are invariant to M (trainer.py's accumulation rule).
    loss = nll / den
    inv = (1.0 / den).astype(jnp.float32)

    def scale(g):
        return (g * inv).astype(g.dtype)

    # Embedding grad: scatter-add of the stage-0 input cotangents,
    # plus the tied head's transpose contribution.
    xct = xct.reshape(B, T, -1).astype(jnp.float32) * inv
    dembed = (jnp.zeros_like(params["embed"])
              .at[batch["tokens"]].add(xct))
    grads = {
        "blocks": jax.tree.map(scale, merge_stages(gblocks)),
        "final_norm": scale(gnorm),
        "embed": dembed,
    }
    if cfg.tie_embeddings:
        grads["embed"] = grads["embed"] + scale(ghead).T
    else:
        grads["lm_head"] = scale(ghead)
    return loss, grads


# ------------------------------------------------- transformer integration


def transformer_pipeline_forward(params: dict, tokens: jax.Array, cfg,
                                 mesh: Mesh, n_microbatches: int,
                                 axis: str = "stage") -> jax.Array:
    """models/transformer.forward with the block stack pipelined.

    Embedding and the LM head stay outside the pipeline (they are one
    matmul each); the L blocks split into ``stage``-many chunks. Same
    logits as the dense forward, modulo bf16 accumulation order.
    """
    from ptype_tpu.models import transformer as tfm

    if cfg.n_experts:
        # The stage ring carries activations only; threading the MoE
        # router aux loss through it is not implemented — refusing beats
        # silently optimizing a different objective than the dense path.
        raise ClusterError(
            "pipeline parallelism does not support MoE configs yet "
            "(router aux loss would be dropped); use dp/fsdp/tp/ep"
        )
    S = int(mesh.shape[axis])
    B, T = tokens.shape
    dt = cfg.dtype
    x = params["embed"][tokens].astype(dt)
    sin, cos = tfm.rope_tables(cfg, T)
    stage_blocks = split_stages(params["blocks"], S)
    attn = _stage_attn(cfg)

    def stage_fn(blocks, x_mb):
        def body(x, layer):
            x, _aux = tfm._block(x, layer, sin, cos, cfg, attn)
            return x, None

        if cfg.remat:
            body = jax.checkpoint(body)
        x_mb, _ = lax.scan(body, x_mb, blocks)
        return x_mb

    x = pipeline_apply(stage_fn, stage_blocks, x, mesh, n_microbatches,
                       axis)
    x = tfm.rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      head.astype(jnp.float32))


def pipeline_state_shardings(params_like, mesh: Mesh, optimizer,
                             axis: str = "stage"):
    """NamedSharding pytree for a pipelined TrainState: block leaves
    shard their leading layer dim over ``axis`` (L = S·L/S, so the
    per-stage split is a local reshape), everything else replicated;
    optax moments mirror the params."""
    from ptype_tpu.train.trainer import TrainState

    def param_sh(path, leaf):
        top = getattr(path[0], "key", None) if path else None
        if top == "blocks":
            return NamedSharding(mesh, P(axis, *(None,) * (leaf.ndim - 1)))
        return NamedSharding(mesh, P())

    params_shape = jax.eval_shape(lambda: params_like) \
        if not hasattr(jax.tree.leaves(params_like)[0], "shape") \
        else params_like
    p_sh = jax.tree_util.tree_map_with_path(param_sh, params_shape)
    opt_shape = jax.eval_shape(optimizer.init, params_shape)

    from ptype_tpu.train.trainer import opt_state_shardings

    repl = NamedSharding(mesh, P())
    o_sh = opt_state_shardings(opt_shape, params_shape, p_sh, repl)
    return TrainState(p_sh, o_sh, repl)


def make_pipeline_train_step(cfg, mesh: Mesh, n_microbatches: int,
                             optimizer=None, axis: str = "stage",
                             state_shardings=None,
                             schedule: str = "gpipe"):
    """(state, batch) → (state, metrics) with the block stack pipelined.

    State layout matches train/trainer.py's TrainState, so checkpoints
    interchange between pipelined and dense training. Pass
    ``state_shardings`` (from :func:`pipeline_state_shardings`) to pin
    each stage's layers — and their Adam moments — to that stage's
    devices; without it the state is replicated (fine for tests, wrong
    for models sized to per-stage memory).

    ``schedule``: "gpipe" (autodiff: forward scan + reversed backward
    scan, stash = M microbatch activations/stage) or "1f1b"
    (hand-scheduled combined scan, stash bounded at 2S-1 — see
    :func:`schedule_info` for the accounting that makes 1F1B the
    memory-bound choice that lets M, and therefore the bubble, scale).
    """
    import optax

    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.train.trainer import TrainState, default_optimizer

    optimizer = optimizer or default_optimizer()
    if schedule not in ("gpipe", "1f1b"):
        raise ClusterError(f"unknown pipeline schedule {schedule!r}")

    def loss_fn(p, batch):
        logits = transformer_pipeline_forward(
            p, batch["tokens"], cfg, mesh, n_microbatches, axis
        )
        return tfm.nll_from_logits(logits, batch)

    def step(state: TrainState, batch: dict):
        if schedule == "1f1b":
            loss, grads = pipeline_loss_and_grads_1f1b(
                state.params, batch, cfg, mesh, n_microbatches, axis)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params,
                                                      batch)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new = TrainState(new_params, opt_state, state.step + 1)
        return new, {"loss": loss, "step": new.step}

    kw = {}
    if state_shardings is not None:
        kw = {"in_shardings": (state_shardings,
                               NamedSharding(mesh, P())),
              "out_shardings": (state_shardings,
                                {"loss": NamedSharding(mesh, P()),
                                 "step": NamedSharding(mesh, P())})}
    return jax.jit(step, donate_argnums=(0,), **kw)

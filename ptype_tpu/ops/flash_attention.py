"""Flash attention — Pallas TPU kernel, forward + backward.

The MFU target (≥30% at 125M on a v5e-8, BASELINE.json) dies on a
materialized S×S score matrix: at S=1024 the dense path writes
B·H·S² f32 to HBM each direction. This kernel keeps scores in VMEM
block-by-block (online softmax forward; recomputed-block backward), so
attention is HBM-linear in S — the standard flash decomposition, written
for the MXU:

- block_q × block_k score tiles (one MXU pass each), bf16 matmuls with
  f32 accumulators (``preferred_element_type``);
- **K/V streamed through the grid** — the kv-block index is the
  innermost grid dim and online-softmax state lives in VMEM scratch
  that persists across it, so VMEM use is O(block), independent of S
  (the llama preset's S=8192 fits);
- **native GQA**: K/V keep their ``n_kv_heads`` heads; the kernel index
  maps route query head h to kv head h // group — no ``jnp.repeat``
  materializing the H-head tensors GQA exists to avoid;
- causal masking at block granularity; blocks strictly above the
  diagonal are skipped (``pl.when`` — fetched but never computed);
- forward emits the log-sum-exp rows as a residual; backward is two
  kernels (dq; dk/dv accumulated over query heads of the group) using
  the delta = rowsum(dO∘O) trick, wired as a ``jax.custom_vjp``;
- ``interpret=True`` on CPU so the numerics tier of the test suite
  (SURVEY.md §4) validates the kernel without a TPU.

Layout: public API takes (B, S, H, Dh) like models/transformer._attention
and transposes to (B, H, S, Dh) internally (head-major keeps each
(b, h) program's K/V stream contiguous in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ptype_tpu.parallel.topology import DATA_AXIS

NEG_INF = -1e30

#: ``pallas_call`` names of the three kernels — how a compiled module
#: or a device trace identifies them (chip_smoke.py reads them back).
KERNEL_NAMES = ("ptype_flash_fwd", "ptype_flash_dq", "ptype_flash_dkv")


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                scale: float, causal: bool, want_lse: bool):
    """Grid (B, H, num_q, num_k): one (q block, k block) tile per step.

    ``rest`` is ``(lse_ref if want_lse, m_scr, l_scr, acc_scr)`` — the
    LSE output exists only when the caller wants the residual (the
    primal path declares just ``o``, skipping ~B·H·S·LANES f32 of
    discarded HBM writes). Scratch (m, l, acc) carries the online
    softmax across the innermost kv dim; m/l are lane-replicated
    (block_q, block_k) f32 so every op stays 2-D and tile-aligned.
    """
    if want_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    qi, kb = pl.program_id(2), pl.program_id(3)
    num_k = pl.num_programs(3)
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: K blocks strictly above this Q block's diagonal contribute
    # nothing — skip the MXU work entirely.
    live = (kb * block_k < (qi + 1) * block_q) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...][:, :1]  # row stats live in lane 0
        l_prev = l_scr[...][:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)  # (block_q, 1)
        p = jnp.exp(s - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == num_k - 1)
    def _finalize():
        m = m_scr[...][:, :1]  # (block_q, 1) — stay 2-D for Mosaic
        l = l_scr[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # LSE rows are lane-replicated to the 128-lane tile (the row
            # layout (B, H, S) puts a squeezed size-1 head dim second-to-
            # last in the block, violating Mosaic's (8, 128) tiling rule
            # — the round-2 TPU lowering failure).
            lse_ref[...] = jnp.broadcast_to(m + jnp.log(l_safe),
                                            lse_ref.shape)


#: Lane width of the f32 Mosaic tile. Row residuals (LSE, delta) are
#: stored lane-replicated at this width so their block's last two dims
#: are (block_q, 128)-aligned.
LANES = 128


def _spec_shapes(block_q: int, block_k: int, Dh: int) -> dict:
    """The three BlockSpec block shapes every kernel in this module
    declares — the ONE source both the pallas_calls and the lowering
    checker (:func:`lowering_block_shapes`) consume, so a layout
    change can't pass the CPU-tier check while failing on Mosaic."""
    return {"q": (None, None, block_q, Dh),
            "kv": (None, None, block_k, Dh),
            "row": (None, None, block_q, LANES)}


def _fwd(q, k, v, *, block_q: int, block_k: int, causal: bool,
         interpret: bool, want_lse: bool = True):
    """q: (B, H, S, Dh); k, v: (B, K, S, Dh) → (o like q, lse
    (B, H, S, LANES) lane-replicated | None when ``not want_lse``)."""
    B, H, S, Dh = q.shape
    K = k.shape[1]
    group = H // K
    scale = 1.0 / (Dh ** 0.5)
    grid = (B, H, S // block_q, S // block_k)

    qmap = lambda b, h, qi, kb: (b, h, qi, 0)           # noqa: E731
    kvmap = lambda b, h, qi, kb: (b, h // group, kb, 0)  # noqa: E731
    shp = _spec_shapes(block_q, block_k, Dh)

    out_specs = [pl.BlockSpec(shp["q"], qmap)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if want_lse:
        out_specs.append(pl.BlockSpec(shp["row"], qmap))
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32))

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          want_lse=want_lse),
        grid=grid,
        in_specs=[
            pl.BlockSpec(shp["q"], qmap),
            pl.BlockSpec(shp["kv"], kvmap),
            pl.BlockSpec(shp["kv"], kvmap),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # m (lane-repl)
            pltpu.VMEM((block_q, LANES), jnp.float32),  # l (lane-repl)
            pltpu.VMEM((block_q, Dh), jnp.float32),     # acc
        ],
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(q, k, v)
    return (out[0], out[1]) if want_lse else (out[0], None)


# ----------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool):
    """dq for one q block, streaming k/v blocks through the grid."""
    qi, kb = pl.program_id(2), pl.program_id(3)
    num_k = pl.num_programs(3)
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (kb * block_k < (qi + 1) * block_q) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, :1]      # lane-replicated → (block_q, 1)
        delta = delta_ref[...][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # normalized probs via lse
        dp = jax.lax.dot_general(
            do, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_k - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool):
    """dk/dv for one kv block of one KV HEAD: grid (B, K, num_k, G,
    num_q) streams every query block of every query head in the GQA
    group through scratch accumulators — the group-sum GQA's backward
    needs, without materializing repeated K/V."""
    ki = pl.program_id(2)
    g, qb = pl.program_id(3), pl.program_id(4)
    num_g, num_q = pl.num_programs(3), pl.num_programs(4)
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]

    @pl.when((g == 0) & (qb == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = ((qb + 1) * block_q > ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, :1]      # lane-replicated → (block_q, 1)
        delta = delta_ref[...][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g == num_g - 1) & (qb == num_q - 1))
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


# ------------------------------------------------------------- custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, block_q, block_k, causal, interpret):
    o, _ = _fwd(q, k, v, block_q=block_q, block_k=block_k, causal=causal,
                interpret=interpret, want_lse=False)
    return o


def _flash_fwd(q, k, v, block_q, block_k, causal, interpret):
    o, lse = _fwd(q, k, v, block_q=block_q, block_k=block_k, causal=causal,
                  interpret=interpret)
    # Save one lane of the replicated LSE: the (B, H, S, LANES) layout is
    # a kernel-I/O constraint, not information — holding all 128 lanes
    # from forward to backward would inflate saved-activation HBM 128×.
    return o, (q, k, v, o, lse[..., :1])


def _flash_bwd(block_q, block_k, causal, interpret, res, do):
    q, k, v, o, lse1 = res
    B, H, S, Dh = q.shape
    K = k.shape[1]
    group = H // K
    scale = 1.0 / (Dh ** 0.5)
    # Row residuals ride the same lane-replicated (B, H, S, LANES)
    # layout the forward emits for LSE (Mosaic (8, 128) tiling rule);
    # both are broadcast transiently here, inside the backward.
    lse = jnp.broadcast_to(lse1, (B, H, S, LANES))
    delta = jnp.broadcast_to(
        jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                axis=-1, keepdims=True),
        (B, H, S, LANES))

    qmap = lambda b, h, qi, kb: (b, h, qi, 0)            # noqa: E731
    kvmap = lambda b, h, qi, kb: (b, h // group, kb, 0)  # noqa: E731
    shp = _spec_shapes(block_q, block_k, Dh)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal),
        grid=(B, H, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec(shp["q"], qmap),
            pl.BlockSpec(shp["kv"], kvmap),
            pl.BlockSpec(shp["kv"], kvmap),
            pl.BlockSpec(shp["q"], qmap),
            pl.BlockSpec(shp["row"], qmap),
            pl.BlockSpec(shp["row"], qmap),
        ],
        out_specs=pl.BlockSpec(shp["q"], qmap),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, Dh), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(q, k, v, do, lse, delta)

    # dk/dv: grid walks (kv head, k block) then the group's query heads
    # and q blocks innermost, accumulating the GQA group-sum in scratch.
    bmap_q = lambda b, kk, ki, g, qb: (b, kk * group + g, qb, 0)  # noqa: E731,E501
    bmap_kv = lambda b, kk, ki, g, qb: (b, kk, ki, 0)             # noqa: E731,E501

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal),
        grid=(B, K, S // block_k, group, S // block_q),
        in_specs=[
            pl.BlockSpec(shp["q"], bmap_q),
            pl.BlockSpec(shp["kv"], bmap_kv),
            pl.BlockSpec(shp["kv"], bmap_kv),
            pl.BlockSpec(shp["q"], bmap_q),
            pl.BlockSpec(shp["row"], bmap_q),
            pl.BlockSpec(shp["row"], bmap_q),
        ],
        out_specs=[
            pl.BlockSpec(shp["kv"], bmap_kv),
            pl.BlockSpec(shp["kv"], bmap_kv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, Dh), jnp.float32),
            pltpu.VMEM((block_k, Dh), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------- public API


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool | None = None) -> jax.Array:
    """Flash attention over (B, S, H, Dh) tensors (transformer layout).

    GQA-native: K/V may carry fewer heads (``H % K == 0``); query head h
    reads kv head ``h // (H/K)`` inside the kernel — no repeat. Sequence
    length must divide by the (clamped) block sizes; pad upstream —
    presets use power-of-two seq. ``interpret`` defaults to True on CPU
    backends so tests validate the kernel without a TPU.

    Default blocks are 1024×1024. VMEM is O(block), independent of S,
    but it is not only the operand tiles: besides the double-buffered
    (1024, Dh) q/k/v/o blocks (0.25 MiB each at Dh=128 bf16) every
    kernel works on (1024, 1024) f32 score tiles — ``s`` and ``p`` in
    the forward, ``s``/``p``/``dp``/``ds`` in the dk/dv kernel. At the
    125M train shape (B=16, S=1024, H=K=6, Dh=128) the compiled module
    reports 5.0 / 7.3 / 9.4 MiB of scoped VMEM for the forward / dq /
    dkv kernel, under Mosaic's 16 MiB default, so none of the three
    ``pallas_call``s passes compiler params. All three compile as
    written and match the float32 reference on a "TPU v5 lite" at that
    shape, at S=8192 GQA (H=8, K=2) and at Dh=64 (chip run, PR 21;
    chip_smoke.py re-proves it). Block sizes have not been tuned on
    the current installation.
    """
    if interpret is None:
        interpret = _on_cpu()
    B, S, H, Dh = q.shape
    K = k.shape[2]
    if H % K:
        raise ValueError(f"flash_attention: n_heads {H} must divide by "
                         f"n_kv_heads {K}")
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(
            f"flash_attention: seq {S} must divide by blocks "
            f"({block_q}, {block_k})"
        )
    to_hmajor = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    o = _flash(to_hmajor(q), to_hmajor(k), to_hmajor(v),
               block_q, block_k, causal, interpret)
    return jnp.swapaxes(o, 1, 2)


def lowering_block_shapes(B: int, H: int, S: int, Dh: int,
                          K: int | None = None,
                          block_q: int = 1024, block_k: int = 1024
                          ) -> list[tuple[str, tuple, tuple]]:
    """Every (operand name, block shape, array shape) the three
    pallas_calls declare at these dimensions — the Mosaic tiling
    contract as data, checkable WITHOUT a TPU.

    The TPU lowering requires the last two dims of every block shape
    to divide by (8, 128) or equal the array's. BENCH_r02 recorded the
    violation this guards against: the LSE output was once declared
    (B, H, S) with a squeezed size-1 dim second-to-last in the block —
    the fix stores row residuals lane-replicated at (block_q, LANES).
    ``tests/test_flash_lowering.py`` asserts the rule over every entry
    here for the bench/train configs, so a spec regression fails tier-1
    on CPU instead of the next TPU session."""
    K = K or H
    block_q, block_k = min(block_q, S), min(block_k, S)
    q4 = (B, H, S, Dh)
    kv4 = (B, K, S, Dh)
    lse4 = (B, H, S, LANES)
    # The block shapes come from the SAME _spec_shapes the
    # pallas_calls consume (None = squeezed dim → size 1 here).
    shp = {k: tuple(1 if d is None else d for d in v)
           for k, v in _spec_shapes(block_q, block_k, Dh).items()}
    qb, kvb, lseb = shp["q"], shp["kv"], shp["row"]
    out = []
    # forward: q, k, v → o (+ lse when the residual is wanted)
    out += [("fwd/q", qb, q4), ("fwd/k", kvb, kv4), ("fwd/v", kvb, kv4),
            ("fwd/o", qb, q4), ("fwd/lse", lseb, lse4)]
    # backward dq: q, k, v, do, lse, delta → dq
    out += [("dq/q", qb, q4), ("dq/k", kvb, kv4), ("dq/v", kvb, kv4),
            ("dq/do", qb, q4), ("dq/lse", lseb, lse4),
            ("dq/delta", lseb, lse4), ("dq/dq", qb, q4)]
    # backward dk/dv: same operands → dk, dv
    out += [("dkv/q", qb, q4), ("dkv/k", kvb, kv4), ("dkv/v", kvb, kv4),
            ("dkv/do", qb, q4), ("dkv/lse", lseb, lse4),
            ("dkv/delta", lseb, lse4), ("dkv/dk", kvb, kv4),
            ("dkv/dv", kvb, kv4)]
    return out


def check_tpu_lowering(B: int, H: int, S: int, Dh: int,
                       K: int | None = None,
                       block_q: int = 1024, block_k: int = 1024
                       ) -> list[str]:
    """Violations of the Mosaic (8, 128) divisibility rule across
    :func:`lowering_block_shapes` — empty when the kernels lower."""
    bad = []
    for name, block, array in lowering_block_shapes(
            B, H, S, Dh, K, block_q, block_k):
        for dim, want in ((-2, 8), (-1, 128)):
            if block[dim] % want and block[dim] != array[dim]:
                bad.append(
                    f"{name}: block {block} dim {dim} = {block[dim]} "
                    f"not divisible by {want} nor equal to array "
                    f"{array}")
    return bad


def make_flash_attn_fn(mesh=None, block_q: int = 1024,
                       block_k: int = 1024):
    """attn_fn(q, k, v, cfg) for models/transformer.forward — the
    ``attn_impl="flash"`` lowering. A sequence length the kernel cannot
    tile raises (:func:`flash_attention` names the shape); there is no
    dense substitute.

    ``mesh``: the mesh of the jit the call sits in. A ``pallas_call``
    is opaque to the SPMD partitioner: on a TPU backend JAX refuses to
    lower a bare Mosaic kernel inside a multi-device jit ("Mosaic
    kernels cannot be automatically partitioned"), which interpret
    mode on the CPU mesh never showed. So on a multi-device mesh the
    kernel runs under ``jax.shard_map`` on the local shard: batch over
    the data-like axes (``data``/``fsdp``, the same ones
    ``transformer.batch_spec`` uses), heads over ``model`` when both
    head counts divide it. Callers already inside a fully manual
    region (Ulysses, the pipeline stage ring, store-DP's per-worker
    grads) pass no mesh."""

    def attn_fn(q, k, v, cfg):
        return flash_attention(q, k, v, causal=cfg.causal,
                               block_q=block_q, block_k=block_k)

    if mesh is None or mesh.devices.size == 1:
        return attn_fn

    batch_axes = tuple(a for a in (DATA_AXIS, "fsdp")
                       if a in mesh.axis_names) or None
    n_model = int(mesh.shape.get("model", 1))

    def sharded_attn_fn(q, k, v, cfg):
        heads = ("model" if n_model > 1 and q.shape[2] % n_model == 0
                 and k.shape[2] % n_model == 0 else None)
        spec = P(batch_axes, None, heads, None)
        return jax.shard_map(
            functools.partial(attn_fn, cfg=cfg), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return sharded_attn_fn

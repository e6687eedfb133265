"""Paged attention — Pallas TPU decode kernel over a block table.

The paged engine's prefill chunk and speculative programs attend
through an XLA gather (models/generate._paged_attention_gather): it
materializes the whole gathered (B, nb·bt, Kh, Dh) K/V per layer per
step in HBM before the einsum reads it (the default decode step reads
only the live rows' blocks, generate._live_block_attention). This
kernel skips the materialization: the block
table rides **scalar prefetch** (``pltpu.PrefetchScalarGridSpec``), so
each grid step's BlockSpec index map dials the bank block the table
names and Mosaic DMAs exactly that (block_tokens, Dh) tile into VMEM —
online softmax across the table dimension, flash-style, with
per-sequence position masking from the prefetched ``pos``.

Layout contract (the (8, 128) Mosaic tiling rule, same machinery as
ops/flash_attention):

- the bank layer is transposed to head-major ``(Kh, n_blocks,
  block_tokens, Dh)`` before the call so the K/V block tile is
  ``(block_tokens, Dh)`` — the NAIVE untransposed layout would put a
  squeezed size-1 head dim second-to-last in the block, the exact
  BENCH_r02 failure class the flash LSE output hit;
- queries are grouped ``(B, Kh, G, Dh)`` (GQA-native: the kernel never
  repeats K/V heads) and the G dim rides whole in the block;
- :func:`check_tpu_lowering` validates every declared BlockSpec
  against the rule AND the kernel's own alignment requirements
  (``block_tokens % 8``, ``Dh % 128``) WITHOUT a TPU — the serving
  engine only enables ``attn="kernel"`` on a real TPU backend when
  this returns clean; CPU tests run ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: ``pallas_call`` name — how a compiled module or a device trace
#: identifies the kernel (chip_smoke.py reads it back).
KERNEL_NAME = "ptype_paged_attention"
#: f32 Mosaic tile: (sublanes, lanes).
SUBLANES = 8
LANES = 128


def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, bt: int, scale: float):
    """Grid (B, Kh, nb): one (sequence, kv head, table slot) tile per
    step; the innermost table dim streams blocks through the online-
    softmax scratch. ``tables_ref``/``pos_ref`` are scalar-prefetched:
    the k/v index maps already consumed ``tables`` to pick the bank
    block, the body reads ``pos`` for masking."""
    b, i = pl.program_id(0), pl.program_id(2)
    nb = pl.num_programs(2)
    G = q_ref.shape[2]
    SG = m_scr.shape[0]  # sublane-padded query-group rows

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    limit = pos_ref[b] + 1        # attend positions < limit
    base = i * bt                 # table slot i holds these positions

    @pl.when(base < limit)
    def _compute():
        q = q_ref[0, 0]           # (G, Dh)
        if SG > G:                # pad rows to the f32 sublane tile;
            #                       pad rows accumulate garbage that
            #                       _finalize never reads back.
            q = jnp.concatenate(
                [q, jnp.zeros((SG - G, q.shape[1]), q.dtype)], axis=0)
        k = k_ref[0, 0]           # (bt, Dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (SG, bt)
        col = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < limit, s, NEG_INF)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _finalize():
        l = l_scr[...][:G, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...][:G] / l_safe).astype(o_ref.dtype)


def paged_attention(q, kc, vc, tables, pos,
                    interpret: bool | None = None) -> jax.Array:
    """Decode attention through block tables, one bank layer at a time.

    q: (B, 1, H, Dh) this step's queries; kc/vc: (n_blocks,
    block_tokens, Kh, Dh) bank layer; tables: (B, nb) int32 position-
    ordered block ids; pos: (B,) current token position (attend
    ``<= pos``). Returns (B, 1, H, Dh), matching the gather path.
    ``interpret`` defaults to True on CPU backends (the test tier)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, _, H, Dh = q.shape
    n_blocks, bt, Kh, _ = kc.shape
    nb = tables.shape[1]
    if H % Kh:
        raise ValueError(f"paged_attention: n_heads {H} must divide "
                         f"by kv_heads {Kh}")
    if not interpret:
        bad = check_tpu_lowering(B, H, Kh, Dh, n_blocks, bt, nb)
        if bad:
            raise ValueError(
                "paged_attention: config does not meet the TPU "
                "lowering contract: " + "; ".join(bad))
    G = H // Kh
    SG = max(G, SUBLANES)
    scale = 1.0 / (Dh ** 0.5)
    qh = q[:, 0].reshape(B, Kh, G, Dh)     # head h -> (h // G, h % G)
    kt = jnp.transpose(kc, (2, 0, 1, 3))   # (Kh, n_blocks, bt, Dh)
    vt = jnp.transpose(vc, (2, 0, 1, 3))

    qmap = lambda b, kh, i, tr, pr: (b, kh, 0, 0)             # noqa: E731,E501
    kvmap = lambda b, kh, i, tr, pr: (kh, tr[b, i], 0, 0)     # noqa: E731,E501
    shp = _spec_shapes(G, bt, Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Kh, nb),
        in_specs=[
            pl.BlockSpec(shp["q"], qmap),
            pl.BlockSpec(shp["kv"], kvmap),
            pl.BlockSpec(shp["kv"], kvmap),
        ],
        out_specs=pl.BlockSpec(shp["q"], qmap),
        scratch_shapes=[
            pltpu.VMEM((SG, LANES), jnp.float32),  # m (lane-repl)
            pltpu.VMEM((SG, LANES), jnp.float32),  # l (lane-repl)
            pltpu.VMEM((SG, Dh), jnp.float32),     # acc
        ],
    )
    o = pl.pallas_call(
        functools.partial(_paged_kernel, bt=bt, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, Dh), q.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), qh, kt, vt)
    return o.reshape(B, 1, H, Dh)


def _spec_shapes(G: int, bt: int, Dh: int) -> dict:
    """The BlockSpec block shapes the pallas_call declares — the ONE
    source the call and :func:`lowering_block_shapes` share (the
    flash-kernel pattern: a layout change can't pass the CPU-tier
    check while failing on Mosaic)."""
    return {"q": (1, 1, G, Dh), "kv": (1, 1, bt, Dh)}


def lowering_block_shapes(B: int, H: int, Kh: int, Dh: int,
                          n_blocks: int, bt: int, nb: int
                          ) -> list[tuple[str, tuple, tuple]]:
    """Every (operand, block shape, array shape) the kernel declares
    at these dimensions — the Mosaic tiling contract as data,
    checkable WITHOUT a TPU (see ops/flash_attention for the failure
    class this guards against)."""
    G = H // Kh
    shp = _spec_shapes(G, bt, Dh)
    q4 = (B, Kh, G, Dh)
    kv4 = (Kh, n_blocks, bt, Dh)
    return [("q", shp["q"], q4), ("k", shp["kv"], kv4),
            ("v", shp["kv"], kv4), ("o", shp["q"], q4)]


def check_tpu_lowering(B: int, H: int, Kh: int, Dh: int,
                       n_blocks: int, bt: int, nb: int) -> list[str]:
    """Violations of the Mosaic (8, 128) divisibility rule across
    :func:`lowering_block_shapes`, plus the kernel's own alignment
    requirements — empty when the kernel lowers. The serving engine
    consults this before enabling ``attn="kernel"`` on a TPU backend;
    tests assert it over the bench/serving configs on CPU."""
    bad = []
    for name, block, array in lowering_block_shapes(
            B, H, Kh, Dh, n_blocks, bt, nb):
        for dim, want in ((-2, SUBLANES), (-1, LANES)):
            if block[dim] % want and block[dim] != array[dim]:
                bad.append(
                    f"{name}: block {block} dim {dim} = {block[dim]} "
                    f"not divisible by {want} nor equal to array "
                    f"{array}")
    # The kernel's VMEM tiles must be NATIVELY aligned — block == array
    # on a misaligned dim satisfies the BlockSpec rule but leaves the
    # (bt, Dh) compute tile unfillable on the MXU/VPU grid.
    if bt % SUBLANES:
        bad.append(f"block_tokens {bt} not divisible by {SUBLANES} "
                   f"(sublane tile)")
    if Dh % LANES:
        bad.append(f"head_dim {Dh} not divisible by {LANES} "
                   f"(lane tile)")
    if H % Kh:
        bad.append(f"n_heads {H} not divisible by kv_heads {Kh}")
    return bad

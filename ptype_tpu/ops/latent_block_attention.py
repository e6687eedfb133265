"""Decode attention over a latent cache's live blocks — one Pallas TPU
kernel a layer.

A latent cache with no indexer (``TransformerConfig.latent`` without
indexer widths) holds one row a token that is key and value at once,
shared by every query head, and a decode step reads every row a live
lane holds. As an XLA loop (a gather of a tile of blocks, the scores,
the values' product) a row's bytes cross HBM four times: the gather
reads them and writes the tile, the scores read it, the values' product
reads its leading lanes again — a gather does not fuse into a dot's
operand (PERF.md §5, PR 35). Here a row crosses HBM ONCE:

- the bank stays in HBM (``memory_space=ANY``), never an operand XLA
  copies or re-lays; a block is one contiguous ``(block_tokens,
  cache_dim)`` row group, copied HBM → VMEM by its own DMA, a sub-tile
  of :data:`SUB_BLOCKS` blocks in flight at once and the next
  sub-tile's copies running under this one's products (two buffers):
  the copies alone run at the HBM rate;
- the list is ``generate.live_block_list(own_tiles=True)``'s: every
  tile's blocks belong to ONE lane, in position order, so a sub-tile
  is scored against its owner's ``H`` query rows alone (bf16 products
  summed in float32, then rounded to the query's type as the XLA walk's
  dot rounds them), masked at the owner's limit and folded into that
  lane's float32 running max / sum / accumulator in VMEM, ``p`` cast
  to the query's type before the product with the rows' leading
  ``v_dim`` lanes: the softmax of ``generate._table_attention``,
  summed a sub-tile at a time;
- only the blocks under the owner's limit are copied: the trash-block
  padding of a row's last tile is never read;
- a tile's ids reach the scalar core a tile at a time (HBM → SMEM, the
  next tile's under this one's work), and the trip count is data
  (``n_tiles``): one compiled program whatever the load. A lane with
  no listed block gets zeros.

``interpret=True`` on CPU, as ``ops.flash_attention``;
:func:`check_tpu_lowering` states the Mosaic tiling contract as data,
and tests/test_latent_block_lowering.py lowers and compiles the kernel
for TPU with no chip at the served shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: ``pallas_call`` name: how a compiled module or a device trace
#: identifies the kernel.
KERNEL_NAME = "ptype_latent_block_attn"
#: Blocks copied and scored at a time: 2,048 keys at 16 a block, 2.6 MB
#: of bf16 rows at 640 lanes a buffer. Chosen on the chip against 16,
#: 32 and 64 (PERF.md §6, PR 36).
SUB_BLOCKS = 128
NEG_INF = -1e30
#: Block copies started in one trip of the kernel's loop over a chunk.
COPY_GROUP = 32
#: Mosaic's tiling of a 1-D int32 array: a DMA'd slice of the ids is a
#: whole number of these.
IDS_ALIGN = 1024
#: The most VMEM a call may ask for: half of a v5e core's 128 MiB.
VMEM_CAP = 64 << 20


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _kernel(meta_ref, owner_ref, first_ref, limits_ref, q_ref, ids_hbm,
            bank_hbm, o_ref, ids_ref, buf_ref, m_ref, l_ref, acc_ref,
            ids_sem, buf_sem, *, scale: float, v_dim: int, tile: int,
            sub: int):
    """One call a layer. SMEM: ``meta`` (n_tiles, base), the tiles'
    ``owner`` and ``first`` position, the lanes' ``limits``. VMEM: the
    queries ``q`` (B, H, D) and the output (B, H, v_dim). HBM: the
    tiles' ids, flat, a tile padded to whole ``IDS_ALIGN``s; the flat
    bank (R, bt, D). Scratch: two tiles of ids (SMEM), two sub-tiles of
    rows, the running max / sum / accumulator of the lane being folded,
    the DMA semaphores."""
    n_tiles, base = meta_ref[0], meta_ref[1]
    wide = ids_ref.shape[0] // 2    # a tile's ids, padded (IDS_ALIGN)
    bt = bank_hbm.shape[1]
    per_tile = tile // sub          # sub-tiles ("chunks") a tile
    keys = sub * bt
    f32 = jnp.float32

    def ids_copy(t):
        return pltpu.make_async_copy(
            ids_hbm.at[pl.ds(pl.multiple_of(t * wide, wide), wide)],
            ids_ref.at[pl.ds(pl.multiple_of(t % 2 * wide, wide), wide)],
            ids_sem.at[t % 2])

    def held(c):
        """Of chunk ``c``: its tile, its place in it, the owner and the
        owner's limit, its first position and how many of its blocks
        lie under the limit."""
        t, j = c // per_tile, c % per_tile
        owner = owner_ref[t]
        limit = limits_ref[owner]
        at = first_ref[t] + j * keys
        n = jnp.clip((limit - at + bt - 1) // bt, 0, sub)
        return t, j, owner, limit, at, n

    def block_copy(t, j, i, slot):
        bid = ids_ref[t % 2 * wide + j * sub + i]
        return pltpu.make_async_copy(
            bank_hbm.at[base + bid],
            buf_ref.at[slot, pl.ds(pl.multiple_of(i * bt, bt), bt)],
            buf_sem.at[slot])

    def start(c, n):
        """Start chunk ``c``'s ``n`` block copies, :data:`COPY_GROUP`
        to a loop's trip: a trip a copy costs the scalar core more than
        the copy's own few bundles, and a whole chunk unrolled costs
        every process a second of lowering (PERF.md §6, PR 36)."""
        t, j, slot = c // per_tile, c % per_tile, c % 2

        def one(i, _):
            block_copy(t, j, i, slot).start()

        def group(g, _):
            lax.fori_loop(
                0, COPY_GROUP, lambda i, _: one(g * COPY_GROUP + i, None),
                None, unroll=True)

        whole = n // COPY_GROUP
        lax.fori_loop(0, whole, group, None)
        lax.fori_loop(whole * COPY_GROUP, n, one, None)

    def await_(c, n):
        """Await chunk ``c``'s ``n`` copies. A DMA semaphore counts
        bytes, so ONE wait the size of the whole sub-tile takes what a
        whole chunk's ``sub`` copies signalled; a row's last chunk
        awaits its copies one by one."""
        t, j, slot = c // per_tile, c % per_tile, c % 2

        @pl.when(n == sub)
        def _():
            pltpu.make_async_copy(buf_ref.at[slot], buf_ref.at[slot],
                                  buf_sem.at[slot]).wait()

        @pl.when(n < sub)
        def _():
            lax.fori_loop(
                0, n, lambda i, _: block_copy(t, j, i, slot).wait(), None)

    # Rows a buffer never received are multiplied by p = 0: they must
    # be finite, whatever the scratch held.
    buf_ref[...] = jnp.zeros_like(buf_ref)
    o_ref[...] = jnp.zeros_like(o_ref)
    chunks = n_tiles * per_tile

    @pl.when(n_tiles > 0)
    def _():
        ids_copy(0).start()

    def fold(c, prev):
        """Chunk ``c + 1``'s copies start, then chunk ``c``'s are
        awaited and folded (``c`` from -1: the first trip only
        starts)."""
        nxt = c + 1

        @pl.when(nxt < chunks)
        def _():
            t, j = nxt // per_tile, nxt % per_tile

            @pl.when(j == 0)
            def _():
                ids_copy(t).wait()

                @pl.when(t + 1 < n_tiles)
                def _():
                    ids_copy(t + 1).start()
            start(nxt, held(nxt)[-1])

        def folded():
            t, j, owner, limit, at, n = held(c)

            @pl.when((j == 0) & (owner != prev))
            def _():
                m_ref[...] = jnp.full_like(m_ref, NEG_INF)
                l_ref[...] = jnp.zeros_like(l_ref)
                acc_ref[...] = jnp.zeros_like(acc_ref)

            @pl.when(n > 0)
            def _():
                await_(c, n)
                q = q_ref[owner]                           # (H, D)
                rows = buf_ref[c % 2]                      # (keys, D)
                # Rounded to the query's type as XLA's dot of the
                # table walk rounds them (generate._table_attention).
                s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
                s = s.astype(q.dtype).astype(f32) * f32(scale)
                mask = (at + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                        < limit)
                s = jnp.where(mask, s, f32(NEG_INF))
                m = m_ref[...]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(mask, jnp.exp(s - m_new), f32(0))
                alpha = jnp.exp(m - m_new)
                l_ref[...] = l_ref[...] * alpha + jnp.sum(
                    p, axis=1, keepdims=True)
                acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                    p.astype(q.dtype), rows[:, :v_dim],
                    preferred_element_type=f32)
                m_ref[...] = m_new

            # The lane's last tile writes last.
            @pl.when(j == per_tile - 1)
            def _():
                l = l_ref[...]
                o_ref[owner] = (acc_ref[...] / jnp.where(l > 0, l, f32(1))
                                ).astype(o_ref.dtype)
            return owner

        return lax.cond(c >= 0, folded, lambda: prev)

    lax.fori_loop(-1, chunks, fold, jnp.int32(-1))


def _sub_blocks(tile: int, sub_blocks: int | None) -> int:
    """Blocks a sub-tile: at most :data:`SUB_BLOCKS`, dividing the
    list's tile."""
    sub = min(int(sub_blocks or SUB_BLOCKS), tile)
    while tile % sub:
        sub -= 1
    return sub


def vmem_bytes(B: int, H: int, D: int, v_dim: int, block_tokens: int,
               tile: int, sub_blocks: int | None = None,
               itemsize: int = 2) -> int:
    """The VMEM the kernel asks for (``vmem_limit_bytes``): queries and
    output resident, two sub-tiles of rows, the running sums, and room
    for a sub-tile's float32 scores and their temporaries."""
    keys = _sub_blocks(tile, sub_blocks) * block_tokens
    resident = B * H * (D + v_dim) * itemsize
    buffers = 2 * keys * D * itemsize
    sums = H * (v_dim + 2 * 128) * 4
    scores = 6 * H * keys * 4
    return 2 * resident + buffers + sums + scores + (4 << 20)


def check_tpu_lowering(B: int, H: int, D: int, v_dim: int,
                       block_tokens: int, tile: int, max_tiles: int,
                       n_rows: int, sub_blocks: int | None = None,
                       itemsize: int = 2) -> list[str]:
    """The Mosaic tiling contract of everything the kernel holds in
    VMEM or copies, checkable WITHOUT a TPU as
    ``flash_attention.check_tpu_lowering`` checks the flash kernels':
    the last two dims of a block or slice divide by (8 x 4 / itemsize,
    128) or equal the array's, a slice of the 1-D ids is whole
    :data:`IDS_ALIGN`s, and the VMEM asked for stays under
    :data:`VMEM_CAP`. Returns the violations: empty when the kernel
    lowers."""
    keys = _sub_blocks(tile, sub_blocks) * block_tokens
    wide = -(-tile // IDS_ALIGN) * IDS_ALIGN
    bad = []
    for name, block, array in (
            ("q", (B, H, D), (B, H, D)),
            ("o", (B, H, v_dim), (B, H, v_dim)),
            ("bank/block", (1, block_tokens, D), (n_rows, block_tokens, D)),
            ("rows/block", (block_tokens, D), (keys, D)),
            ("rows/values", (keys, v_dim), (keys, D)),
            ("ids/tile", (wide,), (max_tiles * wide,))):
        rule = (((-1, IDS_ALIGN),) if len(block) == 1
                else ((-2, 8 * 4 // itemsize), (-1, 128)))
        for dim, want in rule:
            if block[dim] % want and block[dim] != array[dim]:
                bad.append(
                    f"{name}: block {block} dim {dim} = {block[dim]} "
                    f"not divisible by {want} nor equal to array "
                    f"{array}")
    asked = vmem_bytes(B, H, D, v_dim, block_tokens, tile, sub_blocks,
                       itemsize)
    if asked > VMEM_CAP:
        bad.append(f"vmem: {asked} bytes asked for, over {VMEM_CAP}")
    return bad


def latent_block_attention(q, bank, base, blocks, limits, *,
                           scale: float, v_dim: int,
                           sub_blocks: int | None = None,
                           interpret: bool | None = None):
    """Attention of one query a lane over the latent rows the live
    lanes hold. q: (B, H, D) in the absorbed form
    (``sparse_mla.absorb_query``); ``bank``: the flat bank ``(L *
    n_blocks, block_tokens, D)``, ``base`` the layer's first row in it;
    ``blocks``: ``generate.live_block_list(own_tiles=True)``'s pair;
    ``limits`` (B,): lane ``b`` attends positions ``< limits[b]`` of its
    own blocks. ``scale`` multiplies the scores; the values are a row's
    leading ``v_dim`` lanes. Returns (B, H, v_dim) in q's type; a lane
    with no listed block gets zeros. ``interpret`` defaults to True on
    CPU."""
    lst, n_tiles = blocks
    B, H, D = q.shape
    tile = lst.shape[2]
    sub = _sub_blocks(tile, sub_blocks)
    if interpret is None:
        interpret = _on_cpu()
    i32 = jnp.int32
    meta = jnp.stack([jnp.asarray(n_tiles, i32), jnp.asarray(base, i32)])
    # A slice of a 1-D int32 array in HBM starts and ends on IDS_ALIGN.
    wide = -(-tile // IDS_ALIGN) * IDS_ALIGN
    ids = jnp.pad(lst[0], ((0, 0), (0, wide - tile))).reshape(-1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), v_dim=v_dim,
                          tile=tile, sub=sub),
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), q.dtype),
        in_specs=[smem, smem, smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.SMEM((2 * wide,), i32),
            pltpu.VMEM((2, sub * bank.shape[1], D), bank.dtype),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, v_dim), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(
                B, H, D, v_dim, bank.shape[1], tile, sub,
                jnp.dtype(bank.dtype).itemsize)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(meta, lst[1, :, 0], lst[2, :, 0], jnp.asarray(limits, i32), q,
      ids, bank)

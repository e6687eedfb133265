"""Paged KV-cache serving engine (ISSUE 9).

The serving-side analogue of the training data plane's bucketed
collectives: one device-resident bank of fixed-size KV blocks shared by
every live sequence, so resident cache memory tracks *actual* token
counts instead of ``n_slots × reach``:

- :mod:`~ptype_tpu.serve_engine.blocks` — the :class:`BlockPool`
  (ref-counted fixed-size blocks, per-sequence block tables, LRU
  eviction of released blocks, content-addressing by the same FNV-1a
  prefix hash chain the gateway's affinity routing keys on);
- :mod:`~ptype_tpu.serve_engine.engine` — the
  :class:`PagedGeneratorActor` continuous engine rebased onto the
  pool: chunked prefill (a long prompt can no longer stall co-batched
  decodes for its whole prefill), prefix reuse (an affinity-routed
  request skips prefill for every already-resident full block), and
  per-slot RNG sampling on the continuous path.

The decode attention reads what the live rows hold
(``models/generate.decode_step_banks`` over the engine's live list: a
GQA step reads ``generate.live_block_list``'s blocks in an XLA loop, a
latent step behind an indexer indexes, selects, gathers and attends
over ``generate.live_lane_list``'s lanes, a tile of them a trip, and a
latent step with no indexer reads its block list through one Pallas
kernel a layer, ``ops.latent_block_attention``; the prefill chunk and
the speculative programs gather through the block table).
``Info()``'s ``decode_attn`` says which: ``list``, ``lanes``,
``latent_kernel``.
"""

from ptype_tpu.serve_engine.blocks import (BlockPool, block_hashes,
                                           prefix_affinity_key)
from ptype_tpu.serve_engine.engine import (SERVE_CLASS_CODES,
                                           SERVE_CLASSES,
                                           PagedGeneratorActor,
                                           SpecConfig)
from ptype_tpu.serve_engine.migrate import WIRE_MODES, KVMigrator

__all__ = ["BlockPool", "block_hashes", "prefix_affinity_key",
           "PagedGeneratorActor", "SpecConfig", "SERVE_CLASSES",
           "SERVE_CLASS_CODES", "KVMigrator", "WIRE_MODES"]

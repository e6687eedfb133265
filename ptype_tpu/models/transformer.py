"""Decoder-only transformer, TPU-first.

The reference framework ships no model (SURVEY.md §2: "no ML code"); the
optimus example's worker compute was ``Prime.Check``'s simulated 250 ms
scan (example/optimus/prime.go:15-25). This module supplies the real
compute the north star demands — "optimus trains a 125M-param transformer"
(BASELINE.json) — designed for the MXU and XLA, not translated from
anything:

- **Scan over layers.** All blocks' parameters are stacked on a leading
  layer dim and the body is ``lax.scan``-ed: one compiled layer body
  regardless of depth (compile time O(1) in layers, XLA-friendly static
  control flow).
- **bf16 compute, f32 params.** Matmuls run in bfloat16 on the MXU;
  parameters and the softmax/logit paths stay f32 for stability.
- **RMSNorm + RoPE + SwiGLU + GQA** — one architecture covers the
  125M optimus preset and the Llama-3-8B FSDP baseline config. Beside
  it, for serving: latent K,V attention, behind a sparse-attention
  indexer or reading a row's whole context (models/sparse_mla.py),
  YaRN's scaled positions, a stack of several layer groups
  (:func:`layer_groups`: one scan a run of identical layers), layers
  of a sliding window among full ones with a cache a kind
  (``attn_windows``, :func:`cache_layers`), a stated head width and a
  per-head q/k norm, and a dropless router over a share of the experts,
  choosing inside groups where the model says so
  (:func:`_moe_dropless`).
- **Sharding by annotation.** :func:`param_specs` returns a PartitionSpec
  pytree (fsdp/model axes); the train layer jits with those shardings and
  GSPMD inserts the collectives (ICI-mapped; scaling-book recipe).
- **Remat.** ``cfg.remat`` wraps the block body in ``jax.checkpoint`` to
  trade FLOPs for HBM.
- **Named scopes.** Every part of a step is a ``jax.named_scope`` with a
  fixed name, the same in training, prefill and decode because the code
  is shared: ``embed``, ``qkv`` (norm, projections, RoPE), ``kv_write``
  and ``kv_gather`` (models/generate.py), ``attn``, ``attn_out``,
  ``mlp``, ``head`` (final norm + LM head), ``loss``, ``optimizer``
  (train/trainer.py), ``sample`` (serve_engine/engine.py); inside
  ``mlp``, a dropless expert layer's ``router``, ``experts`` and
  ``shared_expert``; latent attention's ``index`` and ``select``
  (models/sparse_mla.py); in a stack that states attention kinds the
  paged programs' attention is ``attn_window`` or ``attn_full`` by
  the layer's kind, not ``attn`` (models/generate.py). A scope is
  HLO metadata only: it names the operation in a device trace (the
  profiler's ``tf_op`` stat) and changes no compiled program.
  ``benchmark/readers/scope_time_pct.py`` buckets device time by them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ptype_tpu.parallel.topology import DATA_AXIS


@dataclass(frozen=True)
class LatentAttention:
    """Widths of latent K,V attention (DeepSeek-V2 MLA) and, where the
    model has one, of the sparse-attention indexer that picks its keys
    (DeepSeek-V3.2). Without the indexer's widths (``index_topk`` 0)
    a query reads every latent row it can see."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    #: Leading dims of the indexer's q and k that rotate.
    index_rope_dim: int = 64

    @property
    def indexer(self) -> bool:
        """An indexer selects each query's keys."""
        return self.index_topk > 0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def row_dim(self) -> int:
        """Values of a token's cache row: latent + shared rotary key."""
        return self.kv_rank + self.rope_dim

    @property
    def cache_dim(self) -> int:
        """Width of the row as the bank stores it: ``row_dim``, padded
        with zeros to whole 128-lane tiles once it is wider than one
        (576 → 640). With a 576-wide bank the TPU compiler gives the
        bank it returns another layout than the one it takes, and a
        step copies the whole bank twice (AOT compile for v5e, PR 28)."""
        row = self.row_dim
        return row if row <= 128 else -(-row // 128) * 128


@dataclass(frozen=True)
class YarnScaling:
    """YaRN's scaling of the rotary positions, in DeepSeek-V3's form
    (:func:`rope_tables`, :func:`yarn_score_factor`)."""

    factor: float
    #: The reach the model was trained at before the scaling.
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    #: KV heads for grouped-query attention; None → MHA (== n_heads).
    n_kv_heads: int | None = None
    #: SwiGLU hidden size (LLaMA sizing ≈ 8/3 · d_model, MXU-aligned).
    d_ff: int = 2048
    max_seq: int = 1024
    rope_theta: float = 10000.0
    #: YaRN's scaling of the rotary frequencies, with its factor on
    #: the attention scores (latent attention's); None → plain RoPE.
    rope_yarn: "YarnScaling | None" = None
    #: Compute dtype for MXU matmuls; params stay in param_dtype.
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    #: Tie the LM head to the token embedding (GPT-2-style).
    tie_embeddings: bool = True
    #: Rematerialize each block in backward (jax.checkpoint).
    remat: bool = False
    #: jax.checkpoint policy when ``remat``: "none" saves nothing
    #: (recompute everything), "dots" saves matmul outputs but
    #: recomputes the cheap elementwise chains (norms, RoPE, SwiGLU
    #: products) — the usual HBM-vs-FLOPs middle ground.
    remat_policy: str = "none"
    #: ``lax.scan`` unroll factor for the layer stack. Measured on v5e
    #: at 125M: unroll>1 is ~25% SLOWER (0.33 vs 0.45 MFU — the
    #: unrolled body loses the loop-level overlap scheduling), so the
    #: default stays 1; the knob exists because the tradeoff flips with
    #: model size and backend generation.
    scan_unroll: int = 1
    #: Causal (decoder) vs. bidirectional (encoder/BERT) attention.
    causal: bool = True
    #: Attention lowering, resolved by :func:`resolve_attn_fn`:
    #: "auto" (flash on TPU, xla elsewhere), "xla" (compiler-fused dense),
    #: "flash" (Pallas kernel, ops/flash_attention.py), "ring" / "ulysses"
    #: (sequence-parallel over the "seq" mesh axis — these need a mesh, so
    #: the Trainer resolves them; see parallel/ring_attention.py).
    attn_impl: str = "auto"
    #: Mixture-of-experts: number of experts per MLP (0 = dense). The
    #: expert dim shards over the "expert" mesh axis (EP — the
    #: all_to_all family, SURVEY.md §2 parallelism table).
    n_experts: int = 0
    #: Experts routed per token (top-k, GShard-style).
    expert_top_k: int = 2
    #: Expert capacity = ceil(top_k · tokens/expert · this factor);
    #: overflow tokens fall back to the residual stream (dropped).
    capacity_factor: float = 1.25
    #: Coefficient of the router load-balancing aux loss.
    moe_aux_coef: float = 0.01
    #: RMSNorm epsilon.
    norm_eps: float = 1e-6
    #: Latent (low-rank) K,V attention (models/sparse_mla.py) in place
    #: of GQA, with a learned sparse-attention indexer if its widths
    #: are stated; None → GQA.
    latent: "LatentAttention | None" = None
    #: Leading layers with a dense MLP before the expert layers (the
    #: layer stack is then two groups, each its own ``lax.scan``).
    n_dense_layers: int = 0
    #: SwiGLU hidden size of one routed or shared expert (None → d_ff).
    d_ff_expert: int | None = None
    #: Experts every token passes through, beside the routed ones.
    n_shared_experts: int = 0
    #: "softmax" (GShard top-k with capacity, :func:`_moe_mlp`) or
    #: "sigmoid_bias": sigmoid scores, selection by score + a learned
    #: correction bias, normalised gates × ``routed_scale``, no token
    #: dropped (:func:`_moe_dropless`).
    moe_router: str = "softmax"
    routed_scale: float = 1.0
    #: (groups, kept): the dropless router chooses inside groups — the
    #: ``n_experts`` scores form ``groups`` equal runs, a group's score
    #: is the sum of its two largest, and only the ``kept`` best
    #: groups' experts can be chosen. (1, 1): no limit.
    expert_groups: tuple[int, int] = (1, 1)
    #: (first, count): the slice of the ``n_experts`` this program
    #: holds, as one member of an expert-parallel group; the router
    #: keeps ``n_experts`` outputs and what the absent experts would
    #: add is left out. None → all of them.
    experts_held: tuple[int, int] | None = None
    #: Width of one attention head; None → ``d_model // n_heads``.
    d_head: int | None = None
    #: The attention kind of each layer, ``n_layers`` entries: the keys
    #: a query sees, itself included (a sliding window), or 0 for all
    #: of them (full attention). None → every layer full, one kind of
    #: cache. The windowed layers of one stack share one window. Such
    #: a stack is served (models/generate.py, serve_engine/): its
    #: window layers keep their own, bounded cache (:func:`cache_spec`).
    attn_windows: tuple[int, ...] | None = None
    #: Per-head RMSNorm of q and k (one scale vector of ``head_dim`` a
    #: layer each, shared by the heads), before any rotation.
    qk_norm: bool = False
    #: Full-attention layers of a stack with ``attn_windows`` carry no
    #: rotary positions (their window layers do).
    nope_full: bool = False

    def __post_init__(self):
        if self.rope_yarn is not None and self.latent is None:
            raise ValueError(
                "rope_yarn scales the scores of latent attention "
                "(yarn_score_factor); the GQA paths have no such factor")
        groups, kept = self.expert_groups
        if groups > 1 and (self.n_experts % groups or not
                           0 < kept <= groups
                           or self.n_experts // groups < 2):
            raise ValueError(
                f"expert_groups {self.expert_groups}: {self.n_experts} "
                f"experts do not form {groups} equal groups of two or "
                f"more with 1..{groups} of them kept")
        w = self.attn_windows
        if w is None:
            return
        if self.latent is not None:
            raise ValueError("attention kinds are GQA's: a latent cache "
                             "has its own selection of keys")
        if len(w) != self.n_layers or any(int(x) < 0 for x in w):
            raise ValueError(
                f"attn_windows states {len(w)} layer(s) {w}; the stack "
                f"has {self.n_layers}, each a window >= 1 or 0 for full")
        if len({int(x) for x in w if x}) > 1:
            raise ValueError(
                f"the window layers of one stack share one window (one "
                f"bounded pool); attn_windows states {sorted(set(w))}")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def window(self) -> int:
        """The one window of this stack's window layers; 0 without."""
        return max(self.attn_windows or (0,))

    @property
    def plain(self) -> bool:
        """A GQA stack of one group, every layer full attention, with
        the capacity router or none: what training, sharding,
        speculation and the contiguous cache are written for."""
        return (self.latent is None and self.moe_router == "softmax"
                and not (self.n_experts and self.n_dense_layers)
                and self.attn_windows is None and not self.qk_norm)

    @property
    def expert_ff(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads


#: Named presets for the BASELINE.json configs. "tiny" is the test-size
#: model every CPU-mesh test uses.
PRESETS: dict[str, TransformerConfig] = {
    "tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq=128,
    ),
    # ≈110M params. 6 heads × 128 head_dim (not GPT-2's 12 × 64): same
    # d_model/params/FLOPs, but 128-wide heads fill the MXU contraction
    # and the 128-lane tile — Dh=64 tensors pad 2× in HBM and ran the
    # flash kernel 1.5× slower (measured on v5e).
    "optimus-125m": TransformerConfig(n_heads=6),
    "optimus-350m": TransformerConfig(
        d_model=1024, n_layers=24, n_heads=8, d_ff=2816,
    ),
    # Encoder config for the async param-server baseline ("BERT-base async
    # param-server mode", BASELINE.json configs) — bidirectional attention,
    # MLM-style masked loss via loss_mask.
    "bert-base": TransformerConfig(
        vocab_size=30592, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
        max_seq=512, causal=False, tie_embeddings=True,
    ),
    "llama-3-8b": TransformerConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=8192, rope_theta=500000.0,
        tie_embeddings=False, remat=True,
    ),
    # Mixture-of-experts variant of the optimus config — 8 experts,
    # top-2 routing; the EP baseline (expert dim over the "expert" axis).
    "optimus-moe": TransformerConfig(
        d_ff=1024, n_experts=8, expert_top_k=2,
    ),
    "tiny-moe": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=64,
        max_seq=128, n_experts=4, expert_top_k=2,
    ),
}


def preset(name: str, **overrides) -> TransformerConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    return replace(PRESETS[name], **overrides)


# ------------------------------------------------------------------ params


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Initialize the stacked-parameter pytree.

    Block params carry a leading ``n_layers`` dim — the scan axis. Weight
    init: truncated-normal-free simple scaled normals (0.02 embed / GPT
    residual scaling on the out-projections).
    """
    if not cfg.plain:
        return _init_grouped(rng, cfg)
    L, D, H, K = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads
    Dh, F, V = cfg.head_dim, cfg.d_ff, cfg.vocab_size
    pd = cfg.param_dtype
    keys = jax.random.split(rng, 9)

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape, pd) * scale).astype(pd)

    resid_scale = 0.02 / jnp.sqrt(2.0 * L)
    E = cfg.n_experts
    if E:
        mlp = {
            "mlp_norm": jnp.ones((L, D), pd),
            "router": norm(keys[8], (L, D, E), 0.02),
            "w_gate": norm(keys[5], (L, E, D, F), 0.02),
            "w_up": norm(keys[6], (L, E, D, F), 0.02),
            "w_down": norm(keys[7], (L, E, F, D), resid_scale),
        }
    else:
        mlp = {
            "mlp_norm": jnp.ones((L, D), pd),
            "w_gate": norm(keys[5], (L, D, F), 0.02),
            "w_up": norm(keys[6], (L, D, F), 0.02),
            "w_down": norm(keys[7], (L, F, D), resid_scale),
        }
    params = {
        "embed": norm(keys[0], (V, D), 0.02),
        "blocks": {
            "attn_norm": jnp.ones((L, D), pd),
            "wq": norm(keys[1], (L, D, H, Dh), 0.02),
            "wk": norm(keys[2], (L, D, K, Dh), 0.02),
            "wv": norm(keys[3], (L, D, K, Dh), 0.02),
            "wo": norm(keys[4], (L, H, Dh, D), resid_scale),
            **mlp,
        },
        "final_norm": jnp.ones((D,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(jax.random.split(keys[0])[0], (D, V), 0.02)
    return params


def layer_groups(cfg: TransformerConfig) -> tuple[tuple[str, int], ...]:
    """The layer stack as runs of identical layers, ``(kind, count)``:
    each run is one ``lax.scan`` over its own stacked parameters. The
    kind is the MLP's, ``"dense"`` or ``"experts"``, and, in a stack
    that states attention kinds (``cfg.attn_windows``), the
    attention's after a ``+``: ``L`` for a window layer, ``G`` for a
    full one (``"experts+L"``), so a period ``LLLG`` over expert
    layers is two runs a period."""
    nd = cfg.n_dense_layers if cfg.n_experts else cfg.n_layers
    kinds = ["dense" if l < nd else "experts"
             for l in range(cfg.n_layers)]
    if cfg.attn_windows is not None:
        kinds = [f"{k}+{'L' if w else 'G'}"
                 for k, w in zip(kinds, cfg.attn_windows)]
    runs: list[list] = []
    for k in kinds:
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    return tuple((k, n) for k, n in runs)


def block_groups(params: dict, cfg: TransformerConfig) -> list[tuple]:
    """``[(stacked layer params, first layer index, count)]``, one a
    group: ``params["blocks"]`` is the one stacked dict of a stack
    with one group, or a tuple of them in layer order."""
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        blocks = (blocks,)
    groups = layer_groups(cfg)
    if len(blocks) != len(groups):
        raise ValueError(
            f"the parameters hold {len(blocks)} layer group(s); this "
            f"configuration's stack is {groups}")
    out, first = [], 0
    for stacked, (_kind, n) in zip(blocks, groups):
        out.append((stacked, first, n))
        first += n
    return out


def cache_spec(cfg: TransformerConfig) -> dict[str, tuple[int, ...]]:
    """What one token holds in one layer's cache: named arrays and
    their shapes. The block pool allocates its banks from this, the
    migrator packs by it, and the paged programs carry the banks as
    one dict with these names. Every layer holds the same arrays a
    token; how MANY tokens a layer holds is its kind's
    (:func:`cache_layers`)."""
    if cfg.latent is not None:
        spec = {"ckv": (cfg.latent.cache_dim,)}
        if cfg.latent.indexer:
            spec["ki"] = (cfg.latent.index_dim,)
        return spec
    return {"k": (cfg.kv_heads, cfg.head_dim),
            "v": (cfg.kv_heads, cfg.head_dim)}


def cache_layers(cfg: TransformerConfig) -> dict[str, tuple[int, ...]] | None:
    """The layers of each kind of cache, in a stack that states
    attention kinds: ``{"full": layers, "window": layers}``. A full
    layer keeps every token of a sequence; a window layer only the
    last ``cfg.window`` a query can still see, so its blocks wholly
    behind the window are given back as the sequence advances. Each
    kind has banks, a pool and a block table a sequence of its own
    (``serve_engine``), each bank with as many layers as the kind has.
    None where every layer holds the same (one pool)."""
    if cfg.attn_windows is None:
        return None
    return {"full": tuple(l for l, w in enumerate(cfg.attn_windows)
                          if not w),
            "window": tuple(l for l, w in enumerate(cfg.attn_windows)
                            if w)}


def scaled_normal(key, shape, scale, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _init_mlp(key, cfg: TransformerConfig, kind: str, n: int) -> dict:
    """The MLP half of ``n`` stacked layers of one kind."""
    kind = kind.split("+")[0]
    D, pd = cfg.d_model, cfg.param_dtype
    resid = 0.02 / (2.0 * cfg.n_layers) ** 0.5
    ks = jax.random.split(key, 8)
    norm = partial(scaled_normal, dtype=pd)

    if kind == "dense":
        F = cfg.d_ff
        return {"mlp_norm": jnp.ones((n, D), pd),
                "w_gate": norm(ks[0], (n, D, F), 0.02),
                "w_up": norm(ks[1], (n, D, F), 0.02),
                "w_down": norm(ks[2], (n, F, D), resid)}
    F, E = cfg.expert_ff, cfg.n_experts
    held = cfg.held[1]
    out = {"mlp_norm": jnp.ones((n, D), pd),
           "router": norm(ks[3], (n, D, E), 0.02),
           "w_gate": norm(ks[0], (n, held, D, F), 0.02),
           "w_up": norm(ks[1], (n, held, D, F), 0.02),
           "w_down": norm(ks[2], (n, held, F, D), resid)}
    if cfg.moe_router == "sigmoid_bias":
        out["router_bias"] = norm(ks[4], (n, E), 0.02).astype(jnp.float32)
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        out.update(ws_gate=norm(ks[5], (n, D, Fs), 0.02),
                   ws_up=norm(ks[6], (n, D, Fs), 0.02),
                   ws_down=norm(ks[7], (n, Fs, D), resid))
    return out


def _init_grouped(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Parameters of a stack with several groups, latent attention,
    attention kinds or a dropless router: one stacked dict a group."""
    D, V, pd = cfg.d_model, cfg.vocab_size, cfg.param_dtype
    H, K, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    resid = 0.02 / (2.0 * cfg.n_layers) ** 0.5
    norm = partial(scaled_normal, dtype=pd)
    blocks = []
    for g, (kind, n) in enumerate(layer_groups(cfg)):
        ka, km = jax.random.split(jax.random.fold_in(rng, g + 1))
        if cfg.latent is not None:
            from ptype_tpu.models import sparse_mla

            attn = sparse_mla.init_attention(ka, cfg, n)
        else:
            ks = jax.random.split(ka, 4)
            attn = {"attn_norm": jnp.ones((n, D), pd),
                    "wq": norm(ks[0], (n, D, H, Dh), 0.02),
                    "wk": norm(ks[1], (n, D, K, Dh), 0.02),
                    "wv": norm(ks[2], (n, D, K, Dh), 0.02),
                    "wo": norm(ks[3], (n, H, Dh, D), resid)}
            if cfg.qk_norm:
                attn.update(q_norm=jnp.ones((n, Dh), pd),
                            k_norm=jnp.ones((n, Dh), pd))
        blocks.append({**attn, **_init_mlp(km, cfg, kind, n)})
    k0, k1 = jax.random.split(jax.random.fold_in(rng, 0))
    params = {"embed": norm(k0, (V, D), 0.02),
              "blocks": blocks[0] if len(blocks) == 1 else tuple(blocks),
              "final_norm": jnp.ones((D,), pd)}
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(k1, (D, V), 0.02)
    return params


def count_params(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: TransformerConfig, seq_len: int,
                    n_params: int | None = None) -> float:
    """Fwd+bwd training FLOPs per token (PaLM appendix B convention):
    ``6·N_matmul + 12·L·D·S`` — the MFU denominator."""
    if n_params is None and not cfg.plain:
        raise ValueError(
            "flops_per_token counts the GQA block of a stack with one "
            "group, every layer full attention; a latent-attention or "
            "grouped stack, or one with attention kinds (windows, a "
            "q/k norm), is not trained here, and its serving counts "
            "are the benchmark family's")
    if n_params is None:
        # ACTIVE matmul params only (norms excluded — negligible; for
        # MoE, the top-k routed experts count, not the full bank).
        L, D = cfg.n_layers, cfg.d_model
        H, K, Dh, F = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff
        if cfg.n_experts:
            mlp = cfg.expert_top_k * 3 * D * F + D * cfg.n_experts
        else:
            mlp = 3 * D * F
        per_layer = D * Dh * (H + 2 * K) + H * Dh * D + mlp
        n_params = cfg.vocab_size * D + L * per_layer
        if not cfg.tie_embeddings:
            n_params += D * cfg.vocab_size
    return 6.0 * n_params + 12.0 * cfg.n_layers * cfg.d_model * seq_len


# ----------------------------------------------------------------- forward


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * scale.astype(x.dtype)


def rope_tables(cfg: TransformerConfig, seq_len: int | None = None,
                positions: jax.Array | None = None,
                dim: int | None = None):
    """(sin, cos) tables, shape (S, head_dim/2), f32. Pass either a
    ``seq_len`` (positions 0..S-1, the training path) or explicit
    ``positions`` (the decode path, models/generate.py) — one formula
    for both, so RoPE changes can never diverge between them."""
    half = (dim or cfg.head_dim) // 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    if positions is None:
        positions = jnp.arange(seq_len)
    y = cfg.rope_yarn
    if y is not None:
        # DeepSeek-V3's YaRN: a pair that turns more than ``beta_fast``
        # times within the original reach keeps its frequency, one that
        # turns fewer than ``beta_slow`` times has it divided by
        # ``factor``, and a linear ramp over the pair index blends the
        # two between those (``find_correction_range``). Written as one
        # factor on ``inv_freq`` so that ``factor`` 1 is exactly 1.
        def pair_of(turns):
            return (2 * half * math.log(y.original_max
                                        / (turns * 2 * math.pi))
                    / (2 * math.log(cfg.rope_theta)))

        low = max(math.floor(pair_of(y.beta_fast)), 0)
        high = min(math.ceil(pair_of(y.beta_slow)), 2 * half - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / ((high if high != low else high + 0.001) - low), 0, 1)
        inv_freq = inv_freq * (1.0 - ramp * (1.0 - 1.0 / y.factor))
    # Broadcast (not outer, which flattens): positions may be (S,) —
    # shared, the training path — or (B, S) for per-row ragged offsets.
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    if y is not None:
        m = (yarn_mscale(y.factor, y.mscale)
             / yarn_mscale(y.factor, y.mscale_all_dim))
        return jnp.sin(angles) * m, jnp.cos(angles) * m
    return jnp.sin(angles), jnp.cos(angles)


def yarn_score_factor(cfg: TransformerConfig) -> float:
    """What YaRN multiplies the attention scores by, beside
    ``qk_dim ** -0.5``: ``yarn_mscale(factor, mscale_all_dim) ** 2``
    (DeepSeek-V3); 1.0 without YaRN or with ``mscale_all_dim`` 0."""
    y = cfg.rope_yarn
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate pairs (x1, x2) of the head dim. x: (B, S, H, Dh).

    ``sin``/``cos`` are (S, half) — shared positions, the training
    path — or (B, S, half) for PER-ROW positions (left-padded ragged
    prompts, where row i's column s sits at position s - pad_i)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if sin.ndim == 2:
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _attention(q, k, v, cfg: TransformerConfig, kv_mask=None):
    """Causal attention; q:(B,S,H,Dh) k,v:(B,S,K,Dh). Softmax in f32.

    GQA-native: query heads are grouped as (K, G) and contracted against
    the K kv heads directly — no ``jnp.repeat`` materializing H-head K/V
    (the memory GQA exists to avoid; VERDICT r2 weak #4).

    ``kv_mask`` (B, S) bool, optional: keys where False are masked out
    for every query — the left-pad validity mask of ragged-prompt
    prefill (models/generate.py). Training never passes it."""
    B, S, H, Dh = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, H // K, Dh)
    scores = jnp.einsum("bqngd,bsnd->bngqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(Dh))
    if cfg.causal:
        causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
        scores = jnp.where(causal[None, None, None], scores,
                           jnp.float32(-1e30))
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, None, :], scores,
                           jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bngqs,bsnd->bqngd", probs, v)
    return o.reshape(B, S, H, Dh)


def default_attn_impl() -> str:
    """THE 'auto' policy, in one place (resolve_attn_fn, the Ulysses
    inner default, and prefill's gate all consult it — hand-copied
    backend checks drift): flash kernel on TPU, XLA dense elsewhere."""
    return "flash" if jax.default_backend() == "tpu" else "xla"


def resolve_attn_fn(cfg: TransformerConfig, mesh=None):
    """Resolve ``cfg.attn_impl`` to a concrete ``attn_fn(q, k, v, cfg)``.

    "auto" picks the Pallas flash kernel on TPU backends (the dense path
    materializes B·H·S² f32 scores — the thing that kills the ≥30% MFU
    target) and the XLA-fused dense path elsewhere. "ring"/"ulysses"
    need a mesh with a "seq" axis; the Trainer passes its mesh, and a
    bare ``forward`` call raises a clear error instead of silently
    running dense.
    """
    impl = cfg.attn_impl
    if impl == "auto":
        impl = default_attn_impl()
    if impl == "xla":
        return _attention
    if impl == "flash":
        from ptype_tpu.ops.flash_attention import make_flash_attn_fn

        return make_flash_attn_fn(mesh)
    if impl in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(
                f"attn_impl={impl!r} needs a mesh with a 'seq' axis — "
                "use the Trainer (which passes its mesh) or pass attn_fn "
                "explicitly (parallel/ring_attention.py)"
            )
        from ptype_tpu.parallel.ring_attention import (
            make_ring_attention, make_ulysses_attention)

        make = (make_ring_attention if impl == "ring"
                else make_ulysses_attention)
        return make(mesh)
    raise ValueError(f"unknown attn_impl {impl!r}; "
                     "want auto|xla|flash|ring|ulysses")


def _moe_mlp(h, layer, cfg: TransformerConfig, capacity: int | None = None):
    """GShard-style top-k MoE MLP. h: (B, S, D) → (y, aux_loss).

    Einsum dispatch with static expert capacity: tokens scatter into an
    (E, C, D) buffer, the expert SwiGLUs run as one batched einsum over
    the stacked expert weights (expert dim shardable over the "expert"
    mesh axis — GSPMD lowers the dispatch to all_to_all), and outputs
    gather back weighted by the router. Overflow past capacity falls
    back to the residual stream. ``capacity`` overrides the
    capacity_factor formula — decode passes the exact per-step token
    count so single-token steps never drop (generate.py).
    """
    B, S, D = h.shape
    E, topk = cfg.n_experts, cfg.expert_top_k
    dt = cfg.dtype
    T = B * S
    x = h.reshape(T, D)

    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32),
        layer["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_w, gate_e = jax.lax.top_k(probs, topk)  # (T, k)
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)

    # Load-balancing aux (Switch eq. 4): E · Σ_e frac_tokens · frac_prob.
    me = jnp.mean(probs, axis=0)
    dispatched = jnp.sum(jax.nn.one_hot(gate_e, E, dtype=jnp.float32),
                        axis=1)  # (T, E)
    ce = jnp.mean(dispatched, axis=0) / topk
    aux = E * jnp.sum(me * ce)

    import math as _math

    C = (capacity if capacity is not None
         else max(_math.ceil(topk * T / E * cfg.capacity_factor), 1))
    flat_e = gate_e.reshape(-1)  # (T·k,)
    # Position within each expert, token-priority order.
    counts = jnp.cumsum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=0)
    pos = counts[jnp.arange(T * topk), flat_e] - 1
    keep = pos < C
    tok = jnp.arange(T * topk) // topk

    # Dispatch via the INVERSE index map: scatter each kept assignment's
    # token id (a single i32) into its (expert, slot) cell — (e, slot)
    # pairs are unique for kept entries and overflow rides slot=C,
    # dropped by mode="drop" — then GATHER token rows into the (E, C, D)
    # buffer. Scattering the D-wide activation rows instead
    # (``.at[e, slot].add(x[tok])``, the previous lowering) ran 22×
    # slower on v5e (102 ms vs 4.7 ms fwd+bwd at T=16k, D=768: TPU
    # scatter serializes; gather vectorizes).
    slot_oob = jnp.where(keep, pos, C)
    inv = jnp.zeros((E, C), jnp.int32).at[flat_e, slot_oob].set(
        tok + 1, mode="drop", unique_indices=True)  # 0 = empty slot
    X = jnp.where((inv > 0)[..., None],
                  x[jnp.maximum(inv - 1, 0)].astype(dt), 0)
    slot = jnp.clip(pos, 0, C - 1)

    g = jnp.einsum("ecd,edf->ecf", X, layer["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", X, layer["w_up"].astype(dt))
    Y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   layer["w_down"].astype(dt))

    y_tok = Y[flat_e, slot] * keep[:, None].astype(dt)
    y_tok = y_tok * gate_w.reshape(-1)[:, None].astype(dt)
    y = jnp.sum(y_tok.reshape(T, topk, D), axis=1)
    return y.reshape(B, S, D), aux


def _swiglu(h, w_gate, w_up, w_down, dt):
    gate = jnp.einsum("bsd,df->bsf", h, w_gate.astype(dt))
    up = jnp.einsum("bsd,df->bsf", h, w_up.astype(dt))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      w_down.astype(dt))


#: Rows in one tile of a dropless layer's routed rows
#: (:func:`_moe_dropless`): a decode step's (T = slots) and a prefill
#: chunk's. Chosen on the chip (PERF.md §6, PR 34).
EXPERT_TILE_STEP = 8
EXPERT_TILE_CHUNK = 128


def expert_tile(T: int) -> int:
    """Rows in one tile of the routed rows of ``T`` tokens: from the
    static shape alone, the small tile below a chunk tile's worth of
    tokens (a decode step, a short chunk bucket), the large one from
    there on."""
    return EXPERT_TILE_STEP if T < EXPERT_TILE_CHUNK else EXPERT_TILE_CHUNK


def expert_tiles(load, T: int):
    """``[tiles, hit]`` int32 of one dropless layer over ``T`` tokens,
    from its ``load`` (:func:`_moe_dropless`): the tiles its loop
    visits, ``Σ_e ceil(n_e / tile)`` over the held experts (its trip
    count), and the held experts that took a row at all."""
    n = load[:-1]
    return jnp.stack([jnp.sum(-(-n // expert_tile(T))),
                      jnp.sum(n > 0)]).astype(jnp.int32)


def _moe_dropless(h, layer, cfg: TransformerConfig, live=None, at=None):
    """Dropless top-k MoE over the experts held here, with a shared
    expert. h: (B, S, D) → (y, load).

    The router keeps its ``n_experts`` outputs: sigmoid scores ``s``,
    the ``expert_top_k`` largest ``s + bias`` chosen (``bias`` the
    learned correction, selection only; with ``cfg.expert_groups``
    among the experts of the best groups alone, DeepSeek-V3's
    group-limited choice), gates ``routed_scale · s / Σ_chosen s``.
    Of a token's choices those that fall on ``cfg.held`` are computed,
    as a grouped product over tiles of the routed rows: the
    assignments are ordered by held expert (token order inside an
    expert), each expert's run padded to a multiple of
    :func:`expert_tile` rows so that a tile belongs to one expert, and
    ONE loop runs over the tiles in use, ``Σ_e ceil(n_e / tile)``, a
    trip count that is data. A trip gathers its tile's token rows,
    takes its expert's three matrices as a slice of the stack, computes
    the SwiGLU and writes its rows of the output; each assignment then
    gathers its row, times its gate. An expert no row chose is not
    read, and none is padded to T rows. The list's static bound covers
    every load (a token chooses an expert at most once): no token is
    dropped however crowded one expert is, and no capacity is set.
    What the absent experts would have added is left out.

    ``layer``'s ``w_gate``, ``w_up``, ``w_down`` are this layer's
    ``(held, D, F)`` stacks or, with ``at`` (the layer's index, a
    traced scalar), a whole group's ``(n, held, D, F)``: the paged
    programs' layer scan closes over the group's stacks and scans the
    index, so that the loop's dots read their expert's matrices in
    place out of the parameters. (Sliced by the scan, a layer's stack
    would be the loop's operand and so be copied out first, as
    ``lax.ragged_dot``'s custom call made the compiler do, 1.2 GB a
    layer at GLM-5's widths: AOT compile for v5e, PR 28 and PR 34.)

    ``load`` (held + 1,) int32: assignments per held expert, and last
    those whose expert is not held; of the tokens ``live`` (B, S)
    marks, if given. A decode step's inactive lanes and a chunk's pads
    route too, all alike: their assignments visit no tile and are not
    counted (counted, they would read as one crowded expert)."""
    B, S, D = h.shape
    k = cfg.expert_top_k
    first, count = cfg.held
    dt = cfg.dtype
    T = B * S
    tile = expert_tile(T)
    # Σ_e ceil(n_e / tile) <= Σ n_e // tile + held, and n_e <= T.
    max_tiles = min(T * min(k, count) // tile + count,
                    count * -(-T // tile))
    R = max_tiles * tile
    x = h.reshape(T, D)
    with jax.named_scope("router"):
        s = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", x.astype(jnp.float32),
            layer["router"].astype(jnp.float32), precision="highest"))
        sel = s + layer["router_bias"].astype(jnp.float32)
        groups, kept = cfg.expert_groups
        if groups > 1:
            # Choice inside groups: only the experts of the ``kept``
            # groups with the largest sum of their two best can win.
            by_group = sel.reshape(T, groups, -1)
            standing = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
            _, best = lax.top_k(standing, kept)
            stays = jnp.any(jax.nn.one_hot(best, groups, dtype=bool),
                            axis=1)  # (T, groups)
            sel = jnp.where(stays[:, :, None], by_group,
                            -jnp.inf).reshape(sel.shape)
        _, idx = lax.top_k(sel, k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        g = cfg.routed_scale * w / jnp.maximum(
            jnp.sum(w, axis=-1, keepdims=True), 1e-20)
        local = idx - first
        here = ((local >= 0) & (local < count)).reshape(-1)  # (T·k,)
        alive = (jnp.ones((T * k,), bool) if live is None
                 else jnp.repeat(live.reshape(T), k))
        run = here & alive  # the assignments computed here
        e = jnp.where(run, local.reshape(-1), count)
        onehot = jax.nn.one_hot(e, count + 1, dtype=jnp.int32)
        n = jnp.sum(onehot[:, :count], axis=0)
        load = jnp.concatenate(
            [n, jnp.sum(alive & ~here, dtype=jnp.int32)[None]])
        # Row of each assignment within its expert, in token order,
        # and in the list: an expert's run starts on a tile.
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        tiles = -(-n // tile)
        ends = jnp.cumsum(tiles)
        n_tiles = expert_tiles(load, T)[0]
        starts = jnp.append((ends - tiles) * tile, R)
        row = jnp.where(run, starts[e] + pos, R)
        # The inverse map list row -> token, as _moe_mlp builds it (a
        # scatter of one int32 an assignment; the D-wide rows are
        # gathered). What is not computed names row R: dropped.
        tok = jnp.arange(T * k) // k
        inv = jnp.zeros((R,), jnp.int32).at[row].set(
            tok + 1, mode="drop", unique_indices=True)
        # tile -> its expert (tiles past those in use are never run).
        owner = jnp.minimum(jnp.sum(
            jnp.arange(max_tiles)[:, None] >= ends[None, :], axis=1),
            count - 1)
        gate = jnp.where(run, g.reshape(-1), 0.0)
    with jax.named_scope("experts"):
        stacks = [layer[m] if at is not None else layer[m][None]
                  for m in ("w_gate", "w_up", "w_down")]
        l = jnp.int32(0) if at is None else at
        xt = x.astype(dt)

        def trip(t, Y):
            rows = lax.dynamic_slice(inv, (t * tile,), (tile,))
            X = jnp.where((rows > 0)[:, None],
                          xt[jnp.maximum(rows - 1, 0)], 0)
            mine = (l, owner[t], 0, 0)
            wg, wu, wd = (lax.dynamic_slice(
                m, mine, (1, 1) + m.shape[2:])[0, 0].astype(dt)
                for m in stacks)
            out = jnp.dot(jax.nn.silu(jnp.dot(X, wg)) * jnp.dot(X, wu), wd)
            return lax.dynamic_update_slice(Y, out, (t * tile, 0))

        # The stacks are closed over and only read: nothing of them is
        # loop state.
        Y = lax.fori_loop(0, n_tiles, trip, jnp.zeros((R, D), dt))
        ys = Y[jnp.minimum(row, R - 1)] * gate[:, None].astype(dt)
        y = jnp.sum(ys.reshape(T, k, D), axis=1).reshape(B, S, D)
    if "ws_gate" in layer:
        with jax.named_scope("shared_expert"):
            y = y + _swiglu(h, layer["ws_gate"], layer["ws_up"],
                            layer["ws_down"], dt)
    return y, load


@jax.named_scope("qkv")
def qkv_proj(x, layer, cfg: TransformerConfig, sin, cos,
             rotate: bool = True):
    """Pre-norm + Q/K/V projections + RoPE. x: (B, S, D) → three
    (B, S, H|K, Dh). Shared by training forward and the KV-cache
    prefill/decode paths (models/generate.py) — the block math lives
    here once. With ``cfg.qk_norm`` each head of q and k is
    RMS-normed over its ``head_dim`` values first; ``rotate`` False
    leaves the rotary positions out (a full-attention layer of a
    ``cfg.nope_full`` stack)."""
    dt = cfg.dtype
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if not rotate:
        return q, k, v
    return apply_rope(q, sin, cos), apply_rope(k, sin, cos), v


@jax.named_scope("attn_out")
def attn_residual(x, o, layer, cfg: TransformerConfig):
    """Output projection + residual add. o: (B, S, H, Dh)."""
    return x + jnp.einsum("bshk,hkd->bsd", o,
                          layer["wo"].astype(cfg.dtype))


@jax.named_scope("mlp")
def mlp_residual(x, layer, cfg: TransformerConfig,
                 moe_capacity: int | None = None, live=None, at=None):
    """Pre-norm MLP + residual: a dense SwiGLU, or, in a layer that
    holds a router, the experts behind it as ``cfg.moe_router`` routes
    them. → (x, aux, load): the capacity router's load-balancing loss
    (0.0 without one) and the dropless router's load counts
    (:func:`_moe_dropless`, which also takes ``live`` and ``at``; None
    without one)."""
    dt = cfg.dtype
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    if "router" in layer:
        if cfg.moe_router == "sigmoid_bias":
            y, load = _moe_dropless(h, layer, cfg, live, at)
            return x + y, jnp.float32(0.0), load
        y, aux = _moe_mlp(h, layer, cfg, capacity=moe_capacity)
        return x + y, aux, None
    gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"].astype(dt))
    up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(dt))
    x = x + jnp.einsum(
        "bsf,fd->bsd", jax.nn.silu(gate) * up, layer["w_down"].astype(dt)
    )
    return x, jnp.float32(0.0), None


def _block(x, layer, sin, cos, cfg: TransformerConfig, attn_fn):
    """One transformer block; x: (B, S, D) in compute dtype.
    Returns (x, moe_aux) — aux is 0.0 for dense MLPs."""
    if cfg.latent is not None:
        from ptype_tpu.models import sparse_mla

        o = sparse_mla.attend_expanded(x, layer, cfg)
    else:
        q, k, v = qkv_proj(x, layer, cfg, sin, cos)
        with jax.named_scope("attn"):
            o = attn_fn(q, k, v, cfg)
    x = attn_residual(x, o, layer, cfg)
    x, aux, _load = mlp_residual(x, layer, cfg)
    return x, aux


def hidden_with_aux(params: dict, tokens: jax.Array,
                    cfg: TransformerConfig, attn_fn=None):
    """Backbone up to (and including) the final norm: (x (B,S,D) in
    compute dtype, aux). The LM head is applied by the caller — either
    densely (:func:`forward_with_aux`) or fused with the loss
    (:func:`loss_terms`) so the (B,S,V) f32 logits never materialize."""
    if cfg.attn_windows is not None:
        raise ValueError(
            "the full-sequence forward runs every layer as full "
            "attention; a stack with attention kinds (windows) is "
            "served through the paged programs (models/generate.py) "
            "until the flash kernels take a window mask")
    attn_fn = attn_fn or resolve_attn_fn(cfg)
    B, S = tokens.shape
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(dt)
    sin, cos = rope_tables(cfg, S)

    def body(x, layer):
        x, aux = _block(x, layer, sin, cos, cfg, attn_fn)
        return x, aux

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, policy=policy)
    aux = None
    for stacked, _first, _n in block_groups(params, cfg):
        x, auxs = lax.scan(body, x, stacked, unroll=cfg.scan_unroll)
        aux = jnp.sum(auxs) if aux is None else aux + jnp.sum(auxs)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.float32(0.0) if aux is None else aux


def _head_weight(params: dict, cfg: TransformerConfig) -> jax.Array:
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])


@jax.named_scope("head")
def head_logits(x: jax.Array, head: jax.Array,
                cfg: TransformerConfig) -> jax.Array:
    """LM head matmul: bf16 operands, f32 MXU accumulation.

    Casting both operands to f32 (the previous lowering) ran the
    largest matmul in the model at half MXU rate (VERDICT r2 weak #7);
    ``preferred_element_type`` keeps the f32 accumulator — and the f32
    logits the softmax needs — with bf16 inputs."""
    return jnp.einsum("...d,dv->...v", x.astype(cfg.dtype),
                      head.astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def forward_with_aux(params: dict, tokens: jax.Array,
                     cfg: TransformerConfig, attn_fn=None):
    """(logits (B,S,V) f32, aux) — aux is the summed MoE router
    load-balancing loss (0.0 for dense configs)."""
    x, aux = hidden_with_aux(params, tokens, cfg, attn_fn)
    return head_logits(x, _head_weight(params, cfg), cfg), aux


def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            attn_fn=None) -> jax.Array:
    """Logits (B, S, V) in f32. ``attn_fn`` overrides the attention
    implementation (ring attention injects itself here)."""
    return forward_with_aux(params, tokens, cfg, attn_fn)[0]


def nll_terms_from_logits(logits: jax.Array, batch: dict):
    """(nll_sum, denom) — the unnormalized pieces of the (masked) mean
    cross-entropy. Gradient accumulation sums these across microbatches
    and divides ONCE, so the loss (and its grads) are invariant to the
    accumulation factor even when valid-token counts differ per
    microbatch."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, batch["targets"][..., None], axis=-1
    )[..., 0]
    nll = logz - gold
    mask = batch.get("loss_mask")
    if mask is None:
        return jnp.sum(nll), jnp.float32(nll.size)
    mask = mask.astype(nll.dtype)
    return jnp.sum(nll * mask), jnp.maximum(jnp.sum(mask), 1.0)


def nll_from_logits(logits: jax.Array, batch: dict) -> jax.Array:
    """(Masked) mean cross-entropy from precomputed logits — shared by
    the dense forward, the pipelined forward, and eval paths."""
    nll_sum, denom = nll_terms_from_logits(logits, batch)
    return nll_sum / denom


#: Rows of (tokens × vocab) logits materialized at once by the fused
#: loss head. 8192 × 32k vocab f32 ≈ 1 GB of transient per chunk — big
#: enough to keep the MXU fed, small enough that the full (B·S, V)
#: tensor (4.3 GB at batch 32 / seq 1024) never exists.
LOSS_CHUNK_ROWS = 8192


@jax.named_scope("loss")
def _chunked_nll(x, head, targets, mask, cfg: TransformerConfig):
    """(nll_sum, denom) with the head matmul fused into the loss.

    The dense path materializes (B, S, V) f32 logits — at the bench's
    32-per-chip batch that is 4.3 GB and was the HBM wall that forced
    the ladder down to batch 16. Here rows stream through a
    ``lax.scan`` in :data:`LOSS_CHUNK_ROWS` chunks; each chunk's body is
    rematerialized (``jax.checkpoint``) so backward recomputes the
    chunk logits instead of saving them — saved residuals shrink from
    O(B·S·V) to O(B·S·D).
    """
    B, S, D = x.shape
    n = B * S
    x = x.reshape(n, D)
    targets = targets.reshape(n)
    mask = None if mask is None else mask.reshape(n).astype(jnp.float32)

    # Largest divisor of n that fits the chunk budget — NOT just "n if
    # it doesn't divide evenly": global batch 12 × seq 1024 (n=12288)
    # must chunk at 6144, not fall back to one 1.6 GB dense chunk.
    chunk = min(n, LOSS_CHUNK_ROWS)
    while n % chunk:
        chunk -= 1
    if chunk < 512:  # pathological n (odd/prime): dense beats 1-row scan
        chunk = n
    xc = x.reshape(n // chunk, chunk, D)
    tc = targets.reshape(n // chunk, chunk)
    mc = (jnp.ones((n // chunk, chunk), jnp.float32) if mask is None
          else mask.reshape(n // chunk, chunk))

    @jax.checkpoint
    def body(carry, xs):
        nll_sum, denom = carry
        xr, tr, mr = xs
        logits = head_logits(xr, head, cfg)  # (chunk, V) f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        nll = (logz - gold) * mr
        return (nll_sum + jnp.sum(nll), denom + jnp.sum(mr)), None

    (nll_sum, denom), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (xc, tc, mc))
    return nll_sum, jnp.maximum(denom, 1.0)


def loss_terms(params: dict, batch: dict, cfg: TransformerConfig,
               attn_fn=None):
    """(nll_sum, denom, aux) — loss pieces for gradient accumulation
    (train/trainer.py sums across microbatches, normalizes once). The
    LM head runs fused with the cross-entropy (:func:`_chunked_nll`):
    full logits are never materialized."""
    x, aux = hidden_with_aux(params, batch["tokens"], cfg, attn_fn)
    nll_sum, denom = _chunked_nll(
        x, _head_weight(params, cfg), batch["targets"],
        batch.get("loss_mask"), cfg)
    return nll_sum, denom, aux


def loss_fn(params: dict, batch: dict, cfg: TransformerConfig,
            attn_fn=None) -> jax.Array:
    """Mean next-token cross-entropy (+ MoE router aux when configured).
    ``batch``: tokens (B,S) int32, targets (B,S) int32, optional
    loss_mask (B,S)."""
    nll_sum, denom, aux = loss_terms(params, batch, cfg, attn_fn)
    loss = nll_sum / denom
    if cfg.n_experts:
        loss = loss + cfg.moe_aux_coef * aux
    return loss


# ---------------------------------------------------------------- sharding


def _maybe(axis: str | None, size: int, axis_sizes: dict[str, int]):
    """Use the axis in a spec only if present and it divides ``size`` —
    strategies degrade to replication when an axis is absent
    (mesh.py axis conventions)."""
    if axis is None or axis not in axis_sizes:
        return None
    return axis if size % axis_sizes[axis] == 0 else None


def param_specs(cfg: TransformerConfig,
                axis_sizes: dict[str, int]) -> dict:
    """PartitionSpec pytree matching :func:`init_params`.

    Conventions (scaling-book layout): ``model`` (TP) shards head and ff
    dims — megatron-style column/row pairing so each block needs exactly
    one psum on each residual write; ``fsdp`` shards the d_model dim of
    every matmul weight (ZeRO-3-style, allgathered by GSPMD per layer).
    Block specs carry a leading None for the scan/layer dim.
    """
    if not cfg.plain:
        raise ValueError(
            "param_specs shards the GQA block of a stack with one "
            "group, every layer full attention; a latent-attention, "
            "grouped or dropless-expert stack, or one with attention "
            "kinds (windows, a q/k norm), runs on one device (serving) "
            "until its sharding and the experts' all-to-all are "
            "written")
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    H, K, E = cfg.n_heads, cfg.kv_heads, cfg.n_experts
    fsdp = partial(_maybe, "fsdp", axis_sizes=axis_sizes)
    tp = partial(_maybe, "model", axis_sizes=axis_sizes)
    ep = partial(_maybe, "expert", axis_sizes=axis_sizes)
    if E:
        mlp_specs = {
            "mlp_norm": P(None, None),
            "router": P(None, fsdp(D), None),
            "w_gate": P(None, ep(E), fsdp(D), tp(F)),
            "w_up": P(None, ep(E), fsdp(D), tp(F)),
            "w_down": P(None, ep(E), tp(F), fsdp(D)),
        }
    else:
        mlp_specs = {
            "mlp_norm": P(None, None),
            "w_gate": P(None, fsdp(D), tp(F)),
            "w_up": P(None, fsdp(D), tp(F)),
            "w_down": P(None, tp(F), fsdp(D)),
        }
    specs = {
        "embed": P(tp(V), fsdp(D)),
        "blocks": {
            "attn_norm": P(None, None),
            "wq": P(None, fsdp(D), tp(H), None),
            "wk": P(None, fsdp(D), tp(K), None),
            "wv": P(None, fsdp(D), tp(K), None),
            "wo": P(None, tp(H), None, fsdp(D)),
            **mlp_specs,
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(fsdp(D), tp(V))
    return specs


def batch_spec(axis_sizes: dict[str, int], seq_axis: bool = False) -> P:
    """Token batch sharding: batch dim over every data-like axis present
    (data + fsdp both act as data for activations); optionally the seq
    dim over ``seq`` (ring attention)."""
    batch_axes = tuple(a for a in (DATA_AXIS, "fsdp")
                       if a in axis_sizes)
    first = batch_axes if batch_axes else None
    second = "seq" if (seq_axis and "seq" in axis_sizes) else None
    return P(first, second)

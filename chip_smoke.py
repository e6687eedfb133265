"""chip_smoke.py — the standing proof that the main path runs on the TPU.

One process, every chip the host shows (``jax.device_count()`` of 1 or
4), the entry points a user would call, at the full width of
optimus-125M with random weights from a seed:

- **train** — ``join(cfg)`` → ``cluster.mesh()`` → ``Trainer``, the way
  ``examples/optimus/trainer.py`` builds it, S=1024, per-chip batch 16,
  bf16, ``attn_impl="auto"``;
- **train-store** (more than one chip) — the same model through
  ``StoreDPTrainer`` on a ``TensorStore`` over the same mesh, and one
  ``tree_all_reduce`` of known values;
- **serve** — one ``PagedGeneratorActor`` per chip, built by the replica
  worker's own factory, placed by ``LocalLauncher``, fronted by
  ``InferenceGateway`` over the real socket codec;
- **kernels** — every Pallas kernel in ``ptype_tpu/ops`` against its
  float32 ``jnp`` reference on the chip, plus the MoE and ragged-generate
  lowerings.

The first stdout line is a JSON description of the installation; without
a TPU the script exits non-zero right after it. Each phase prints one
named line; a failed check raises, so a phase cannot fail while the run
exits 0. The last stdout line is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

What the phases print along the way (step time, peak memory, MFU) is
smoke output, not a benchmark. ``tests/test_chip_smoke.py`` runs every
phase at tiny sizes on the CPU mesh with interpreted kernels, so chip
time is not spent on typos.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys
import threading
import time
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))
TRAINER_CONFIG = os.path.join(REPO, "examples", "optimus", "trainer.yaml")

PRESET = "optimus-125m"
SEQ = 1024
PER_CHIP_BATCH = 16
#: Flash fwd+bwd shapes (name, B, S, H, K, Dh): the train shape, long
#: context with GQA, and the half-lane head width.
FLASH_SHAPES = (
    ("train", 16, 1024, 6, 6, 128),
    ("s8192-gqa", 1, 8192, 8, 2, 128),
    ("dh64", 2, 1024, 8, 2, 64),
)


def check(cond, msg: str) -> None:
    """A smoke assertion (``assert`` would vanish under ``-O``)."""
    if not cond:
        raise AssertionError(msg)


def say(phase: str, result: dict) -> None:
    print(f"chip_smoke {phase}: ok {json.dumps(result, sort_keys=True)}",
          flush=True)


# ------------------------------------------------------------ the module


_LOWERED_CALL = re.compile(
    r'@tpu_custom_call\(.*?kernel_name = "(\w+)".*?: \(tensor<([0-9x]+)x\w+>',
    re.S)
_COMPILED_CALL = re.compile(
    r'custom_call_target="tpu_custom_call", '
    r'operand_layout_constraints=\{\w+\[([\d,]*)\]')
_SCOPED_VMEM = re.compile(
    r'custom_call_target="tpu_custom_call",[^\n]*'
    r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
    r'"offset":"0","size":"(\d+)"')


def lowered_kernels(stablehlo: str) -> list[tuple[str, tuple[int, ...]]]:
    """(kernel name, first operand's dims) of every Mosaic custom call
    in a module lowered for TPU. Inside a ``shard_map`` the dims are the
    per-device ones. An interpreted kernel leaves no custom call."""
    return [(name, tuple(int(d) for d in dims.split("x")))
            for name, dims in _LOWERED_CALL.findall(stablehlo)]


def compiled_kernel_operands(hlo: str) -> list[tuple[int, ...]]:
    """First operand's dims of every Mosaic custom call in a COMPILED
    (post-partitioning, per-device) module — where it shows whether the
    SPMD partitioner handed the kernel the local shard or gathered the
    global batch onto every chip."""
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in _COMPILED_CALL.findall(hlo)]


def scoped_vmem_bytes(hlo: str) -> list[int]:
    """Scoped VMEM each Mosaic custom call of a compiled module uses."""
    return [int(n) for n in _SCOPED_VMEM.findall(hlo)]


def check_kernels(jitted, args, names, batch: int) -> dict:
    """Lower ``jitted(*args)`` and prove the named Pallas kernels are in
    it — compiled, not interpreted — and that each sees ``batch`` rows
    (dim 0 of its first operand): in the lowered module and, after
    partitioning, in the compiled per-device one. Off the chip there is
    nothing to read: kernels run interpreted there (the CPU tests)."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    lowered = jitted.lower(*args)
    seen = lowered_kernels(lowered.as_text())
    for name in names:
        dims = [d for n, d in seen if n == name]
        check(dims, f"kernel {name} is not in the lowered module "
                    f"(interpreted or substituted); have {seen}")
        check(all(d[0] == batch for d in dims),
              f"kernel {name} lowered at {dims}, want {batch} rows")
    hlo = lowered.compile().as_text()
    operands = compiled_kernel_operands(hlo)
    check(len(operands) >= len(names),
          f"compiled module holds {len(operands)} Mosaic custom calls, "
          f"want >= {len(names)}")
    check(all(o[0] == batch for o in operands),
          f"compiled custom-call operands {operands}: every call must "
          f"see {batch} rows (the per-device shard)")
    return {"custom_calls": len(operands),
            "operand0": sorted(set(operands)),
            "scoped_vmem_bytes": sorted(set(scoped_vmem_bytes(hlo)))}


# ------------------------------------------------------------------ train


def _fixed_batch(model_cfg, batch: int, seq: int) -> dict:
    from ptype_tpu.train.data import synthetic_batches

    return next(synthetic_batches(model_cfg.vocab_size, batch, seq))


def phase_train(cluster, model_cfg, *, seq: int = SEQ,
                per_chip_batch: int = PER_CHIP_BATCH, steps: int = 6
                ) -> dict:
    """The GSPMD trainer, built the way examples/optimus/trainer.py
    builds it. One fixed batch is stepped repeatedly, so the loss must
    fall. Returns its findings; ``losses`` (the first two steps') is
    what train-store compares against."""
    import jax

    from ptype_tpu.metrics import device_peak_tflops, mfu
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.ops.flash_attention import KERNEL_NAMES
    from ptype_tpu.train.trainer import Trainer, default_optimizer

    mesh = cluster.mesh()
    n_dev = jax.device_count()
    check(mesh.devices.size == n_dev,
          f"mesh covers {mesh.devices.size} of {n_dev} devices")
    # The example's $WARMUP knob at 0: the learning rate is live from
    # the first update, so a handful of steps moves the loss.
    trainer = Trainer(model_cfg, mesh,
                      optimizer=default_optimizer(warmup=0), sync_every=0)
    batch = _fixed_batch(model_cfg, per_chip_batch * n_dev, seq)
    sharded = trainer.shard_batch(batch)

    shards = sharded["tokens"].addressable_shards
    check(len({s.device for s in shards}) == n_dev
          and all(s.data.shape == (per_chip_batch, seq) for s in shards),
          f"batch shards {[(s.device, s.data.shape) for s in shards]}: "
          f"want one ({per_chip_batch}, {seq}) shard on each of {n_dev} "
          "devices")
    param_devs = set()
    for leaf in jax.tree.leaves(trainer.state.params):
        param_devs |= leaf.devices()
    check(param_devs == set(mesh.devices.flat),
          f"params live on {len(param_devs)} of {n_dev} devices")

    module = check_kernels(trainer.train_step, (trainer.state, sharded),
                           KERNEL_NAMES, per_chip_batch)

    t0 = time.perf_counter()
    losses = [float(trainer.step(batch)["loss"])]
    first_step_s = time.perf_counter() - t0
    losses.append(float(trainer.step(batch)["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = trainer.step(batch)
    jax.block_until_ready(out["loss"])
    window_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    last = float(out["loss"])
    readback_s = time.perf_counter() - t0
    # block_until_ready drains the device queue: had it not, this
    # readback would have waited out the whole window of queued steps.
    check(readback_s < 0.25 * window_s,
          f"float(loss) took {readback_s:.4f}s after block_until_ready "
          f"({window_s:.4f}s window): the queue was not drained")
    check(all(math.isfinite(x) for x in losses + [last]),
          f"non-finite loss: {losses + [last]}")
    check(last < losses[0],
          f"loss did not fall: {losses[0]} -> {last} over {steps + 2} steps")

    trainer.sync()
    tokens_per_s = steps * per_chip_batch * n_dev * seq / window_s
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "devices": n_dev, "batch": per_chip_batch * n_dev, "seq": seq,
        "params_m": round(trainer.n_params / 1e6, 1),
        "loss_first": round(losses[0], 4), "loss_last": round(last, 4),
        "losses": losses, "module": module,
        "smoke_first_step_s": round(first_step_s, 2),
        "smoke_step_ms": round(window_s / steps * 1e3, 2),
        "smoke_mfu_window": round(mfu(
            tokens_per_s, tfm.flops_per_token(model_cfg, seq), n_dev,
            device_peak_tflops(jax.devices()[0])), 4),
        "smoke_mfu_trainer_cumulative": round(
            trainer.throughput()["mfu"], 4),
        "readback_after_block_ms": round(readback_s * 1e3, 3),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def phase_train_store(cluster, model_cfg, gspmd_losses, *, seq: int = SEQ,
                      per_chip_batch: int = PER_CHIP_BATCH) -> dict:
    """The north star: Store push/pull IS the allreduce. Two store-DP
    steps on the batch the GSPMD trainer stepped, from the same seed —
    the losses must agree to bf16 tolerance — and one bucketed
    ``tree_all_reduce`` of known values against the host sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ptype_tpu.ops.flash_attention import KERNEL_NAMES
    from ptype_tpu.parallel.collectives import tree_all_reduce
    from ptype_tpu.parallel.tensorstore import TensorStore
    from ptype_tpu.train.store_dp import StoreDPTrainer
    from ptype_tpu.train.trainer import default_optimizer

    mesh = cluster.mesh()
    n_dev = jax.device_count()
    store = TensorStore(mesh, kv=cluster.store)
    trainer = StoreDPTrainer(model_cfg, store,
                             optimizer=default_optimizer(warmup=0))
    batch = _fixed_batch(model_cfg, per_chip_batch * n_dev, seq)
    outs = [trainer.step(batch), trainer.step(batch)]
    losses = [float(o["loss"]) for o in outs]
    for got, want in zip(losses, gspmd_losses):
        check(math.isfinite(got) and abs(got - want) <= 2e-2,
              f"store-DP losses {losses} vs GSPMD {gspmd_losses[:2]}")
    check(outs[1]["grad_epoch"] > outs[0]["grad_epoch"],
          f"grad epochs did not advance: {outs}")

    stacked_batch = {
        k: jax.device_put(
            jnp.reshape(v, (n_dev, per_chip_batch, -1)),
            NamedSharding(mesh, P(store.axis, None, None)))
        for k, v in batch.items()}
    module = check_kernels(
        trainer.grads_step, (trainer.params(), stacked_batch),
        KERNEL_NAMES, per_chip_batch)

    # Known values: worker w contributes (w + 1) * base, so the sum is
    # n(n+1)/2 * base exactly (small integers, exact in f32 and bf16).
    host = {"a": np.arange(6000, dtype=np.float32).reshape(60, 100) % 7,
            "b": np.arange(96, dtype=np.float32).reshape(2, 48) % 5}
    scale = np.arange(1, n_dev + 1, dtype=np.float32)
    stacked = {
        "a": host["a"][None] * scale[:, None, None],
        "b": (host["b"][None] * scale[:, None, None]).astype(jnp.bfloat16),
    }
    placed = {k: jax.device_put(
        v, NamedSharding(mesh, P(store.axis, None, None)))
        for k, v in stacked.items()}
    reduced = tree_all_reduce(placed, mesh, store.axis, "sum")
    total = n_dev * (n_dev + 1) / 2
    for k in host:
        got = np.asarray(reduced[k].astype(jnp.float32))
        check(np.array_equal(got, host[k] * total),
              f"tree_all_reduce[{k}] differs from the host sum")
    return {"losses": [round(x, 4) for x in losses],
            "gspmd_losses": [round(x, 4) for x in gspmd_losses[:2]],
            "grad_epoch": outs[1]["grad_epoch"], "module": module,
            "tree_all_reduce": "exact"}


# ------------------------------------------------------------------ serve


def _greedy_slack(ref_logits, tokens) -> float:
    """How far below the reference's best logit each emitted token sits,
    worst case. ``ref_logits`` (n, V) are the float32 reference's
    logits at the positions that predicted ``tokens`` (n,)."""
    import numpy as np

    ref = np.asarray(ref_logits, np.float32)
    return float(np.max(ref.max(axis=-1)
                        - ref[np.arange(len(tokens)), tokens]))


def phase_serve(cluster, preset: str, *, max_new: int = 8,
                prefix_len: int = 96, long_len: int = 300,
                devices=None) -> dict:
    """One paged replica per device (default: every device) behind the
    gateway, over real sockets. Greedy output is checked two ways:
    token-for-token against ``generate()`` on the same params
    (reported), and — the pass bar, robust to a bf16 near-tie — every
    emitted token must sit within a hair of the float32 reference's best
    logit when the reference is fed the engine's own prefix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ptype_tpu import actor as actor_mod
    from ptype_tpu import jitwatch
    from ptype_tpu.gateway.frontdoor import GatewayConfig, InferenceGateway
    from ptype_tpu.models import generate as gen
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.reconciler.replica import LocalLauncher
    from ptype_tpu.reconciler.worker import _actor_factory
    from ptype_tpu.serve_engine.blocks import prefix_affinity_key

    devices = list(devices or jax.devices())
    n_dev = len(devices)
    model_cfg = tfm.preset(preset)
    make, warmup = _actor_factory("paged", preset)
    launcher = LocalLauncher(cluster.registry, make, warmup=warmup,
                             service="llm", devices=devices)
    gw = None
    try:
        t0 = time.perf_counter()
        for i in range(n_dev):
            launcher.spawn(f"chip-smoke-replica-{i}")
        spawn_s = time.perf_counter() - t0
        actors = [h.actor for h in launcher.hosts]
        bt = actors[0].block_tokens

        # The in-process fast path skips the codec (and bf16 leaves once
        # failed only on the wire): force every dial onto the socket.
        with mock.patch.object(actor_mod, "lookup_local",
                               lambda addr, port: None):
            gw = InferenceGateway(
                cluster.registry, "llm",
                GatewayConfig(probe_interval_s=0.3,
                              per_replica_inflight=4,
                              default_deadline_s=600.0))
            deadline = time.monotonic() + 60
            while gw.pool.n_healthy() < n_dev:
                check(time.monotonic() < deadline,
                      f"{gw.pool.n_healthy()} of {n_dev} replicas healthy")
                time.sleep(0.05)

            rng = np.random.default_rng(0)
            vocab = model_cfg.vocab_size
            shared = rng.integers(1, vocab, prefix_len)
            shorts = [rng.integers(1, vocab, 12) for _ in range(n_dev)]
            family = [np.concatenate([shared, rng.integers(1, vocab, 4)])
                      for _ in range(4)]
            prompts = shorts + family + [rng.integers(1, vocab, long_len)]
            family_key = prefix_affinity_key(shared, bt)
            outs: list = [None] * len(prompts)
            errs: list = []

            def ask(idxs, key=None):
                try:
                    for i in idxs:
                        out = gw.generate(
                            jnp.asarray(prompts[i], jnp.int32)[None],
                            max_new, affinity_key=key)
                        outs[i] = np.asarray(out)[0]
                except Exception as e:  # noqa: BLE001 — re-raised below
                    errs.append(e)

            # The fleet is warm: from here on the engines' programs
            # (the decode step, the prefill chunks) must compile
            # nothing. The repo's own recompile watchdog keeps the
            # books (an already-armed one is left as found).
            armed_here = jitwatch.active() is None
            watch = (jitwatch.enable(transfer_level="off") if armed_here
                     else jitwatch.active())
            try:
                watch.mark_steady()
                t0 = time.perf_counter()
                # One short request after another: each goes to a
                # replica the gateway has no latency for yet, so every
                # replica serves one — and affinity, which yields to a
                # replica that looks idle-and-instant, holds from here.
                ask(range(n_dev))
                # Then the shared-prefix family in order on its
                # affinity key (the first member makes the prefix
                # resident, the rest hit it) while the long prompt
                # prefills alongside.
                threads = [
                    threading.Thread(target=ask, args=(
                        range(n_dev, n_dev + 4), family_key)),
                    threading.Thread(target=ask, args=([n_dev + 4],))]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=900)
                serve_s = time.perf_counter() - t0
                in_window = watch.recompiles_since_steady()
            finally:
                if armed_here:
                    jitwatch.disable()
            if errs:
                raise errs[0]
            check(not {"engine_step", "prefill_chunk"} & set(in_window),
                  "the engine compiled inside the serving window, after "
                  f"warm-up: {in_window}")
            check(all(o is not None and o.shape == (max_new,)
                      for o in outs),
                  f"missing or misshapen outputs: {outs}")
            time.sleep(1.0)  # one probe round: snapshots carry Info()
            pool_status = gw.pool.status()

        # ---- placement: one replica per device, state and all.
        homes = []
        for a in actors:
            devs = a.pool.k.devices() | a.pool.v.devices()
            for leaf in jax.tree.leaves(a.params):
                devs |= leaf.devices()
            check(len(devs) == 1,
                  f"a replica's params and banks span {devs}")
            homes.append(next(iter(devs)))
        check(len(set(homes)) == n_dev,
              f"{n_dev} replicas live on {len(set(homes))} devices: "
              f"{homes}")

        # ---- correctness against the references, on replica 0's params.
        params = actors[0].params
        ref_cfg = tfm.preset(preset, dtype=jnp.float32, attn_impl="xla")
        fwd = jax.jit(lambda p, t: tfm.forward(p, t, ref_cfg))
        by_len: dict[int, list[int]] = {}
        for i, p in enumerate(prompts):
            by_len.setdefault(len(p), []).append(i)
        worst_slack, exact = 0.0, 0
        for L, idxs in by_len.items():
            batch = jnp.asarray(np.stack([prompts[i] for i in idxs]),
                                jnp.int32)
            solo = np.asarray(gen.generate(params, model_cfg, batch,
                                           max_new))
            seqs = jnp.asarray(np.stack(
                [np.concatenate([prompts[i], outs[i]]) for i in idxs]),
                jnp.int32)
            logits = np.asarray(fwd(params, seqs))
            for row, i in enumerate(idxs):
                exact += int(np.array_equal(solo[row], outs[i]))
                worst_slack = max(worst_slack, _greedy_slack(
                    logits[row, L - 1:L - 1 + max_new], outs[i]))
        # Logits here have std ~0.55 and a wrong token sits ~4 std below
        # the best one; bf16 noise between two correct lowerings is
        # ~1e-2. 0.05 tells them apart with room on both sides.
        check(worst_slack <= 0.05,
              f"an emitted token sits {worst_slack:.4f} below the float32 "
              "reference's best logit: the engine is not decoding "
              "greedily from these params")

        infos = [a.Info() for a in actors]
        hits = sum(i["prefix_hits"] for i in infos)
        check(hits > 0 and max(i["prefix_hit_rate"] for i in infos) > 0,
              f"no prefix reuse: {[i['prefix_hit_rate'] for i in infos]}")
        check(max(i["prefill_chunks"] for i in infos) >= 3,
              "the long prompt did not cross several prefill chunks")
        served = [i for i in infos if i["requests_retired"] > 0]
        check(served and all(i["ttft_p99_ms"] > 0 and i["tpot_p50_ms"] > 0
                             for i in served),
              f"TTFT/TPOT stamps missing: {infos}")
        snaps = pool_status["replicas"]
        check(len(snaps) == n_dev and all("kv_free_blocks" in s
                                          for s in snaps),
              f"gateway snapshots lack the engine's Info(): {snaps}")
        return {
            "replicas": n_dev, "devices": [str(d) for d in homes],
            "requests": len(prompts), "max_new": max_new,
            "exact_vs_generate": f"{exact}/{len(prompts)}",
            "worst_greedy_slack": round(worst_slack, 5),
            "prefix_hits": hits,
            "calls": [i["calls"] for i in infos],
            "compiles_in_window": in_window,
            "smoke_spawn_s": round(spawn_s, 1),
            "smoke_serve_s": round(serve_s, 1),
            "smoke_ttft_p99_ms": max(i["ttft_p99_ms"] for i in infos),
            "smoke_tpot_p50_ms": max(i["tpot_p50_ms"] for i in infos),
        }
    finally:
        if gw is not None:
            gw.close()
        launcher.close()


# ---------------------------------------------------------------- kernels


def _ref_attention(q, k, v):
    """Causal GQA attention in float32, highest precision — the
    reference the flash kernel answers to."""
    import jax
    import jax.numpy as jnp

    B, S, H, Dh = q.shape
    K = k.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    qg = q.reshape(B, S, K, H // K, Dh)
    s = jnp.einsum("bqngd,bsnd->bngqs", qg, k,
                   precision="highest") / math.sqrt(Dh)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngqs,bsnd->bqngd", p, v,
                      precision="highest").reshape(B, S, H, Dh)


def _close(got, want, what: str, rel: float = 2e-2) -> float:
    """max|got - want| within ``rel`` of the reference's own scale."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    check(math.isfinite(err) and err <= rel * scale,
          f"{what}: max abs err {err:.5f} vs reference scale {scale:.3f}")
    return err


def _flash_case(name, B, S, H, K, Dh, **blocks) -> dict:
    import jax
    import jax.numpy as jnp

    from ptype_tpu.ops.flash_attention import (KERNEL_NAMES,
                                               flash_attention)

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, Dh), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, H, Dh), jnp.float32)

    def objective(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w), o

        return jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))

    flash = objective(
        lambda q, k, v: flash_attention(q, k, v, **blocks))
    ref = objective(_ref_attention)
    check_kernels(flash, (q, k, v), KERNEL_NAMES, B)
    (_, o), grads = flash(q, k, v)
    (_, o_ref), grads_ref = ref(q, k, v)
    errs = {"o": _close(o, o_ref, f"flash {name} output")}
    for n, g, g_ref in zip("qkv", grads, grads_ref):
        errs["d" + n] = _close(g, g_ref, f"flash {name} d{n}")
    return {k_: round(e, 5) for k_, e in errs.items()}


def _latent_block_case(H, D, Dv, bt, lanes, row_blocks, tile) -> float:
    """``ops.latent_block_attention`` over rows of unequal length (one
    lane idle, one row's last tile mostly padding) against a float32
    softmax over each row's own positions; on the chip, compiled and
    not interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ptype_tpu.models import generate as gen
    from ptype_tpu.ops.latent_block_attention import (
        KERNEL_NAME, latent_block_attention)

    dt = jnp.bfloat16
    n_blocks = lanes * row_blocks + 1
    bank = jax.random.normal(jax.random.PRNGKey(8), (n_blocks, bt, D), dt)
    q = jax.random.normal(jax.random.PRNGKey(9), (lanes, H, D), dt)
    rng = np.random.default_rng(2)
    ctx = rng.integers(bt, row_blocks * bt, lanes)
    ctx[0], ctx[-1] = 0, min(tile * bt + 1, row_blocks * bt)
    tables = (rng.permutation(n_blocks - 1)[:lanes * row_blocks]
              .reshape(lanes, row_blocks) + 1).astype(np.int32)
    lst, n = gen.live_block_list(tables, -(-ctx // bt), ctx > 0, bt,
                                 tile=tile, own_tiles=True)
    kernel = jax.jit(lambda q, bank, lst, n, limits: latent_block_attention(
        q, bank, 0, (lst, n), limits, scale=D ** -0.5, v_dim=Dv))
    args = (q, bank, jnp.asarray(lst), jnp.asarray(n),
            jnp.asarray(np.maximum(ctx, 1), jnp.int32))
    if jax.default_backend() == "tpu":
        seen = lowered_kernels(kernel.lower(*args).as_text())
        check([k for k, _ in seen] == [KERNEL_NAME],
              f"kernel {KERNEL_NAME} is not in the lowered module "
              f"(interpreted or substituted); have {seen}")
    got = np.asarray(kernel(*args), np.float32)
    rows = np.asarray(bank, np.float32)[tables].reshape(lanes, -1, D)
    s = np.einsum("bhd,bsd->bhs", np.asarray(q, np.float32), rows) \
        * D ** -0.5
    keys = np.arange(rows.shape[1])[None, None] < ctx[:, None, None]
    s = np.where(keys, s, -1e30)
    p = np.where(keys, np.exp(s - s.max(-1, keepdims=True)), 0.0)
    want = np.einsum("bhs,bsd->bhd",
                     p / np.maximum(p.sum(-1, keepdims=True), 1e-30),
                     rows[..., :Dv])
    check(bool((got[0] == 0).all()), "latent block kernel: an idle lane "
                                     "must read zeros")
    return _close(got, want, "latent block kernel output")


#: ``ops.latent_block_attention`` at A.X-K1's widths (H, D, Dv, block
#: tokens, lanes, blocks a row, list tile).
LATENT_SHAPE = (64, 640, 512, 16, 8, 300, 256)


def phase_kernels(cluster, *, flash_shapes=FLASH_SHAPES,
                  flash_blocks: dict | None = None,
                  width_preset: str = PRESET, prefill_len: int = 200,
                  latent_shape=LATENT_SHAPE) -> dict:
    """Every Pallas kernel in ``ptype_tpu/ops`` against its float32
    reference, and the lowerings no TPU compiler had seen: the flash
    prefill (an unaligned prompt, so the pad path runs), the engine's
    decode step over the live rows' block list, the MoE train step,
    ragged and MoE generate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ptype_tpu.models import generate as gen
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.ops.flash_attention import KERNEL_NAMES
    from ptype_tpu.serve_engine.engine import PagedGeneratorActor
    from ptype_tpu.train.trainer import Trainer

    out: dict = {"flash": {}}
    for name, B, S, H, K, Dh in flash_shapes:
        out["flash"][name] = _flash_case(name, B, S, H, K, Dh,
                                         **(flash_blocks or {}))

    out["latent_block_err"] = round(_latent_block_case(*latent_shape), 5)

    # The model's full width at cut depth: prefill, kernel path against
    # the dense path, and the paged decode step, block list against the
    # tables, on one set of params.
    wide = tfm.preset(width_preset, n_layers=2)
    params = jax.jit(lambda r: tfm.init_params(r, wide))(
        jax.random.PRNGKey(2))
    Kh, Dh = wide.kv_heads, wide.head_dim

    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, prefill_len),
                                0, wide.vocab_size, jnp.int32)
    reach = -(-prefill_len // 128) * 128

    def prefill(impl):
        cfg = tfm.preset(width_preset, n_layers=2, attn_impl=impl)
        return jax.jit(lambda p, t: gen.prefill(
            p, t, cfg, gen.init_cache(cfg, 2, max_seq=reach))[0])

    check_kernels(prefill("flash"), (params, prompt), KERNEL_NAMES[:1], 2)
    out["flash_prefill_err"] = round(_close(
        prefill("flash")(params, prompt), prefill("xla")(params, prompt),
        f"flash prefill logits at S={prefill_len}"), 5)

    # The serve phase's shape: what PagedGeneratorActor(preset) builds.
    n_slots, bt = 8, 16
    nb = wide.max_seq // bt
    n_blocks = n_slots * nb + 1
    kb = jax.random.normal(
        jax.random.PRNGKey(4), (2, n_blocks, bt, Kh, Dh), wide.dtype)
    vb = jax.random.normal(
        jax.random.PRNGKey(5), (2, n_blocks, bt, Kh, Dh), wide.dtype)
    rng = np.random.default_rng(1)
    tables = jnp.asarray(
        rng.permutation(n_blocks - 1)[:n_slots * nb].reshape(n_slots, nb)
        + 1, jnp.int32)
    pos = jnp.asarray(rng.integers(1, nb * bt, n_slots), jnp.int32)
    tok = jnp.asarray(rng.integers(0, wide.vocab_size, n_slots), jnp.int32)
    wr_b = tables[jnp.arange(n_slots), pos // bt]

    # The engine's step: over the live rows' block list, against the
    # same step through the tables.
    blocks = gen.live_block_list(
        np.asarray(tables), np.asarray(pos) // bt + 1,
        np.ones(n_slots, bool), bt)
    decode = jax.jit(lambda p, kb, vb, blocks: gen.decode_step_banks(
        p, tok, pos, wide, {"k": kb, "v": vb}, tables, wr_b, pos % bt,
        live_list=blocks)[0])
    out["paged_decode_blocks_err"] = round(_close(
        decode(params, kb, vb, blocks), decode(params, kb, vb, None),
        "decode_step_banks over the block list vs the tables, logits"),
        5)

    # Lowerings with no Pallas in them that no TPU compiler had seen.
    moe = tfm.preset("tiny-moe", attn_impl="xla")
    step = Trainer(moe, cluster.mesh(), sync_every=1).step(
        _fixed_batch(moe, 4 * jax.device_count(), 64))
    check(math.isfinite(float(step["loss"])), "MoE train step: bad loss")

    # Parity on tokens needs numerics that cannot flip an argmax:
    # float32 at the highest matmul precision — process-wide, because
    # the engine traces its programs on its own thread.
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        tiny = tfm.preset("tiny", dtype=jnp.float32, attn_impl="xla")
        tparams = jax.jit(lambda r: tfm.init_params(r, tiny))(
            jax.random.PRNGKey(6))
        ragged = jnp.zeros((3, 8), jnp.int32).at[0, 3:].set(7) \
            .at[1, :].set(5).at[2, 6:].set(9)
        lens = jnp.array([5, 8, 2], jnp.int32)
        batched = gen.generate(tparams, tiny, ragged, 4, prompt_lens=lens)
        for i in range(3):
            solo = gen.generate(tparams, tiny,
                                ragged[i:i + 1, 8 - int(lens[i]):], 4)
            check(bool(jnp.all(batched[i] == solo[0])),
                  f"ragged generate row {i} diverges from its solo decode")
        actor = PagedGeneratorActor(tiny, params=tparams, n_slots=2)
        try:
            p0 = jnp.zeros((1, 5), jnp.int32).at[0, 2:].set(4)
            check(bool(jnp.all(jnp.asarray(np.asarray(
                actor.Generate(p0, 4))) == gen.generate(tparams, tiny, p0,
                                                        4))),
                  "paged engine diverges from solo decode")
        finally:
            actor.close()
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    mparams = jax.jit(lambda r: tfm.init_params(r, moe))(
        jax.random.PRNGKey(7))
    toks = gen.generate(mparams, moe, jnp.zeros((2, 8), jnp.int32), 4)
    check(toks.shape == (2, 4), f"MoE generate: bad shape {toks.shape}")
    out["lowerings"] = ["moe-train-step", "ragged-generate",
                        "paged-engine-f32", "moe-generate"]
    return out


# ------------------------------------------------------------------- main


def device_info() -> dict:
    """The installation, as the first stdout line reports it."""
    import jax
    import jaxlib

    from ptype_tpu import native

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
            "native_wire": native.available()}


def main() -> int:
    t_start = time.perf_counter()
    info = device_info()
    print(json.dumps(info), flush=True)
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform="
              f"{info['platform']!r}); nothing was run", file=sys.stderr)
        return 3

    import jax

    from ptype_tpu import compile_cache, join
    from ptype_tpu.config import config_from_file
    from ptype_tpu.models import transformer as tfm

    cache_dir = compile_cache.configure()
    cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0,
                    "cache_misses": 0}

    def on_event(event: str, **_kw) -> None:
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") \
                and key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    cluster = join(config_from_file(TRAINER_CONFIG))
    try:
        model_cfg = tfm.preset(PRESET)
        train = phase_train(cluster, model_cfg)
        say("train", {k: v for k, v in train.items() if k != "losses"})
        if jax.device_count() > 1:
            say("train-store", phase_train_store(cluster, model_cfg,
                                                 train["losses"]))
        say("serve", phase_serve(cluster, PRESET))
        say("kernels", phase_kernels(cluster))
    finally:
        cluster.close()
    # cache_misses counts entries WRITTEN; a compile under JAX's
    # one-second persistence floor is requested but never stored.
    say("compile-cache", {"dir": cache_dir, **cache_events,
                          "wall_s": round(time.perf_counter() - t_start,
                                          1)})
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Optimus trainer — the north-star app (BASELINE.json: "example/optimus
trains a 125M-param transformer ... using Store-backed ICI allreduce").

Where the reference's optimus fanned prime-check chunks over a worker
pool (coordinator.go:67-99), this fans a token batch over the device
mesh: join the cluster, build the mesh from the platform config's axes
(every visible device on ``data`` when the config names none), and
train. Three modes:

- ``gspmd`` (default): the fully-compiled train step (train/trainer.py) —
  the throughput path; collectives inserted by sharding annotations.
- ``store``: Store-backed DP (train/store_dp.py) — push/pull IS the
  gradient exchange, epochs observable.
- ``async``: param-server mode (train/param_server.py) — un-barriered
  push/pull.

Env knobs: PRESET (optimus-125m), STEPS, BATCH, SEQ, MODE,
LR/WARMUP/WEIGHT_DECAY/DECAY_STEPS (optimizer), METRICS_PATH (JSONL sink),
COMPRESS (store mode: bf16|int8 gradient-push wire compression),
ZERO=1 (store mode: ZeRO-1 sharded weight update — reduce-scatter
grads, shard-local AdamW with 1/N moments per replica, allgather
params; sharded checkpoints reshard on restore),
SHARD_UPDATE=1 (gspmd mode: ZeRO-1 weight-update sharding — Adam
moments shard over the data axis, 1/N optimizer HBM, same math).
"""

from __future__ import annotations

import os

from ptype_tpu import compile_cache
from ptype_tpu.cluster import join
from ptype_tpu.config import config_from_env
from ptype_tpu.models import transformer as tfm
from ptype_tpu.train.data import synthetic_batches


def main() -> None:
    compile_cache.configure()
    cfg = config_from_env()

    # Optimizer knobs ($LR/$WARMUP/$WEIGHT_DECAY/$DECAY_STEPS) and a
    # JSONL metrics sink ($METRICS_PATH — tail-able, one line per log
    # interval) for real runs.
    from ptype_tpu.train.trainer import default_optimizer

    optimizer = default_optimizer(
        lr=float(os.environ.get("LR", "3e-4")),
        weight_decay=float(os.environ.get("WEIGHT_DECAY", "0.1")),
        warmup=int(os.environ.get("WARMUP", "100")),
        decay_steps=int(os.environ.get("DECAY_STEPS", "100000")),
    )
    mw = None
    if os.environ.get("METRICS_PATH"):
        from ptype_tpu.metrics import MetricsWriter

        mw = MetricsWriter(os.environ["METRICS_PATH"])

    cluster = join(cfg)
    mode = os.environ.get("MODE", "gspmd")
    preset = os.environ.get("PRESET", "optimus-125m")
    steps = int(os.environ.get("STEPS", "50"))
    seq = int(os.environ.get("SEQ", "1024"))

    model_cfg = tfm.preset(preset)
    mesh = cluster.mesh()
    n_dev = mesh.devices.size
    batch = int(os.environ.get("BATCH", str(8 * n_dev)))
    stream = synthetic_batches(model_cfg.vocab_size, batch, seq)
    print(f"optimus[{mode}] {preset} on {n_dev} devices, "
          f"batch={batch} seq={seq}", flush=True)

    try:
        if mode == "gspmd":
            from ptype_tpu.train.trainer import Trainer

            # SHARD_UPDATE=1: ZeRO-1 cross-replica weight-update
            # sharding — Adam moments shard over the data axis (1/N
            # optimizer HBM), params stay replicated, same math.
            trainer = Trainer(
                model_cfg, mesh, optimizer=optimizer,
                shard_update=os.environ.get("SHARD_UPDATE") == "1")
            print(f"params: {trainer.n_params/1e6:.1f}M", flush=True)
            # CKPT_DIR enables save/resume: restart the process with the
            # same dir and training continues from the latest complete
            # step (reshard-on-restore: the mesh may have changed).
            ckpt_dir = os.environ.get("CKPT_DIR")
            ckpt_every = int(os.environ.get("CKPT_EVERY", "50"))
            ck = None
            if ckpt_dir:
                from ptype_tpu.checkpoint import Checkpointer

                ck = Checkpointer(ckpt_dir)
                latest = ck.latest_step()
                if latest is not None:
                    trainer.state = ck.restore(
                        trainer.state, step=latest,
                        shardings=trainer.state_shardings)
                    print(f"resumed from step {latest}", flush=True)
            for i in range(steps):
                out = trainer.step(next(stream))
                if i % 10 == 0 or i == steps - 1:
                    print(f"step {out['step']:5d} loss {out['loss']:.4f} "
                          f"tok/s/chip {out['tokens_per_sec_per_chip']:.0f} "
                          f"mfu {out['mfu']:.3f}", flush=True)
                    if mw is not None:
                        mw.emit(int(out["step"]), loss=out["loss"],
                                grad_norm=out["grad_norm"],
                                tokens_per_sec_per_chip=out[
                                    "tokens_per_sec_per_chip"],
                                mfu=out["mfu"])
                if (ck is not None and ckpt_every
                        and (i + 1) % ckpt_every == 0):
                    trainer.sync()
                    # async: the snapshot is copied out with
                    # backpressure and written off-thread; training
                    # continues while the bytes land.
                    ck.async_save(int(out["step"]), trainer.state)
            if ck is not None:
                trainer.sync()
                # Drain any in-flight async save BEFORE consulting
                # latest_step(): an uncommitted final-step save would
                # otherwise be re-serialized (and in multi-controller
                # runs, processes would disagree and strand the
                # manifest barrier).
                ck.wait()
                final = int(trainer.state.step)
                if ck.latest_step() != final:
                    ck.save(final, trainer.state)
                print(f"checkpointed step {final}", flush=True)
        elif mode == "store":
            from ptype_tpu.parallel.tensorstore import TensorStore
            from ptype_tpu.train.store_dp import StoreDPTrainer

            # COMPRESS=bf16|int8 compresses the gradient push wire
            # (tensorstore.py compression hooks; int8 = the EQuARX
            # two-phase quantized allreduce).
            store = TensorStore(mesh, kv=cluster.store,
                                compress=os.environ.get("COMPRESS")
                                or None)
            # ZERO=1: ZeRO-1 sharded weight update (parallel/zero.py)
            # — gradients reduce-scatter, AdamW applies shard-locally
            # (1/N moments per replica), params allgather back. The
            # same LR/WARMUP/... knobs feed the shard-local recipe
            # through OptHParams.
            zero = os.environ.get("ZERO") == "1"
            if zero:
                from ptype_tpu.train.trainer import \
                    default_optimizer_hparams

                trainer = StoreDPTrainer(
                    model_cfg, store, zero=True,
                    zero_hparams=default_optimizer_hparams(
                        lr=float(os.environ.get("LR", "3e-4")),
                        weight_decay=float(
                            os.environ.get("WEIGHT_DECAY", "0.1")),
                        warmup=int(os.environ.get("WARMUP", "100")),
                        decay_steps=int(
                            os.environ.get("DECAY_STEPS", "100000"))))
            else:
                trainer = StoreDPTrainer(model_cfg, store,
                                         optimizer=optimizer)
            # CKPT_DIR persists the Store's parameter space (the
            # durability etcd's data-dir gave the reference Store).
            # Resume restores params INTO the store after the trainer
            # seeded it — optimizer moments restart, the Store-tier
            # "resume = join + Store pull" semantic (SURVEY.md §5).
            sc = zc = None
            ckpt_every = int(os.environ.get("CKPT_EVERY", "50"))
            if os.environ.get("CKPT_DIR"):
                from ptype_tpu.checkpoint import StoreCheckpoint

                # params/ only: the store also holds transient grads/*
                # whose bytes equal the params' — don't double saves.
                sc = StoreCheckpoint(store, os.environ["CKPT_DIR"],
                                     keys_prefix="params/")
                if zero:
                    from ptype_tpu.checkpoint import ZeroCheckpoint

                    # Sharded moments alongside the params: per-replica
                    # crc32 shards + the plan manifest, reshardable if
                    # the device count changed since the save.
                    zc = ZeroCheckpoint(os.path.join(
                        os.environ["CKPT_DIR"], "zero_opt"))
                # Probe emptiness explicitly so a CORRUPT checkpoint
                # still fails loudly instead of silently restarting
                # from step 0.
                resumed_step = sc.latest_step()
                if resumed_step is not None:
                    restored = sc.resume()
                    # Continue the step numbering: a counter restarting
                    # at 0 would re-save the previous run's step
                    # numbers, hit the already-committed guard, and
                    # silently never persist new progress.
                    trainer.step_count = resumed_step
                    print(f"resumed {len(restored)} Store keys at "
                          f"step {resumed_step}", flush=True)
                    if zc is not None:
                        # Pin to the params' step: a crash between the
                        # Store save and the zero save must fail loudly
                        # here, never silently pair newer params with
                        # stale moments / schedule count.
                        zc.restore_into(trainer.zero_state(),
                                        step=resumed_step)
                        print("resumed sharded optimizer state "
                              f"(count {trainer.zero_state().count})",
                              flush=True)
            saved_i = -1
            for i in range(steps):
                out = trainer.step(next(stream))
                if i % 10 == 0 or i == steps - 1:
                    print(f"step {out['step']:5d} loss {out['loss']:.4f} "
                          f"grad_epoch {out['grad_epoch']}", flush=True)
                    if mw is not None:
                        mw.emit(int(out["step"]), loss=out["loss"],
                                grad_epoch=out["grad_epoch"])
                if sc is not None and ckpt_every and (
                        i + 1) % ckpt_every == 0:
                    # Step passed explicitly: params epochs don't bump
                    # on put() (resume semantics pin them), so the
                    # derived step would always be 0.
                    sc.save(step=out["step"])
                    if zc is not None:
                        zc.save(out["step"], trainer.zero_state())
                    saved_i = i
            if sc is not None and saved_i != steps - 1:
                print(f"store checkpoint: {sc.save(step=out['step'])}",
                      flush=True)
                if zc is not None:
                    zc.save(out["step"], trainer.zero_state())
        elif mode == "async":
            from ptype_tpu.parallel.tensorstore import TensorStore
            from ptype_tpu.train.param_server import AsyncWorker, ParamServer

            store = TensorStore(mesh, kv=cluster.store)
            server = ParamServer(model_cfg, store, optimizer=optimizer)
            worker = AsyncWorker(model_cfg, server)
            for i in range(steps):
                out = worker.step(next(stream))
                if i % 10 == 0 or i == steps - 1:
                    print(f"step {i:5d} loss {out['loss']:.4f} "
                          f"applied={out['applied']}", flush=True)
                    if mw is not None:
                        mw.emit(i, loss=out["loss"],
                                applied=float(out["applied"]))
        else:
            raise SystemExit(f"unknown MODE {mode!r}")
    finally:
        if mw is not None:
            mw.close()
        cluster.close()


if __name__ == "__main__":
    main()

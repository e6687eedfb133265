"""Training cells (traffic ``kind: train``): the program's ``Trainer``
(one GSPMD program a step) or ``StoreDPTrainer`` (gradients pushed and
pulled through the Store), built as examples/optimus/trainer.py builds
them, fed fresh seeded batches made on the device inside the window.

Set-up builds ONE trainer, drives it from the seed through its first
steps — the steps the reference follows afterwards — and hands that same
object to the window."""

from __future__ import annotations

import time

from benchmark import family, harness, traffic

FAULTS = ("state_unchanged", "half_batch", "no_exchange")


def build(ctx):
    """→ (trainer, step_batch(step) → batch, accessors)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ptype_tpu.models import transformer as tfm

    mix, cfg = ctx["mix"], ctx["cfg"]
    mesh = ctx["cluster"].mesh()
    n_dev = mesh.devices.size
    if n_dev != ctx["chips"]:
        raise SystemExit(f"benchmark: mesh covers {n_dev} devices, the "
                         f"cell asks for {ctx['chips']}")
    fam = family.of(cfg)
    tcfg = fam.program_config(cfg, mix["seq"], cfg["param_dtype"])
    if mix.get("remat_policy"):
        from dataclasses import replace

        tcfg = replace(tcfg, remat=True, remat_policy=mix["remat_policy"])
    batch = int(mix["per_chip_batch"]) * n_dev
    repl = NamedSharding(mesh, P())
    params = fam.tree(cfg, ctx["seed"], cfg["param_dtype"], repl)
    if mix["trainer"] == "gspmd":
        from ptype_tpu.train.trainer import Trainer, TrainState

        trainer = Trainer(tcfg, mesh, sync_every=0)
        axis_sizes = {n: int(mesh.shape[n]) for n in mesh.axis_names}
        params = jax.device_put(params, trainer.state_shardings.params)
        trainer.state = TrainState(params, trainer.state.opt_state,
                                   trainer.state.step)
        data_sh = NamedSharding(mesh, tfm.batch_spec(axis_sizes))
        get = {"params": lambda: trainer.state.params,
               "opt_state": lambda: trainer.state.opt_state}
    elif mix["trainer"] == "store":
        from ptype_tpu.parallel.tensorstore import TensorStore
        from ptype_tpu.train.store_dp import StoreDPTrainer

        store = TensorStore(mesh, kv=ctx["cluster"].store)
        trainer = StoreDPTrainer(tcfg, store)
        # An outside writer: the trainer's next params() sees the
        # store's write stamp move and pulls these.
        store.put_tree("params", params)
        data_sh = NamedSharding(mesh, P(store.axis, None))
        get = {"params": trainer.params,
               "opt_state": lambda: trainer.opt_state}
    else:
        raise SystemExit(f"benchmark: unknown trainer {mix['trainer']!r}")
    make = traffic.train_batch_fn(int(cfg["vocab_size"]), batch,
                                  int(mix["seq"]), ctx["seed"], data_sh)
    return trainer, make, get, batch


def _mu_of(opt_state):
    """Adam's first moment, found by name in the optimizer's state."""
    import jax

    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
            return
        if isinstance(node, (tuple, list)):
            for x in node:
                visit(x)
        elif isinstance(node, dict):
            for x in node.values():
                visit(x)

    visit(opt_state)
    if len(found) != 1:
        raise SystemExit(f"benchmark: {len(found)} Adam states in the "
                         f"optimizer's state, want 1")
    return found[0]


def leaf_norms(tree) -> dict:
    """name → norms per layer (stacked block leaves give one a layer)."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = leaf.astype(jnp.float32)
        if "blocks" in name:
            out[name] = jnp.sqrt(jnp.sum(
                x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    mix, cfg = ctx["mix"], ctx["cfg"]
    hp = cfg["training"]
    trainer, make, get, batch = build(ctx)
    fault = ctx.get("fault")
    if fault is not None and fault not in FAULTS:
        raise SystemExit(f"benchmark: a training cell has no fault {fault!r}")
    n_check = int(mix.get("check_steps", 3))

    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda x, y: x.astype(jnp.float32)
                     - y.astype(jnp.float32), a, b)))
    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

    def program_step(i):
        b = make(np.int32(i))
        if fault == "half_batch":
            half = batch // 2
            b = {k: jnp.concatenate([v[:half], v[:half]]) for k, v in
                 b.items()}
        if fault == "state_unchanged":
            if mix["trainer"] == "gspmd":
                keep = copy(trainer.state)
                out = trainer.step(b)
                trainer.state = keep
            else:
                keep = (copy(trainer.params()), copy(trainer.opt_state))
                out = trainer.step(b)
                trainer.store.put_tree("params", keep[0])
                trainer.opt_state = keep[1]
            return out
        return trainer.step(b)

    if fault == "no_exchange":
        # What comes back from the Store is one chip's local gradient
        # (worker 0's stands for each chip's own): the other chips'
        # contributions never enter the mean, as if the exchange had
        # been left out.
        push = trainer.store.push_tree
        trainer.store.push_tree = lambda prefix, tree, *a, **kw: push(
            prefix, jax.tree.map(
                lambda leaf: jnp.broadcast_to(leaf[:1], leaf.shape), tree),
            *a, **kw)

    # ---- set-up: the first steps, through the window's own call.
    p0 = copy(get["params"]())
    losses, g1 = [], None
    for i in range(n_check):
        out = program_step(i)
        losses.append(out["loss"])
        if i == 0:
            mu = _mu_of(get["opt_state"]())
            g1 = norms(jax.tree.map(
                lambda m: m / (1.0 - float(hp["b1"])), mu))
    dp = diff_norms(get["params"](), p0)
    program = {
        "losses": [float(x) for x in losses],
        "grad": {k: np.asarray(v) for k, v in g1.items()},
        "change": {k: np.asarray(v) for k, v in dp.items()}}
    del p0, dp, g1
    # Warm the window's own shapes: the same step, the same feed.
    for i in range(n_check, n_check + 2):
        out = program_step(i)
    jax.block_until_ready(out["loss"])

    counter = ctx["compiles"]
    compiles0 = counter.n
    setup_s = time.perf_counter() - ctx["t0"]

    # ---- the window.
    seconds = float(ctx["seconds"])
    steps = 0
    i = n_check + 2
    with harness.traced_window(ctx["trace_dir"]):
        t_open = time.perf_counter()
        while True:
            out = program_step(i)
            i += 1
            steps += 1
            # A step is dispatched ahead of the device; look at the
            # clock only every few, and close on a drained queue.
            if steps % 4 == 0:
                jax.block_until_ready(out["loss"])
                if time.perf_counter() - t_open >= seconds:
                    break
        jax.block_until_ready(out["loss"])
        window_s = time.perf_counter() - t_open
    compiles = counter.n - compiles0
    tokens = steps * batch * int(mix["seq"])
    flops_per_token = family.of(cfg).train_flops_per_token(
        cfg, int(mix["seq"]))
    peak = harness.memory_peak_bytes(ctx["devices"])
    final_loss = float(out["loss"])

    # ---- free the program's state, then follow the first steps.
    del trainer, get, out
    ref = follow(ctx, batch, n_check)
    checks = compare(program, ref)
    extra = {}
    for name, kw in (ctx.get("readings") or {}).items():
        # The limits' other readings (benchmark/readings.py): the
        # reference in the program's place, in a lower precision or
        # with a fault planted, against the reference as it stands.
        other = follow(ctx, batch, n_check, **kw)
        extra[name] = compare(other, ref)
    checks["final_loss_finite"] = (
        0.0 if np.isfinite(final_loss) else float("nan"))
    ok, shown, rest = harness.judge(checks, ctx["limits"])
    return {
        "correct": ok, "attempted": steps, "failed": 0, "checks": shown,
        "e2e": {"setup_s": setup_s,
                "train_tok_s_chip": tokens / window_s / ctx["chips"]},
        "counters": {"window_s": window_s, "steps": steps,
                     "tokens": tokens, "batch": batch,
                     "per_chip_batch": int(mix["per_chip_batch"]),
                     "seq": int(mix["seq"]),
                     "compiles_in_window": compiles,
                     "not_compared": rest,
                     "model_flops_traced": tokens * flops_per_token,
                     "flops_per_token": flops_per_token},
        "memory_peak_bytes": peak, "readings": extra,
    }


def follow(ctx, batch: int, n_check: int, mode: str = "f32",
           rows=None, frozen_state: bool = False) -> dict:
    """The reference through the same first steps, on one device, from
    weights and batches it makes from the seed itself."""
    import jax.numpy as jnp
    import numpy as np

    mix, cfg = ctx["mix"], ctx["cfg"]
    fam = family.of(cfg)
    params = fam.tree(cfg, ctx["seed"], "float32")
    make = traffic.train_batch_fn(int(cfg["vocab_size"]), batch,
                                  int(mix["seq"]), ctx["seed"])
    batches = [make(np.int32(i)) for i in range(n_check)]
    losses, g1, after = fam.train_steps(
        cfg, cfg["training"], params, batches, mode,
        int(mix.get("reference_micro_rows", 4)), rows, frozen_state)
    import jax

    dp = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(after, params)
    return {"losses": [float(x) for x in losses],
            "grad": {k: np.asarray(v) for k, v in
                     jax.jit(leaf_norms)(g1).items()},
            "change": {k: np.asarray(v) for k, v in dp.items()}}


def compare(program: dict, ref: dict) -> dict:
    """The numbers a run computes → {name: value}; the cell's limits
    file says which of them are compared, and against what.

    Norm gaps are | ‖program‖ − ‖reference‖ | over the larger of the
    reference's norm of that leaf and of the median leaf, worst leaf.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's (a key's bias under softmax, say) move under Adam by round-off
    alone and are left out of the change."""
    import numpy as np

    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], ref["losses"])):
        out[f"loss_step{i + 1}_rel"] = abs(a - b) / abs(b)
    names = sorted(ref["grad"])
    gref = np.concatenate([ref["grad"][k] for k in names])
    gprog = np.concatenate([program["grad"][k] for k in names])
    med = float(np.median(gref))
    out["grad_norm_gap_worst_leaf"] = float(np.max(
        np.abs(gprog - gref) / np.maximum(gref, med)))
    cref = np.concatenate([ref["change"][k] for k in names])
    cprog = np.concatenate([program["change"][k] for k in names])
    moved = gref >= 1e-3 * med
    cmed = float(np.median(cref[moved]))
    out["change_norm_gap_worst_leaf"] = float(np.max(
        np.abs(cprog - cref)[moved] / np.maximum(cref[moved], cmed)))
    return out

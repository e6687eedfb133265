"""Training-loop tier for ISSUE 7: the ZeRO-1 sharded weight update on
the real store-DP trainer — trajectory parity vs the replicated
baseline (the barrier path must be tolerance-exact, the int8+EF wire
curve-matched), the measured ~N× per-replica optimizer-memory shrink,
the goodput ledger's optimizer leg, and the sharded-checkpoint
roundtrip that RESUMES TRAINING on a different replica count."""

import os

import jax
import numpy as np
import pytest

from ptype_tpu.checkpoint import StoreCheckpoint, ZeroCheckpoint
from ptype_tpu.errors import CheckpointError
from ptype_tpu.models import transformer as tfm
from ptype_tpu.parallel import mesh as M
from ptype_tpu.parallel.collectives import WireConfig
from ptype_tpu.parallel.tensorstore import TensorStore
from ptype_tpu.train.store_dp import StoreDPTrainer, measure_zero

pytestmark = pytest.mark.slow

TINY = tfm.preset("tiny")


@pytest.fixture(scope="module")
def mesh8():
    return M.build_mesh({"data": 8})


@pytest.fixture(scope="module")
def mesh4():
    return M.build_mesh({"data": 4})


def _batches(batch=16, seq=64, seed=0):
    from ptype_tpu.train.data import synthetic_batches

    return synthetic_batches(TINY.vocab_size, batch, seq, seed=seed)


def _opt_bytes(tree) -> int:
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        shards = getattr(x, "addressable_shards", None)
        total += (shards[0].data.nbytes if shards
                  else getattr(x, "nbytes", 0))
    return total


def test_zero_matches_replicated_store_dp(mesh8):
    """zero=True (reduce-scatter → shard-local AdamW → allgather) is
    the SAME algorithm as the replicated barrier step: loss and
    parameter trajectories match to float tolerance, while each
    replica holds 1/8 of the moments."""
    steps = 4
    a = StoreDPTrainer(TINY, TensorStore(mesh8),
                       rng=jax.random.PRNGKey(1))
    b = StoreDPTrainer(TINY, TensorStore(mesh8),
                       rng=jax.random.PRNGKey(1), zero=True)
    ia, ib = _batches(seed=1), _batches(seed=1)
    la = [a.step(next(ia))["loss"] for _ in range(steps)]
    lb = [b.step(next(ib))["loss"] for _ in range(steps)]
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(a.params()),
                    jax.tree_util.tree_leaves(b.params())):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=1e-6)
    # The acceptance claim measured, not planned: per-replica
    # optimizer bytes shrink ~8× vs the replicated baseline.
    repl = _opt_bytes(a.opt_state)
    shard = b.zero_state().moment_bytes_per_replica()
    assert repl >= 7.5 * shard, (repl, shard)
    # The replicated whole-tree state stays None — loud, never stale.
    assert b.opt_state is None
    # Store semantics: scatter pushes bump bucket epochs per step.
    assert b.step(next(ib))["grad_epoch"] == steps + 1


def test_zero_int8_ef_tracks_fp32_curve(mesh8):
    """The sharded update rides the block-scaled int8 + error-feedback
    wire (residuals owned per shard): the loss curve tracks the exact
    wire within tolerance and still learns."""
    steps = 10
    a = StoreDPTrainer(TINY, TensorStore(mesh8),
                       rng=jax.random.PRNGKey(2), zero=True)
    b = StoreDPTrainer(
        TINY, TensorStore(mesh8, wire=WireConfig(compress="int8",
                                                 int8_min_bytes=0)),
        rng=jax.random.PRNGKey(2), zero=True)
    batch = next(_batches())  # one batch, memorized: loss must fall
    la = [a.step(batch)["loss"] for _ in range(steps)]
    lb = [b.step(batch)["loss"] for _ in range(steps)]
    np.testing.assert_allclose(la, lb, rtol=5e-3)
    assert lb[-1] < lb[0]
    # EF residuals live under the grad LEAF keys (ownership uniform
    # with the allreduce paths).
    assert any(k.startswith("grads/")
               for k in b.store._residuals)


def test_zero_rejects_custom_optimizer_and_overlap(mesh8):
    import optax

    with pytest.raises(ValueError, match="zero=True"):
        StoreDPTrainer(TINY, TensorStore(mesh8),
                       optimizer=optax.sgd(1e-2), zero=True)
    with pytest.raises(ValueError, match="overlap"):
        StoreDPTrainer(TINY, TensorStore(mesh8), zero=True,
                       overlap=True)
    with pytest.raises(ValueError, match="no ZeRO state"):
        StoreDPTrainer(TINY, TensorStore(mesh8)).zero_state()


@pytest.mark.parametrize("n_to", [4, 8])
def test_zero_checkpoint_resumes_on_changed_replica_count(
        tmp_path, mesh8, mesh4, n_to):
    """The acceptance drill: train sharded on 8 replicas, checkpoint
    (params via the Store tier, moments via ZeroCheckpoint — per-shard
    crc32 verified on load), restore onto ``n_to`` replicas, and
    CONTINUE: because the global batch is the same, the resumed
    trajectory must match the uninterrupted 8-replica run to float
    tolerance — the reshard changed the layout, not the math."""
    mesh_to = {4: mesh4, 8: mesh8}[n_to]
    it = _batches(seed=3)
    tr8 = StoreDPTrainer(TINY, TensorStore(mesh8),
                         rng=jax.random.PRNGKey(3), zero=True)
    for _ in range(3):
        tr8.step(next(it))
    ZeroCheckpoint(str(tmp_path / "zero")).save(3, tr8.zero_state())
    StoreCheckpoint(tr8.store, str(tmp_path / "store"),
                    keys_prefix="params/").save(3)

    trN = StoreDPTrainer(TINY, TensorStore(mesh_to),
                         rng=jax.random.PRNGKey(99), zero=True)
    StoreCheckpoint(trN.store, str(tmp_path / "store"),
                    keys_prefix="params/").resume()
    assert ZeroCheckpoint(str(tmp_path / "zero")).restore_into(
        trN.zero_state()) == 3
    assert trN.zero_state().count == 3

    cont8, contN = _batches(seed=4), _batches(seed=4)
    c8 = [tr8.step(next(cont8))["loss"] for _ in range(3)]
    cN = [trN.step(next(contN))["loss"] for _ in range(3)]
    np.testing.assert_allclose(c8, cN, rtol=1e-4)
    # And the restored run still shards: 1/n_to resident moments.
    zs = trN.zero_state()
    for arr in zs.mu:
        assert arr.addressable_shards[0].data.size * n_to == arr.size


def test_zero_checkpoint_corrupt_shard_is_loud(tmp_path, mesh8):
    """A corrupted moment shard must raise CheckpointError naming the
    shard on restore — never silently load bit rot into training."""
    tr = StoreDPTrainer(TINY, TensorStore(mesh8),
                        rng=jax.random.PRNGKey(0), zero=True)
    tr.step(next(_batches()))
    zc = ZeroCheckpoint(str(tmp_path))
    sdir = zc.save(1, tr.zero_state())
    victim = sorted(f for f in os.listdir(sdir)
                    if ".nu.shard" in f and f.endswith(".npy"))[0]
    path = os.path.join(sdir, victim)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CheckpointError, match=victim.split(".npy")[0]):
        ZeroCheckpoint(str(tmp_path)).restore_into(tr.zero_state())


def test_zero_optimizer_leg_lands_in_goodput(mesh8):
    """The shard-local apply is attributed as its own ``optimizer``
    leg in the step breakdown (ISSUE 7 satellite: the FLOP saving is
    a visible number in `obs top` and the bench tail)."""
    from ptype_tpu.health.goodput import GoodputLedger
    from ptype_tpu.metrics import MetricsRegistry

    trainer = StoreDPTrainer(TINY, TensorStore(mesh8),
                             rng=jax.random.PRNGKey(0), zero=True)
    stream = _batches()
    trainer.step(next(stream))  # compile + warm outside the ledger
    ledger = GoodputLedger(registry=MetricsRegistry()).install()
    try:
        for _ in range(3):
            trainer.step(next(stream))
    finally:
        ledger.uninstall()
    s = ledger.summary()
    assert s["step_breakdown"]["optimizer_ms"] > 0
    assert s["step_breakdown"]["collective_ms"] > 0


def test_measure_zero_probe(mesh8):
    """The ZeRO-1 probe: ~8× per-replica optimizer memory
    at matched loss."""
    r = measure_zero(mesh8, steps=2, batch=8)
    assert r["opt_mem_ratio"] >= 7.5
    assert r["zero_opt_mem_mb"] < r["repl_opt_mem_mb"]
    np.testing.assert_allclose(r["final_loss_zero"],
                               r["final_loss_repl"], rtol=1e-3)


# ------------------------------------------------- the ladder (ISSUE 17)


def test_zero_ladder_identical_loss_curve(mesh8):
    """Stages 1/2/3 are the SAME algorithm at different residency —
    loss curves pinned identical (rtol) against the replicated
    baseline, while resident memory steps DOWN the ladder:
    full grads at stage 1, 1/8 grads at 2/3, 1/8 params only at 3."""
    steps = 4
    trainers = {
        "repl": StoreDPTrainer(TINY, TensorStore(mesh8),
                               rng=jax.random.PRNGKey(5)),
    }
    for stage in (1, 2, 3):
        trainers[stage] = StoreDPTrainer(
            TINY, TensorStore(mesh8), rng=jax.random.PRNGKey(5),
            zero=stage)
    losses = {}
    for name, tr in trainers.items():
        it = _batches(seed=5)
        losses[name] = [float(tr.step(next(it))["loss"])
                        for _ in range(steps)]
    for stage in (1, 2, 3):
        np.testing.assert_allclose(losses[stage], losses["repl"],
                                   rtol=1e-5, err_msg=f"stage {stage}")
    # Param trajectories too — the ladder changed residency, not math.
    ref = jax.tree_util.tree_leaves(trainers["repl"].params())
    for stage in (1, 2, 3):
        for x, y in zip(ref,
                        jax.tree_util.tree_leaves(
                            trainers[stage].params())):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-5, atol=1e-6,
                                       err_msg=f"stage {stage}")
    # Memory rungs: grads shrink 8x moving 1 -> 2 (scattered stream),
    # and only stage 3 holds resident param shards (1/8 each).
    g1 = trainers[1].last_grad_bytes
    g2 = trainers[2].last_grad_bytes
    g3 = trainers[3].last_grad_bytes
    assert g1 >= 7.5 * g2, (g1, g2)
    assert abs(g2 - g3) <= max(g2, g3) * 0.01, (g2, g3)
    p3 = trainers[3].zero_state().param_bytes_per_replica()
    assert p3 > 0
    assert trainers[1].zero_state().param_bytes_per_replica() == 0
    total_param_bytes = sum(
        x.nbytes for x in ref)
    assert total_param_bytes >= 7.5 * p3, (total_param_bytes, p3)
    # Stage 3 keeps NO replicated leaves resident.
    assert trainers[3]._param_leaves is None


def test_zero3_checkpoint_roundtrip_carries_param_shards(
        tmp_path, mesh8, mesh4):
    """ZeRO-3 checkpoints persist the resident param flats (pbuckets)
    alongside the moments; restore onto HALF the replicas reshards
    params + moments together and training continues on the 8-replica
    trajectory."""
    it = _batches(seed=6)
    tr8 = StoreDPTrainer(TINY, TensorStore(mesh8),
                         rng=jax.random.PRNGKey(6), zero=3)
    for _ in range(3):
        tr8.step(next(it))
    ZeroCheckpoint(str(tmp_path)).save(3, tr8.zero_state())

    tr4 = StoreDPTrainer(TINY, TensorStore(mesh4),
                         rng=jax.random.PRNGKey(77), zero=3)
    assert ZeroCheckpoint(str(tmp_path)).restore_into(
        tr4.zero_state()) == 3
    # The restored param shards ARE tr8's params, resharded.
    for x, y in zip(jax.tree_util.tree_leaves(tr8.params()),
                    jax.tree_util.tree_leaves(tr4.params())):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # Re-home the store's flat commits to the restored shards before
    # stepping (what a resume wrapper does after restore_into).
    for bi, flat in enumerate(tr4.zero_state().pflat):
        tr4.store.commit_sharded(f"params/bucket{bi:05d}", flat)
    cont8, cont4 = _batches(seed=7), _batches(seed=7)
    c8 = [tr8.step(next(cont8))["loss"] for _ in range(2)]
    c4 = [tr4.step(next(cont4))["loss"] for _ in range(2)]
    np.testing.assert_allclose(c8, c4, rtol=1e-4)


def test_live_reshard_trainer_resumes_on_survivors(mesh8, mesh4):
    """StoreDPTrainer.reshard mid-run (stage 2 and 3): training
    continues on 4 survivors on the SAME trajectory as an
    uninterrupted 8-replica run — and faster than the checkpoint
    round trip it replaces (no disk, no restore)."""
    for stage in (2, 3):
        ref = StoreDPTrainer(TINY, TensorStore(mesh8),
                             rng=jax.random.PRNGKey(8), zero=stage)
        tr = StoreDPTrainer(TINY, TensorStore(mesh8),
                            rng=jax.random.PRNGKey(8), zero=stage)
        it_ref, it = _batches(seed=8), _batches(seed=8)
        for _ in range(3):
            ref.step(next(it_ref))
            tr.step(next(it))
        info = tr.reshard(mesh4)
        assert info["old_n"] == 8 and info["new_n"] == 4
        assert tr.n_workers == 4
        for _ in range(3):
            a = float(ref.step(next(it_ref))["loss"])
            b = float(tr.step(next(it))["loss"])
            np.testing.assert_allclose(a, b, rtol=1e-4,
                                       err_msg=f"stage {stage}")
        # Params stay in lockstep after the move.
        for x, y in zip(jax.tree_util.tree_leaves(ref.params()),
                        jax.tree_util.tree_leaves(tr.params())):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-5, atol=1e-6,
                                       err_msg=f"stage {stage}")


def test_zero_stage_knob_validation(mesh8):
    with pytest.raises(ValueError, match="ladder stage"):
        StoreDPTrainer(TINY, TensorStore(mesh8), zero=4)
    with pytest.raises(ValueError, match="ladder stage"):
        StoreDPTrainer(TINY, TensorStore(mesh8), zero="2")
    with pytest.raises(ValueError, match="live resharding"):
        StoreDPTrainer(TINY, TensorStore(mesh8)).reshard(mesh8)

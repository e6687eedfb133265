"""Microbench tier: bucketed push_tree must BEAT per-leaf push on the
8-device virtual host mesh (the ISSUE-1 acceptance bar). Slow-marked:
it compiles both push paths and runs timed warm iterations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.parallel import mesh as M
from ptype_tpu.parallel.tensorstore import TensorStore, measure_push_tree

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mesh8():
    return M.build_mesh({"data": 8})


def _many_leaf_tree(n_leaves=64, width=512, seed=0):
    rng = np.random.default_rng(seed)
    return {f"l{i:03d}": rng.normal(size=(8, width)).astype(np.float32)
            for i in range(n_leaves)}


def test_bucketed_push_tree_beats_per_leaf(mesh8):
    """64 leaves → 1 bucket: launch overhead is the whole difference,
    so the bucketed path must win with margin even on a noisy host."""
    import time

    ts = TensorStore(mesh8)
    tree = _many_leaf_tree()

    def timed(bucketed, iters=3):
        out = ts.push_tree("g", tree, op="mean", bucketed=bucketed)
        for v in out.values():
            v.block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = ts.push_tree("g", tree, op="mean", bucketed=bucketed)
        for v in out.values():
            v.block_until_ready()
        return (time.perf_counter() - t0) / iters

    per_leaf = timed(False)
    bucketed = timed(True)
    assert bucketed < per_leaf, (
        f"bucketed {bucketed * 1e3:.2f} ms not faster than per-leaf "
        f"{per_leaf * 1e3:.2f} ms")


def test_measure_push_tree_reports_speedup(mesh8):
    """The push_tree probe returns a coherent record on the host
    mesh."""
    r = measure_push_tree(mesh8, preset="tiny", iters=2)
    assert r["bucketed_ms"] > 0 and r["per_leaf_ms"] > 0
    assert r["n_buckets"] <= r["n_leaves"]
    assert r["gbps"] > 0


def test_bucketed_push_numerics_match_on_model_tree(mesh8):
    """End-to-end on a real (tiny) transformer param tree: bucketed
    grads == per-leaf grads, leaf for leaf."""
    from ptype_tpu.models import transformer as tfm

    cfg = tfm.preset("tiny")
    params = jax.jit(lambda r: tfm.init_params(r, cfg))(
        jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None] * 0.5, (8, *p.shape)), params)
    ts = TensorStore(mesh8)
    b = ts.push_tree("gb", stacked, op="mean")
    p = ts.push_tree("gp", stacked, op="mean", bucketed=False)
    for k, v in b.items():
        ref = p["gp" + k[len("gb"):]]
        np.testing.assert_array_equal(np.asarray(v), np.asarray(ref),
                                      err_msg=k)

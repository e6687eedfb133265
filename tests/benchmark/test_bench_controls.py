"""The controls, at a size a test run can hold: the plain reference put
in the program's place and computed in the nearest precision below the
one the configurations state (float8 for bfloat16) has to come out as
not correct, under limits set as the shipped ones are (readings in
perfbench_tiny.py); the program itself stays correct on the same seeds."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import harness, run  # noqa: E402

SEEDS = [11, 2 ** 31 + 5, 424242]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return perfbench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def limits_of(res):
    return {k: v["limit"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_in_float8_is_not_correct(seed, tiny_root):
    import jax

    res = run.execute("optimus-125m.train-s1024", seed, 0.3, False,
                      jax.devices()[:1], root=tiny_root,
                      readings={"control_fp8": {"mode": "fp8"}})
    assert res["correct"] is True, res["checks"]
    control = {**res["readings"]["control_fp8"], "final_loss_finite": 0.0}
    ok, shown, _ = harness.judge(control, limits_of(res))
    assert ok is False, shown


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_in_float8_is_not_correct(seed, tiny_root):
    import jax

    res = run.execute("mistral-7b.serve-chat", seed, 1.0, False,
                      jax.devices()[:1], root=tiny_root, readings=("fp8",))
    assert res["correct"] is True, res["checks"]
    control = {**res["readings"]["fp8"], "requests_failed": 0.0}
    ok, shown, _ = harness.judge(control, limits_of(res))
    assert ok is False, shown


def test_rounding_modes_round():
    """float32 stays; bfloat16 and float8 keep 8 and 4 significant bits
    (what ``reduce_precision`` holds on every backend, where a cast
    there and back may be dropped as excess precision)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.dense import reference

    x = jnp.asarray([1.0 + 2.0 ** -9, 1.0 + 2.0 ** -5, 3.3], jnp.float32)
    assert np.array_equal(reference._round(x, "f32"), x)
    b = np.asarray(reference._round(x, "bf16"))
    assert b[0] == 1.0 and b[1] == 1.0 + 2.0 ** -5
    f = np.asarray(reference._round(x, "fp8"))
    assert f[1] != np.asarray(x)[1] and abs(f[2] - 3.3) < 3.3 / 16
    assert abs(f[2] - 3.3) > 0

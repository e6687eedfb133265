"""chip_smoke.py's plumbing, proven here so chip calls are not spent on
typos: every phase function at tiny sizes on the 8-device CPU mesh with
interpreted kernels, the module readers on recorded text, and the
script's refusal to run without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from ptype_tpu import join  # noqa: E402
from ptype_tpu.config import Config, PlatformConfig  # noqa: E402
from ptype_tpu.models import transformer as tfm  # noqa: E402


@pytest.fixture
def cluster():
    c = join(Config(
        service_name="chip_smoke_test", node_name="n0",
        platform=PlatformConfig(
            coordinator_address="local:chip-smoke-test")))
    yield c
    c.close()


def test_train_and_train_store_phases(cluster):
    cfg = tfm.preset("tiny", attn_impl="flash")
    train = chip_smoke.phase_train(cluster, cfg, seq=32,
                                   per_chip_batch=2, steps=3)
    assert train["devices"] == jax.device_count() == 8
    assert train["loss_last"] < train["loss_first"]
    store = chip_smoke.phase_train_store(cluster, cfg, train["losses"],
                                         seq=32, per_chip_batch=2)
    assert store["tree_all_reduce"] == "exact"


def test_serve_phase_places_one_replica_per_device(cluster):
    out = chip_smoke.phase_serve(cluster, "tiny", long_len=118,
                                 devices=jax.devices()[:4])
    assert out["replicas"] == 4 and len(set(out["devices"])) == 4
    assert out["prefix_hits"] > 0


def test_kernels_phase(cluster):
    out = chip_smoke.phase_kernels(
        cluster, flash_shapes=(("mha", 2, 64, 2, 2, 16),
                               ("gqa", 1, 64, 4, 2, 16)),
        flash_blocks={"block_q": 32, "block_k": 32},
        width_preset="tiny", prefill_len=40,
        latent_shape=(4, 128, 64, 4, 4, 10, 4))
    assert set(out["flash"]) == {"mha", "gqa"}
    assert out["latent_block_err"] < 2e-2
    assert out["paged_decode_blocks_err"] < 2e-2


#: One Mosaic custom call as a compiled v5e module prints it (chip run,
#: PR 21; the serialized kernel body cut).
_COMPILED_LINE = (
    '%closed_call.74 = (bf16[16,6,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)}, '
    'f32[16,6,1024,128]{3,2,1,0:T(8,128)}) custom-call(%copy-done.13, '
    '%custom-call.52, %bitcast.622), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={bf16[16,6,1024,128]{3,2,1,0}, '
    'bf16[16,6,1024,128]{3,2,1,0}, bf16[16,6,1024,128]{3,2,1,0}}, '
    'backend_config={"custom_call_config":{"body":"TUzvUg"},'
    '"used_scoped_memory_configs":[{"memory_space":"1","offset":"0",'
    '"size":"5201920"}]}')


def test_module_readers():
    assert chip_smoke.compiled_kernel_operands(_COMPILED_LINE) == [
        (16, 6, 1024, 128)]
    assert chip_smoke.scoped_vmem_bytes(_COMPILED_LINE) == [5201920]
    # Fusions carry scoped-memory configs too; only kernels count.
    assert chip_smoke.scoped_vmem_bytes(
        _COMPILED_LINE.replace("tpu_custom_call", "Other")) == []
    assert chip_smoke.compiled_kernel_operands("no kernels here") == []


def test_script_refuses_to_run_without_a_tpu():
    """`python chip_smoke.py` on a CPU backend: the installation line,
    a non-zero exit, and no verdict."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0, p.stdout
    assert '"ok"' not in p.stdout
    assert '"platform": "cpu"' in p.stdout.splitlines()[0]


def test_compile_cache_lives_in_one_fixed_place(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the code
    sets no directory. Unset: <checkout>/.jax_cache, derived from the
    package location — never a temp dir, which would never hit. Either
    way the key takes the HLO metadata in (the op names a trace shows)."""
    from ptype_tpu import compile_cache

    # Recorded, not applied: once JAX has opened a persistent cache it
    # stays open for the rest of the process, whatever the config says.
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    keyed = ("jax_compilation_cache_include_metadata_in_key", True)
    assert compile_cache.configure() == "/somewhere/else"
    assert updates == [keyed]
    monkeypatch.delenv(compile_cache.ENV_VAR)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == want
    assert updates == [keyed, keyed, ("jax_compilation_cache_dir", want)]


def test_benchmark_command_may_not_name_this_script():
    """``chip_smoke.py`` is the root's other entry point, and it lies
    outside the benchmark's ``paths``: a manifest whose command names it
    is refused (``manifest.check`` looks a command word up on disk, so
    the case needs a file that exists)."""
    from benchmark import manifest

    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    m["command"] = ["python3", "chip_smoke.py"]
    assert any("outside paths" in e for e in manifest.check(m, REPO))

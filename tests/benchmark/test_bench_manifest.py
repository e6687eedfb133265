"""``python benchmark/run.py --check``: the shipped manifest holds, and a
copy broken in one way per rule is refused with a sentence that names
the rule."""

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import manifest  # noqa: E402


def shipped() -> dict:
    return manifest.load(ROOT)


def test_shipped_manifest_holds():
    assert manifest.check(shipped(), ROOT) == []


def test_check_command_needs_no_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    got = subprocess.run([sys.executable, "benchmark/run.py", "--check"],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert got.returncode == 0, got.stderr
    assert got.stdout.strip().endswith("ok")


def _moves_elsewhere(m):
    # PR 22's fault: a train cell reports a metric that moves a
    # serving-only end-to-end metric.
    x = next(x for x in m["per_layer"]
             if x["name"] == "compiles_in_window.chat")
    x["workloads"].append("optimus-125m.train-s1024")


def _no_workloads_key(m):
    # Without the key the metric is reported everywhere its `moves` is
    # not: the same fault.
    x = next(x for x in m["per_layer"] if x["name"] == "step_mfu.train")
    del x["workloads"]
    x["moves"] = "ttft_mean_ms"


def _name_char(m):
    m["per_layer"][0]["name"] = "step mfu,train"


def _unit_char(m):
    m["end_to_end"][1]["unit"] = "tokens per second"


def _unit_long(m):
    m["end_to_end"][1]["unit"] = "tokens/s/chip/host/pod"


def _source_long(m):
    m["configs"][0]["source"] = "x" * 201


def _config_without_cell(m):
    m["workloads"] = [w for w in m["workloads"]
                      if w["config"] != "mistral-7b"]
    for x in m["end_to_end"] + m["per_layer"]:
        if "workloads" in x:
            x["workloads"] = [c for c in x["workloads"]
                              if not c.startswith("mistral")]


def _too_many_four_chip(m):
    for w in m["workloads"][:2]:
        w["chips"] = 4


def _missing_traffic_file(m):
    m["workloads"][0]["traffic"] = "no-such-mix"


def _missing_config_file(m):
    m["configs"][0]["file"] = "benchmark/configs/none.json"


def _missing_metric_file(m):
    m["per_layer"][0]["name"] = "no_such_metric.train"


def _bound_too_wide(m):
    m["end_to_end"][1]["bound"] = 0.2


def _extra_key(m):
    m["per_layer"][0]["why"] = "because"


def _setup_missing(m):
    m["end_to_end"] = [x for x in m["end_to_end"]
                       if x["name"] != "setup_s"]


def _e2e_source(m):
    m["end_to_end"][1]["source"] = "program_counter"


def _same_pair_twice(m):
    w = copy.deepcopy(m["workloads"][0])
    w["name"] = "again"
    m["workloads"].append(w)


def _command_outside_paths(m):
    m["command"] = ["python3", "bench.py"]


BROKEN = [
    (_moves_elsewhere, "which it should move, is not"),
    (_no_workloads_key, "which it should move, is not"),
    (_name_char, "letters, digits"),
    (_unit_char, "unit"),
    (_unit_long, "unit"),
    (_source_long, "1-200 characters"),
    (_config_without_cell, "has no cell"),
    (_too_many_four_chip, "take four chips"),
    (_missing_traffic_file, "no traffic file"),
    (_missing_config_file, "missing or outside paths"),
    (_missing_metric_file, "no file"),
    (_bound_too_wide, "bound"),
    (_extra_key, "keys"),
    (_setup_missing, "setup_s"),
    (_e2e_source, "source"),
    (_same_pair_twice, "already make a cell"),
    (_command_outside_paths, "outside paths"),
]


@pytest.mark.parametrize("breaker,says", BROKEN,
                         ids=[b.__name__.lstrip("_") for b, _ in BROKEN])
def test_broken_copy_is_refused(breaker, says):
    m = shipped()
    breaker(m)
    errs = manifest.check(m, ROOT)
    assert errs, "the broken manifest passed"
    assert any(says in e for e in errs), errs


# A configuration names its family; the family is a module found by
# that name which holds every function of benchmark/family.py.


def _family_struck(cfg, monkeypatch):
    del cfg["family"]


def _family_unknown(cfg, monkeypatch):
    cfg["family"] = "no_such_family"


def _family_is_a_path(cfg, monkeypatch):
    cfg["family"] = "dense.work"


def _family_lacks_a_function(cfg, monkeypatch):
    from benchmark.families import twin

    cfg["family"] = "twin"
    monkeypatch.delattr(twin, "forward_flops")


BROKEN_FAMILY = [
    (_family_struck, "states no family"),
    (_family_unknown, "no family 'no_such_family' under"),
    (_family_is_a_path, "states no family"),
    (_family_lacks_a_function, "family 'twin' lacks forward_flops"),
]


@pytest.mark.parametrize("breaker,says", BROKEN_FAMILY,
                         ids=[b.__name__.lstrip("_")
                              for b, _ in BROKEN_FAMILY])
def test_configuration_without_a_whole_family_is_refused(
        breaker, says, tmp_path, monkeypatch):
    root = perfbench_tiny.make_root(str(tmp_path))
    m = perfbench_tiny.manifest()
    assert manifest.check(m, root) == []    # the twin's cell included
    f = os.path.join(root, "benchmark", "configs", "mistral-7b.json")
    with open(f) as fh:
        cfg = json.load(fh)
    breaker(cfg, monkeypatch)
    with open(f, "w") as fh:
        json.dump(cfg, fh)
    errs = manifest.check(m, root)
    assert [e for e in errs if "config mistral-7b" in e and says in e], errs


@pytest.mark.parametrize("family,says", [
    (None, "states no family"), ("glm5", "no family 'glm5' under")],
    ids=["struck", "no-module"])
def test_check_command_names_a_family_fault(family, says, tmp_path):
    """The command itself, over a copy of the shipped benchmark."""
    perfbench_tiny.copy_shipped(tmp_path)
    f = tmp_path / "benchmark" / "configs" / "optimus-125m.json"
    cfg = json.loads(f.read_text())
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    f.write_text(json.dumps(cfg))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    got = subprocess.run([sys.executable, "benchmark/run.py", "--check"],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert got.returncode == 1
    assert "config optimus-125m" in got.stderr and says in got.stderr
    assert got.stdout.strip().endswith("1 fault(s)")


def test_run_refuses_a_broken_manifest(tmp_path, monkeypatch):
    """The harness itself refuses before it looks for a chip."""
    from benchmark import run

    m = shipped()
    _moves_elsewhere(m)
    monkeypatch.setattr(manifest, "load", lambda root=ROOT: m)
    assert run.main(["--workload", "optimus-125m.train-s1024",
                     "--seed", "1", "--seconds", "1"]) == 2


def test_every_named_file_is_found_by_name():
    m = shipped()
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        base = os.path.join(ROOT, "benchmark", "families", cfg["family"])
        assert os.path.isdir(base) or os.path.isfile(base + ".py")
    for w in m["workloads"]:
        assert manifest.traffic_file(w["traffic"], m["paths"], ROOT)
    for x in m["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               x["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))

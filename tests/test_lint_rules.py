"""Unit tier for the house lint rules PT001–PT012 (now served by the
tools/ptlint package — the ``lint`` name below is the compatibility
alias over ``ptlint.check_file``) and the TTL-derived repl pump idle
tick. The ptlint v2 core, the PT013–PT017 passes, and the suppression
machinery are covered in tests/test_ptlint.py."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import ptlint as lint  # noqa: E402  (tools/ is not a package)


def _check(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    findings = []
    lint.check_file(str(p), findings)
    return findings


LOOPED_PUSH = (
    "def f(store, leaves):\n"
    "    for leaf in leaves:\n"
    "        store.push('k', leaf)\n"
)


def test_pt001_flags_per_leaf_loop_in_train(tmp_path):
    findings = _check(tmp_path, "train/bad.py", LOOPED_PUSH)
    assert any("PT001" in f for f in findings), findings


def test_pt001_flags_comprehensions(tmp_path):
    src = ("def f(store, leaves):\n"
           "    return [store.all_reduce(x) for x in leaves]\n")
    findings = _check(tmp_path, "train/comp.py", src)
    assert any("PT001" in f for f in findings), findings


def test_pt001_silent_outside_train(tmp_path):
    findings = _check(tmp_path, "parallel/ok.py", LOOPED_PUSH)
    assert not any("PT001" in f for f in findings), findings


def test_pt001_honors_noqa(tmp_path):
    src = ("def f(store, leaves):\n"
           "    for leaf in leaves:\n"
           "        store.push('k', leaf)  # noqa: intentional\n")
    findings = _check(tmp_path, "train/sup.py", src)
    assert not any("PT001" in f for f in findings), findings


def test_pt001_ignores_unlooped_calls(tmp_path):
    src = ("def f(store, stacked):\n"
           "    return store.push('k', stacked)\n")
    findings = _check(tmp_path, "train/fine.py", src)
    assert not any("PT001" in f for f in findings), findings


SLEEP_LOOP = (
    "import time\n"
    "def f(ready):\n"
    "    while not ready():\n"
    "        time.sleep(0.2)\n"
)


def test_pt002_flags_sleep_loop_in_package(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/bad.py", SLEEP_LOOP)
    assert any("PT002" in f for f in findings), findings


def test_pt002_flags_aliased_time_module(tmp_path):
    src = ("import time as _time\n"
           "def f(n):\n"
           "    for _ in range(n):\n"
           "        _time.sleep(0.1)\n")
    findings = _check(tmp_path, "ptype_tpu/alias.py", src)
    assert any("PT002" in f for f in findings), findings


def test_pt002_silent_outside_package(tmp_path):
    findings = _check(tmp_path, "tests/ok.py", SLEEP_LOOP)
    assert not any("PT002" in f for f in findings), findings


def test_pt002_exempts_retry_module_and_backoff_calls(tmp_path):
    # retry.py IS the sanctioned sleeper.
    findings = _check(tmp_path, "ptype_tpu/retry.py", SLEEP_LOOP)
    assert not any("PT002" in f for f in findings), findings
    # Backoff.sleep() inside a loop is the fix, not a finding.
    src = ("from ptype_tpu.retry import Backoff\n"
           "def f(ready):\n"
           "    bo = Backoff()\n"
           "    while not ready():\n"
           "        bo.sleep()\n")
    findings = _check(tmp_path, "ptype_tpu/good.py", src)
    assert not any("PT002" in f for f in findings), findings


def test_pt002_ignores_unlooped_sleep(tmp_path):
    src = "import time\ndef f():\n    time.sleep(0.1)\n"
    findings = _check(tmp_path, "ptype_tpu/one.py", src)
    assert not any("PT002" in f for f in findings), findings


def test_pt002_honors_noqa(tmp_path):
    src = ("import time\n"
           "def f(ready):\n"
           "    while not ready():\n"
           "        time.sleep(0.2)  # noqa: deliberate fixed poll\n")
    findings = _check(tmp_path, "ptype_tpu/sup.py", src)
    assert not any("PT002" in f for f in findings), findings


def test_ptype_tpu_package_is_pt002_clean():
    """The package itself must honor its own rule (the satellite that
    converted every retry loop to the shared Backoff)."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt002 = [f for f in findings if "PT002" in f]
    assert not pt002, pt002


def test_repl_idle_tick_derives_from_ttl():
    import pytest

    from ptype_tpu.coord.service import _repl_idle_tick

    assert _repl_idle_tick(3.0) == 1.0       # default TTL: old behavior
    # small TTL: 3 ticks per TTL so a quiet follower's vote can't flap
    assert _repl_idle_tick(0.6) == pytest.approx(0.2)
    assert _repl_idle_tick(30.0) == 1.0      # big TTL: 1 s ceiling holds


PT003_BYPASS = (
    "def serve(cluster):\n"
    "    client = cluster.new_client('llm')\n"
    "    return client.call('Generator.Generate', [1, 2], 8)\n"
)


def test_pt003_flags_direct_llm_client_in_package(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/bypass.py", PT003_BYPASS)
    assert any("PT003" in f for f in findings), findings


def test_pt003_silent_inside_gateway_package(tmp_path):
    # The gateway IS the sanctioned frontdoor.
    findings = _check(tmp_path, "ptype_tpu/gateway/ok.py", PT003_BYPASS)
    assert not any("PT003" in f for f in findings), findings


def test_pt003_silent_outside_package(tmp_path):
    # Examples / tests may drive the raw client deliberately.
    findings = _check(tmp_path, "examples/demo.py", PT003_BYPASS)
    assert not any("PT003" in f for f in findings), findings


def test_pt003_ignores_other_services(tmp_path):
    src = ("def f(cluster):\n"
           "    return cluster.new_client('calculator')\n")
    findings = _check(tmp_path, "ptype_tpu/calc.py", src)
    assert not any("PT003" in f for f in findings), findings


def test_pt003_honors_noqa(tmp_path):
    src = ("def f(cluster):\n"
           "    return cluster.new_client('llm')  # noqa: bench path\n")
    findings = _check(tmp_path, "ptype_tpu/sup3.py", src)
    assert not any("PT003" in f for f in findings), findings


def test_ptype_tpu_package_is_pt003_clean():
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt003 = [f for f in findings if "PT003" in f]
    assert not pt003, pt003


PT004_PRINT = (
    "def f(x):\n"
    "    print('debugging', x)\n"
    "    return x\n"
)


def test_pt004_flags_bare_print_in_package(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/noisy.py", PT004_PRINT)
    assert any("PT004" in f for f in findings), findings


def test_pt004_exempts_the_operator_cli(tmp_path):
    # __main__.py's stdout IS its contract (JSON records, usage).
    findings = _check(tmp_path, "ptype_tpu/__main__.py", PT004_PRINT)
    assert not any("PT004" in f for f in findings), findings


def test_pt004_silent_outside_package(tmp_path):
    # Tests / examples / bench print deliberately.
    findings = _check(tmp_path, "examples/demo.py", PT004_PRINT)
    assert not any("PT004" in f for f in findings), findings
    findings = _check(tmp_path, "tests/t.py", PT004_PRINT)
    assert not any("PT004" in f for f in findings), findings


def test_pt004_honors_noqa(tmp_path):
    src = ("def f(x):\n"
           "    print('one-off diagnostic', x)  # noqa: deliberate\n")
    findings = _check(tmp_path, "ptype_tpu/sup4.py", src)
    assert not any("PT004" in f for f in findings), findings


def test_ptype_tpu_package_is_pt004_clean():
    """Framework diagnostics ride logs/trace events, never stdout —
    the rule the package itself must honor (ISSUE 4 satellite)."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt004 = [f for f in findings if "PT004" in f]
    assert not pt004, pt004


PT005_DIRECT = (
    "def make():\n"
    "    c = Counter('hits')\n"
    "    return c\n"
)


def test_pt005_flags_direct_family_construction_in_package(tmp_path):
    for cls in ("Counter", "Timing", "Gauge", "Histogram"):
        src = PT005_DIRECT.replace("Counter", cls)
        findings = _check(tmp_path, f"ptype_tpu/{cls.lower()}.py", src)
        assert any("PT005" in f for f in findings), (cls, findings)


def test_pt005_flags_metrics_module_attribute_form(tmp_path):
    src = ("from ptype_tpu import metrics\n"
           "def make():\n"
           "    return metrics.Gauge('depth')\n")
    findings = _check(tmp_path, "ptype_tpu/attr.py", src)
    assert any("PT005" in f for f in findings), findings


def test_pt005_silent_for_registry_factories(tmp_path):
    src = ("from ptype_tpu.metrics import metrics\n"
           "def make():\n"
           "    return metrics.counter('hits'), metrics.gauge('g')\n")
    findings = _check(tmp_path, "ptype_tpu/good5.py", src)
    assert not any("PT005" in f for f in findings), findings


def test_pt005_silent_for_other_counters(tmp_path):
    # collections.Counter is not a metric family.
    src = ("import collections\n"
           "def f(xs):\n"
           "    return collections.Counter(xs)\n")
    findings = _check(tmp_path, "ptype_tpu/coll.py", src)
    assert not any("PT005" in f for f in findings), findings


def test_pt005_exempts_metrics_module_and_outside_package(tmp_path):
    # metrics.py IS the factory.
    findings = _check(tmp_path, "ptype_tpu/metrics.py", PT005_DIRECT)
    assert not any("PT005" in f for f in findings), findings
    # Tests construct families deliberately.
    findings = _check(tmp_path, "tests/t5.py", PT005_DIRECT)
    assert not any("PT005" in f for f in findings), findings


def test_pt005_honors_noqa(tmp_path):
    src = ("def make():\n"
           "    return Counter('x')  # noqa: deliberate\n")
    findings = _check(tmp_path, "ptype_tpu/sup5.py", src)
    assert not any("PT005" in f for f in findings), findings


def test_ptype_tpu_package_is_pt005_clean():
    """Every metric family in the package comes from a MetricsRegistry
    (the health sampler's visibility contract — ISSUE 5 satellite)."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt005 = [f for f in findings if "PT005" in f]
    assert not pt005, pt005


INT8_CAST = ("import jax.numpy as jnp\n"
             "def ship(x):\n"
             "    return x.astype(jnp.int8)\n")


def test_pt006_flags_raw_int8_cast_in_parallel(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/parallel/bad.py", INT8_CAST)
    assert any("PT006" in f for f in findings), findings


def test_pt006_flags_string_dtype_form(tmp_path):
    src = ("def ship(x):\n"
           "    return x.astype('int8')\n")
    findings = _check(tmp_path, "ptype_tpu/parallel/bad2.py", src)
    assert any("PT006" in f for f in findings), findings


def test_pt006_exempts_quantize_helpers(tmp_path):
    src = ("import jax.numpy as jnp\n"
           "def _q_int8_blockwise(x):\n"
           "    return x.astype(jnp.int8)\n"
           "def quantize_leaf(x):\n"
           "    return x.astype(jnp.int8)\n")
    findings = _check(tmp_path, "ptype_tpu/parallel/quant.py", src)
    assert not any("PT006" in f for f in findings), findings


def test_pt006_silent_outside_parallel(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/models/ok.py", INT8_CAST)
    assert not any("PT006" in f for f in findings), findings
    findings = _check(tmp_path, "other/parallel/ok.py", INT8_CAST)
    assert not any("PT006" in f for f in findings), findings


def test_pt006_ignores_other_dtypes(tmp_path):
    src = ("import jax.numpy as jnp\n"
           "def ship(x):\n"
           "    return x.astype(jnp.bfloat16)\n")
    findings = _check(tmp_path, "ptype_tpu/parallel/ok.py", src)
    assert not any("PT006" in f for f in findings), findings


def test_pt006_honors_noqa(tmp_path):
    src = ("import jax.numpy as jnp\n"
           "def ship(x):\n"
           "    return x.astype(jnp.int8)  # noqa: deliberate\n")
    findings = _check(tmp_path, "ptype_tpu/parallel/sup6.py", src)
    assert not any("PT006" in f for f in findings), findings


def test_parallel_package_is_pt006_clean():
    """Every int8 narrowing in the data plane rides the scaled
    quantize helpers (ISSUE 6 satellite)."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu",
                       "parallel")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt006 = [f for f in findings if "PT006" in f]
    assert not pt006, pt006


def test_pt006_flags_keyword_dtype_form(tmp_path):
    src = ("import jax.numpy as jnp\n"
           "def ship(x):\n"
           "    return x.astype(dtype=jnp.int8)\n")
    findings = _check(tmp_path, "ptype_tpu/parallel/kw.py", src)
    assert any("PT006" in f for f in findings), findings


PT007_HOT_PATH = (
    "class T:\n"
    "    def step(self, params, grads):\n"
    "        state = self.optimizer.init(params)\n"
    "        return state\n"
)


def test_pt007_flags_full_tree_opt_state_in_step_path(tmp_path):
    findings = _check(tmp_path, "train/hot.py", PT007_HOT_PATH)
    assert any("PT007" in f for f in findings), findings


def test_pt007_flags_bare_and_call_receivers(tmp_path):
    src = ("def step(optimizer, params):\n"
           "    return optimizer.init(params)\n")
    findings = _check(tmp_path, "train/bare.py", src)
    assert any("PT007" in f for f in findings), findings
    src = ("from x import default_optimizer\n"
           "def refresh(params):\n"
           "    return default_optimizer().init(params)\n")
    findings = _check(tmp_path, "train/call.py", src)
    assert any("PT007" in f for f in findings), findings


def test_pt007_sanctions_init_helpers(tmp_path):
    src = ("class T:\n"
           "    def __init__(self, params):\n"
           "        self.opt_state = self.optimizer.init(params)\n"
           "def init_state(optimizer, params):\n"
           "    return optimizer.init(params)\n"
           "def _init_bucket_apply(opt, params):\n"
           "    return opt.init(params)\n")
    findings = _check(tmp_path, "train/ok.py", src)
    assert not any("PT007" in f for f in findings), findings


def test_pt007_ignores_non_optimizer_inits(tmp_path):
    src = ("def step(sampler, params):\n"
           "    return sampler.init(params)\n")
    findings = _check(tmp_path, "train/other.py", src)
    assert not any("PT007" in f for f in findings), findings


def test_pt007_silent_outside_train(tmp_path):
    findings = _check(tmp_path, "parallel/hot.py", PT007_HOT_PATH)
    assert not any("PT007" in f for f in findings), findings


def test_pt007_honors_noqa(tmp_path):
    src = ("def step(optimizer, params):\n"
           "    return optimizer.init(params)  # noqa: test fixture\n")
    findings = _check(tmp_path, "train/sup7.py", src)
    assert not any("PT007" in f for f in findings), findings


def test_train_package_is_pt007_clean():
    """Every full-tree optimizer-state construction in train/ lives in
    an init helper — the seam the ZeRO-1 sharded update replaces
    (ISSUE 7 satellite)."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu",
                       "train")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt007 = [f for f in findings if "PT007" in f]
    assert not pt007, pt007


PT008_RAW_TRACE = ("import jax\n"
                   "def grab(d):\n"
                   "    jax.profiler.start_trace(d)\n"
                   "    jax.profiler.stop_trace()\n")


def test_pt008_flags_raw_profiler_trace_calls(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/sneaky.py", PT008_RAW_TRACE)
    assert sum("PT008" in f for f in findings) == 2, findings


def test_pt008_flags_from_import_forms(tmp_path):
    src = ("from jax.profiler import start_trace\n"
           "from jax import profiler\n"
           "def grab(d):\n"
           "    start_trace(d)\n"
           "    profiler.stop_trace()\n")
    findings = _check(tmp_path, "ptype_tpu/forms.py", src)
    assert sum("PT008" in f for f in findings) == 2, findings


def test_pt008_exempts_the_managed_seam_only(tmp_path):
    # health/profiling.py (the managed capture plane) IS the sanctioned
    # call site; metrics.py lost its wrapper (``metrics.trace``) and
    # its exemption with it.
    findings = _check(tmp_path, "ptype_tpu/health/profiling.py",
                      PT008_RAW_TRACE)
    assert not any("PT008" in f for f in findings), findings
    findings = _check(tmp_path, "ptype_tpu/metrics.py", PT008_RAW_TRACE)
    assert sum("PT008" in f for f in findings) == 2, findings


def test_pt008_silent_outside_package(tmp_path):
    # Tests and examples drive the profiler deliberately.
    findings = _check(tmp_path, "tests/t8.py", PT008_RAW_TRACE)
    assert not any("PT008" in f for f in findings), findings
    findings = _check(tmp_path, "examples/demo8.py", PT008_RAW_TRACE)
    assert not any("PT008" in f for f in findings), findings


def test_pt008_ignores_other_trace_apis(tmp_path):
    src = ("from ptype_tpu.health import profiling\n"
           "from ptype_tpu import trace\n"
           "def ok(d):\n"
           "    profiling.capture(duration_s=0.1)\n"
           "    trace.enable('svc')\n")
    findings = _check(tmp_path, "ptype_tpu/ok8.py", src)
    assert not any("PT008" in f for f in findings), findings


def test_pt008_honors_noqa(tmp_path):
    src = ("import jax\n"
           "def grab(d):\n"
           "    jax.profiler.start_trace(d)  # noqa: sanctioned\n")
    findings = _check(tmp_path, "ptype_tpu/sup8.py", src)
    assert not any("PT008" in f for f in findings), findings


def test_ptype_tpu_package_is_pt008_clean():
    """Every jax.profiler start/stop in the package rides the managed
    capture seam (ISSUE 8 satellite): metrics.py's legacy wrapper and
    health/profiling.py only."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt008 = [f for f in findings if "PT008" in f]
    assert not pt008, pt008


# --------------------------------------------------------------- PT009


PT009_RAW_BANK = (
    "from ptype_tpu.models import generate as g\n"
    "def build(cfg, n_slots, reach):\n"
    "    bank = g.init_cache(cfg, n_slots, max_seq=reach)\n"
    "    return bank\n")


def test_pt009_flags_raw_cache_bank_in_serving_code(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/serve.py", PT009_RAW_BANK)
    assert sum("PT009" in f for f in findings) == 1, findings
    # Bare-name form too.
    src = ("from ptype_tpu.models.generate import init_cache\n"
           "def build(cfg):\n"
           "    return init_cache(cfg, 8)\n")
    findings = _check(tmp_path, "ptype_tpu/frontend.py", src)
    assert sum("PT009" in f for f in findings) == 1, findings


def test_pt009_exempts_serve_engine_and_models(tmp_path):
    # serve_engine/ IS the paged pool; models/ holds init_cache and
    # the solo compiled path.
    findings = _check(tmp_path, "ptype_tpu/serve_engine/blocks.py",
                      PT009_RAW_BANK)
    assert not any("PT009" in f for f in findings), findings
    findings = _check(tmp_path, "ptype_tpu/models/generate.py",
                      PT009_RAW_BANK)
    assert not any("PT009" in f for f in findings), findings


def test_pt009_silent_outside_package(tmp_path):
    # Tests allocate contiguous caches deliberately (parity refs).
    findings = _check(tmp_path, "tests/t9.py", PT009_RAW_BANK)
    assert not any("PT009" in f for f in findings), findings
    findings = _check(tmp_path, "examples/demo9.py", PT009_RAW_BANK)
    assert not any("PT009" in f for f in findings), findings


def test_pt009_honors_noqa(tmp_path):
    src = ("from ptype_tpu.models import generate as g\n"
           "def build(cfg):\n"
           "    return g.init_cache(cfg, 8)  # noqa: sanctioned\n")
    findings = _check(tmp_path, "ptype_tpu/sup9.py", src)
    assert not any("PT009" in f for f in findings), findings


def test_ptype_tpu_package_is_pt009_clean():
    """The serving actors allocate KV through the paged block pool
    only (ISSUE 9): no contiguous full-reach bank allocations outside
    serve_engine/ and models/."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt009 = [f for f in findings if "PT009" in f]
    assert not pt009, pt009


# --------------------------------------------------------------- PT010


PT010_RAW_TIMER = (
    "import time\n"
    "def step(engine):\n"
    "    t0 = time.perf_counter()\n"
    "    engine.run()\n"
    "    return (time.perf_counter() - t0, time.time())\n")


def test_pt010_flags_raw_timers_in_serve_engine(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sneak.py",
                      PT010_RAW_TIMER)
    assert sum("PT010" in f for f in findings) == 3, findings


def test_pt010_flags_aliased_and_from_import_forms(tmp_path):
    src = ("import time as _t\n"
           "from time import perf_counter as pc, time as wall\n"
           "def step(engine):\n"
           "    a = _t.perf_counter()\n"
           "    b = pc()\n"
           "    c = wall()\n"
           "    return a, b, c\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/forms.py", src)
    assert sum("PT010" in f for f in findings) == 3, findings


def test_pt010_silent_outside_serve_engine(tmp_path):
    # The ledger (health/serving.py) IS the timing home; the rest of
    # the package and the tests time things deliberately.
    for rel in ("ptype_tpu/health/serving.py", "ptype_tpu/serve.py",
                "tests/t10.py", "examples/demo10.py"):
        findings = _check(tmp_path, rel, PT010_RAW_TIMER)
        assert not any("PT010" in f for f in findings), (rel, findings)


def test_pt010_ignores_non_timer_time_attrs(tmp_path):
    src = ("import time\n"
           "def fmt(ts):\n"
           "    return time.strftime('%H:%M', time.localtime(ts))\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/ok10.py", src)
    assert not any("PT010" in f for f in findings), findings


def test_pt010_ignores_unrelated_modules_named_time(tmp_path):
    # Only names bound to the stdlib ``time`` module count; a .time()
    # method on some other object is not a wall-clock read.
    src = ("def f(sim):\n"
           "    return sim.time()\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sim10.py", src)
    assert not any("PT010" in f for f in findings), findings


def test_pt010_honors_noqa(tmp_path):
    src = ("import time\n"
           "def step():\n"
           "    return time.perf_counter()  # noqa: sanctioned\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sup10.py", src)
    assert not any("PT010" in f for f in findings), findings


def test_serve_engine_package_is_pt010_clean():
    """Every latency stamp in serve_engine/ rides the serving ledger's
    seams (ISSUE 10): no raw perf_counter/time calls in the package."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu",
                       "serve_engine")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt010 = [f for f in findings if "PT010" in f]
    assert not pt010, pt010


# --------------------------------------------------------------- PT011


PT011_RAW_SAMPLING = (
    "import jax\n"
    "def pick(key, logits):\n"
    "    a = jax.random.categorical(key, logits)\n"
    "    g = jax.random.gumbel(key, logits.shape)\n"
    "    return a, g\n")


def test_pt011_flags_raw_sampling_in_serve_engine(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sneak.py",
                      PT011_RAW_SAMPLING)
    assert sum("PT011" in f for f in findings) == 2, findings


def test_pt011_flags_aliased_and_from_import_forms(tmp_path):
    src = ("from jax import random\n"
           "import jax.random as jr\n"
           "from jax.random import categorical as cat, gumbel\n"
           "def pick(key, lg):\n"
           "    a = random.categorical(key, lg)\n"
           "    b = jr.gumbel(key, lg.shape)\n"
           "    c = cat(key, lg)\n"
           "    d = gumbel(key, lg.shape)\n"
           "    return a, b, c, d\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/forms.py", src)
    assert sum("PT011" in f for f in findings) == 4, findings


def test_pt011_silent_outside_serve_engine(tmp_path):
    # models/generate.py IS the RNG home; tests/examples sample
    # deliberately.
    for rel in ("ptype_tpu/models/generate.py", "ptype_tpu/serve.py",
                "tests/t11.py", "examples/demo11.py"):
        findings = _check(tmp_path, rel, PT011_RAW_SAMPLING)
        assert not any("PT011" in f for f in findings), (rel, findings)


def test_pt011_ignores_non_sampling_random_apis(tmp_path):
    # fold_in/PRNGKey/uniform are key plumbing, not the acceptance
    # draws the rule guards; np.random-style .choice is unrelated.
    src = ("import jax\n"
           "import numpy as np\n"
           "def keys(seed, rng):\n"
           "    k = jax.random.fold_in(jax.random.PRNGKey(seed), 1)\n"
           "    u = jax.random.uniform(k, (4,))\n"
           "    return k, u, rng.choice(4)\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/ok11.py", src)
    assert not any("PT011" in f for f in findings), findings


def test_pt011_ignores_unrelated_receivers(tmp_path):
    # A bare name not bound to jax.random, a .gumbel attr on a
    # non-random base, and NON-jax `*.random` chains (np.random's
    # legacy sampling API) are not flagged — the rule guards the jax
    # RNG the exactness contract rides, conservatively.
    src = ("import numpy as np\n"
           "def f(rng, dist):\n"
           "    a = rng.categorical(3)\n"
           "    b = dist.gumbel()\n"
           "    c = np.random.gumbel()\n"
           "    return a, b, c\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sim11.py", src)
    assert not any("PT011" in f for f in findings), findings


def test_pt011_honors_noqa(tmp_path):
    src = ("import jax\n"
           "def pick(key, lg):\n"
           "    return jax.random.categorical(key, lg)  # noqa: ok\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sup11.py", src)
    assert not any("PT011" in f for f in findings), findings


def test_serve_engine_package_is_pt011_clean():
    """Every sampling draw behind the speculative path lives in
    models/generate.py's contract-tested helpers (ISSUE 12): no
    direct categorical/gumbel calls in serve_engine/."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu",
                       "serve_engine")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt011 = [f for f in findings if "PT011" in f]
    assert not pt011, pt011


# --------------------------------------------------------------- PT012


PT012_RAW_SERVER = (
    "from ptype_tpu.actor import ActorServer\n"
    "def up(actor):\n"
    "    s = ActorServer('127.0.0.1', 0)\n"
    "    s.register(actor, 'Generator')\n"
    "    return s.serve()\n")


def test_pt012_flags_direct_server_construction_in_package(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/sneaky_serve.py",
                      PT012_RAW_SERVER)
    assert sum("PT012" in f for f in findings) == 1, findings


def test_pt012_flags_attribute_form(tmp_path):
    src = ("from ptype_tpu import actor\n"
           "import ptype_tpu.actor as actor_mod\n"
           "def up():\n"
           "    a = actor.ActorServer('0.0.0.0', 0)\n"
           "    b = actor_mod.ActorServer('0.0.0.0', 0)\n"
           "    return a, b\n")
    findings = _check(tmp_path, "ptype_tpu/gateway/attr12.py", src)
    assert sum("PT012" in f for f in findings) == 2, findings


def test_pt012_silent_in_lifecycle_home_and_outside_package(tmp_path):
    # reconciler/ IS the home; serve.py is its actor library; tests,
    # examples, and bench build ad-hoc fleets deliberately.
    for rel in ("ptype_tpu/reconciler/replica.py",
                "ptype_tpu/reconciler/nested/deep.py",
                "ptype_tpu/serve.py",
                "tests/t12.py", "examples/fleet12.py", "bench.py"):
        findings = _check(tmp_path, rel, PT012_RAW_SERVER)
        assert not any("PT012" in f for f in findings), (rel, findings)


def test_pt012_ignores_non_construction_uses(tmp_path):
    # Type annotations, isinstance checks, and unrelated .ActorServer
    # attributes that are not CALLS stay silent — the rule flags
    # construction only.
    src = ("from ptype_tpu.actor import ActorServer\n"
           "def check(x) -> 'ActorServer | None':\n"
           "    if isinstance(x, ActorServer):\n"
           "        return x\n"
           "    return None\n")
    findings = _check(tmp_path, "ptype_tpu/ok12.py", src)
    assert not any("PT012" in f for f in findings), findings


def test_pt012_honors_noqa(tmp_path):
    src = ("from ptype_tpu.actor import ActorServer\n"
           "def up():\n"
           "    return ActorServer('127.0.0.1', 0)  # noqa: special\n")
    findings = _check(tmp_path, "ptype_tpu/sup12.py", src)
    assert not any("PT012" in f for f in findings), findings


def test_package_is_pt012_clean():
    """Replica lifecycle has one home (ISSUE 13): no direct
    ActorServer construction in ptype_tpu/ outside reconciler/ (the
    operator CLI's serve command rides reconciler.replica.serve_actor)."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt012 = [f for f in findings if "PT012" in f]
    assert not pt012, pt012


# --------------------------------------------------------------- PT021


PT021_RAW_WIRE = (
    "from ptype_tpu.parallel import collectives\n"
    "def ship(kb, bid, res):\n"
    "    w, r = collectives.quantize_leaf(kb[:, bid], 128, res)\n"
    "    blk = collectives.dequantize_leaf(w)\n"
    "    return blk, r\n")


def test_pt021_flags_raw_kv_wire_in_serve_engine(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sneak21.py",
                      PT021_RAW_WIRE)
    assert sum("PT021" in f for f in findings) == 2, findings


def test_pt021_flags_aliased_and_from_import_forms(tmp_path):
    src = ("import ptype_tpu.parallel.collectives as coll\n"
           "from ptype_tpu.parallel import collectives as cc\n"
           "from ptype_tpu.parallel.collectives import (\n"
           "    quantize_leaf as qz, dequantize_leaf)\n"
           "def ship(kb, res):\n"
           "    a = coll.quantize_leaf(kb, 128, res)\n"
           "    b = cc.dequantize_leaf(a)\n"
           "    c = qz(kb, 128, res)\n"
           "    d = dequantize_leaf(b)\n"
           "    return a, b, c, d\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/forms21.py",
                      src)
    assert sum("PT021" in f for f in findings) == 4, findings


def test_pt021_silent_in_migration_home_and_outside_serve_engine(
        tmp_path):
    # migrate.py IS the wire home; the training plane (parallel/,
    # train/) and tests use the codec legitimately.
    for rel in ("ptype_tpu/serve_engine/migrate.py",
                "ptype_tpu/parallel/zero.py", "ptype_tpu/train/loop.py",
                "tests/t21.py", "examples/demo21.py"):
        findings = _check(tmp_path, rel, PT021_RAW_WIRE)
        assert not any("PT021" in f for f in findings), (rel, findings)


def test_pt021_ignores_unrelated_receivers(tmp_path):
    # A quantize_leaf attr on a non-collectives base and an unbound
    # bare name are not flagged — the rule tracks the import alias,
    # conservatively.
    src = ("def f(codec, kb):\n"
           "    a = codec.quantize_leaf(kb, 128, None)\n"
           "    b = kb.dequantize_leaf()\n"
           "    return a, b\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sim21.py", src)
    assert not any("PT021" in f for f in findings), findings


def test_pt021_honors_noqa(tmp_path):
    src = ("from ptype_tpu.parallel import collectives\n"
           "def ship(kb, res):\n"
           "    return collectives.quantize_leaf(kb, 128, res)"
           "  # noqa: parity probe\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sup21.py", src)
    assert not any("PT021" in f for f in findings), findings


def test_serve_engine_package_is_pt021_clean():
    """KV wire serialization has one home (ISSUE 16): no codec calls
    in serve_engine/ outside migrate.py."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu",
                       "serve_engine")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt021 = [f for f in findings if "PT021" in f]
    assert not pt021, pt021


# --------------------------------------------------------------- PT022


PT022_SNEAKY_GATHER = (
    "from jax import lax\n"
    "def assemble(flat, scattered, store):\n"
    "    full = lax.all_gather(flat, 'data')\n"
    "    tree = scattered.gather()\n"
    "    leaf = store.pull('params/w', gather=True)\n"
    "    return full, tree, leaf\n")


def test_pt022_flags_ad_hoc_param_gather_in_train(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/train/sneak22.py",
                      PT022_SNEAKY_GATHER)
    assert sum("PT022" in f for f in findings) == 3, findings


def test_pt022_silent_in_zero_home_and_outside_train(tmp_path):
    # parallel/zero.py is the one sanctioned home; serve/ and tests
    # assemble trees for their own (non-ZeRO) reasons.
    for rel in ("ptype_tpu/parallel/zero.py",
                "ptype_tpu/parallel/collectives.py",
                "ptype_tpu/serve_engine/kv.py", "tests/t22.py",
                "examples/demo22.py"):
        findings = _check(tmp_path, rel, PT022_SNEAKY_GATHER)
        assert not any("PT022" in f for f in findings), (rel, findings)


def test_pt022_ignores_sanctioned_delegation(tmp_path):
    # gather_params() is the sanctioned API; pull without gather=True
    # and unrelated attrs stay silent.
    src = ("def params(self, store):\n"
           "    leaves = self._zero.gather_params()\n"
           "    w = store.pull('params/w')\n"
           "    g = store.pull('grads/b', gather=False)\n"
           "    return leaves, w, g\n")
    findings = _check(tmp_path, "ptype_tpu/train/ok22.py", src)
    assert not any("PT022" in f for f in findings), findings


def test_pt022_honors_noqa(tmp_path):
    src = ("from jax import lax\n"
           "def probe(flat):\n"
           "    return lax.all_gather(flat, 'data')"
           "  # noqa: parity probe\n")
    findings = _check(tmp_path, "ptype_tpu/train/sup22.py", src)
    assert not any("PT022" in f for f in findings), findings


def test_train_package_is_pt022_clean():
    """Full-tree param gather has one home (ISSUE 17): no ad-hoc
    allgather in train/ outside parallel/zero.py."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu",
                       "train")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt022 = [f for f in findings if "PT022" in f]
    assert not pt022, pt022


# --------------------------------------------------------------- PT023


PT023_FLAT_AXIS = (
    "from jax import lax\n"
    "from jax.sharding import PartitionSpec as P\n"
    "def f(x, mesh, store, axis_sizes):\n"
    "    a = lax.psum(x, 'data')\n"
    "    b = P('data')\n"
    "    store.push('k', x, axis='data')\n"
    "    n = mesh.shape['data']\n"
    "    m = axis_sizes['data']\n"
    "    return a, b, n, m\n")


def test_pt023_flags_flat_axis_literals_in_package(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/serve_engine/sneak23.py",
                      PT023_FLAT_AXIS)
    assert sum("PT023" in f for f in findings) == 5, findings


def test_pt023_flags_mesh_keys_and_defaults(tmp_path):
    src = ("from ptype_tpu.parallel.mesh import build_mesh\n"
           "def up(n, mesh_axis='data'):\n"
           "    return build_mesh({'data': n})\n")
    findings = _check(tmp_path, "ptype_tpu/train/geom23.py", src)
    assert sum("PT023" in f for f in findings) == 2, findings


def test_pt023_silent_in_parallel_home_and_outside_package(tmp_path):
    # parallel/ is the literal's one home (topology.DATA_AXIS lives
    # there); tests/examples/tools spell it freely.
    for rel in ("ptype_tpu/parallel/topology.py",
                "ptype_tpu/parallel/collectives.py",
                "tests/t23.py", "examples/demo23.py"):
        findings = _check(tmp_path, rel, PT023_FLAT_AXIS)
        assert not any("PT023" in f for f in findings), (rel, findings)


def test_pt023_ignores_non_axis_data_strings(tmp_path):
    # "data" as a payload key, profiler category, or message field is
    # not an axis name — only axis positions are flagged.
    src = ("def f(item, out, blob):\n"
           "    wal = item['data']\n"
           "    out['data'] = blob\n"
           "    return {'kind': 'x', 'data': blob}\n")
    findings = _check(tmp_path, "ptype_tpu/coord/ok23.py", src)
    assert not any("PT023" in f for f in findings), findings


def test_pt023_honors_noqa(tmp_path):
    src = ("from jax import lax\n"
           "def probe(x):\n"
           "    return lax.psum(x, 'data')"
           "  # noqa: parity probe\n")
    findings = _check(tmp_path, "ptype_tpu/train/sup23.py", src)
    assert not any("PT023" in f for f in findings), findings


def test_ptype_tpu_package_is_pt023_clean():
    """Axis-name discipline (ISSUE 18): no hard-coded flat "data"
    axis literals outside parallel/ — every module reads DATA_AXIS /
    topology.flat_axis / the owning object's .axis so programs ride
    the hierarchical mesh unchanged."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt023 = [f for f in findings if "PT023" in f]
    assert not pt023, pt023


# --------------------------------------------------------------- PT024


PT024_RAW_DRAWS = (
    "import random\n"
    "import numpy as np\n"
    "import numpy.random as npr\n"
    "from random import expovariate, shuffle\n"
    "def schedule(n):\n"
    "    ts = [random.random() for _ in range(n)]\n"      # 1
    "    ts.append(np.random.poisson(3.0))\n"             # 2
    "    ts.append(npr.uniform(0.0, 1.0))\n"              # 3
    "    ts.append(expovariate(2.0))\n"                   # 4
    "    shuffle(ts)\n"                                   # 5
    "    return ts\n"
)


def test_pt024_flags_raw_draws_in_loadgen(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/loadgen/bad24.py",
                      PT024_RAW_DRAWS)
    assert sum("PT024" in f for f in findings) == 5, findings


def test_pt024_silent_in_rng_home_and_outside_loadgen(tmp_path):
    # The seeded RNG home itself wraps stdlib Random — exempt; and
    # the rule is loadgen/-scoped, not package-wide.
    for rel in ("ptype_tpu/loadgen/rng.py",
                "ptype_tpu/serve/sampler24.py",
                "tools/gen24.py"):
        findings = _check(tmp_path, rel, PT024_RAW_DRAWS)
        assert not any("PT024" in f for f in findings), (rel, findings)


def test_pt024_silent_on_tracerng_draws(tmp_path):
    src = (
        "from ptype_tpu.loadgen.rng import TraceRng\n"
        "def schedule(seed, n):\n"
        "    rng = TraceRng(seed, salt='loadgen').fork('schedule')\n"
        "    return [rng.expovariate(2.0) for _ in range(n)]\n"
    )
    findings = _check(tmp_path, "ptype_tpu/loadgen/ok24.py", src)
    assert not any("PT024" in f for f in findings), findings


def test_ptype_tpu_package_is_pt024_clean():
    """Replay discipline (ISSUE 19): every traffic draw in loadgen/
    flows through the seeded TraceRng home, so a trace's seed is a
    complete replay recipe for the frontier and the spike drill."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt024 = [f for f in findings if "PT024" in f]
    assert not pt024, pt024


# ------------------------------------------------------------------ PT025


RAW_LATENCY = (
    "import time\n"
    "def call(self):\n"
    "    t0 = time.perf_counter()\n"
    "    do()\n"
    "    ms = (time.perf_counter() - t0) * 1e3\n"
)


def test_pt025_flags_adhoc_perf_counter_in_gateway(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/gateway/bad25.py",
                      RAW_LATENCY)
    assert any("PT025" in f for f in findings), findings


def test_pt025_flags_from_import_alias_in_serve_engine(tmp_path):
    src = ("from time import perf_counter as pc\n"
           "def step():\n"
           "    t0 = pc()\n")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/bad25.py",
                      src)
    assert any("PT025" in f for f in findings), findings


def test_pt025_exempts_the_stopwatch_home(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/gateway/slo.py",
                      RAW_LATENCY)
    assert not any("PT025" in f for f in findings), findings


def test_pt025_silent_outside_request_path_dirs(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/health/probe25.py",
                      RAW_LATENCY)
    assert not any("PT025" in f for f in findings), findings


def test_pt025_monotonic_deadline_math_is_legal(tmp_path):
    src = ("import time\n"
           "def call(self, deadline_s):\n"
           "    deadline = time.monotonic() + deadline_s\n"
           "    while time.monotonic() < deadline:\n"
           "        pass\n")
    findings = _check(tmp_path, "ptype_tpu/gateway/deadline.py", src)
    assert not any("PT025" in f for f in findings), findings


def test_pt025_honors_suppression(tmp_path):
    src = ("import time\n"
           "def call(self):\n"
           "    t0 = time.perf_counter()  # noqa: probe harness\n")
    findings = _check(tmp_path, "ptype_tpu/gateway/sup25.py", src)
    assert not any("PT025" in f for f in findings), findings


def test_ptype_tpu_package_is_pt025_clean():
    """Attribution has one home (ISSUE 20): every latency measurement
    in gateway/ rides the Stopwatch -> SLOTracker stage seam (and
    serve_engine/ the serving ledger), so the waterfall, exemplars,
    and stage budgets see every millisecond a private timer would
    have hidden."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ptype_tpu")
    findings = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                lint.check_file(os.path.join(dirpath, f), findings)
    pt025 = [f for f in findings if "PT025" in f]
    assert not pt025, pt025

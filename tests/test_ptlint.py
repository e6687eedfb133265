"""ptlint v2 unit tier: the PT013–PT017 passes (positive AND negative
fixtures per rule), the suppression machinery (``# ptlint: disable``
with justification, unused-suppression detection, legacy ``noqa``),
the PT001–PT012 migration golden test, JSON output, the
package-is-clean acceptance per new rule, and the ``make lint``
wall-time budget."""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))

import ptlint  # noqa: E402  (tools/ is not a package)

REPO = os.path.join(os.path.dirname(__file__), "..")


def _check(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    return [f.format() for f in ptlint.check_file_findings(str(p))]


def _codes(findings):
    return [f.split(": ", 2)[1].split(" ", 1)[0] for f in findings]


def _walk_pkg_findings():
    pkg = os.path.join(REPO, "ptype_tpu")
    findings: list[str] = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                ptlint.check_file(os.path.join(dirpath, f), findings)
    return findings


# ------------------------------------------------------------------ PT013


PT013_TOCTOU = (
    "import threading\n"
    "class Actor:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._draining = False\n"
    "    def drained(self):\n"
    "        with self._lock:\n"
    "            return self._draining\n"
    "    def begin_drain(self):\n"
    "        self._draining = True\n"          # bare write: the finding
)


def test_pt013_flags_guarded_here_bare_there(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/toctou.py", PT013_TOCTOU)
    assert any("PT013" in f and "_draining" in f for f in findings), \
        findings


def test_pt013_silent_when_always_guarded(tmp_path):
    src = PT013_TOCTOU.replace(
        "    def begin_drain(self):\n"
        "        self._draining = True\n",
        "    def begin_drain(self):\n"
        "        with self._lock:\n"
        "            self._draining = True\n")
    findings = _check(tmp_path, "ptype_tpu/ok13.py", src)
    assert not any("PT013" in f for f in findings), findings


def test_pt013_exempts_init_and_locked_suffix(tmp_path):
    src = (
        "import threading\n"
        "class Actor:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"                       # init write: exempt
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._bump_locked()\n"
        "    def _bump_locked(self):\n"
        "        self._n += 1\n"                      # caller holds it
    )
    findings = _check(tmp_path, "ptype_tpu/conv13.py", src)
    assert not any("PT013" in f for f in findings), findings


def test_pt013_exempts_constructor_only_helpers(tmp_path):
    src = (
        "import threading\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._rev = 0\n"
        "        self._replay()\n"
        "    def _replay(self):\n"
        "        self._rev = 7\n"       # happens-before publication
        "    def put(self):\n"
        "        with self._lock:\n"
        "            self._rev += 1\n"
    )
    findings = _check(tmp_path, "ptype_tpu/ctor13.py", src)
    assert not any("PT013" in f for f in findings), findings


def test_pt013_ignores_immutable_and_sync_attrs(tmp_path):
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self, cfg):\n"
        "        self.cfg = cfg\n"                    # never re-stored
        "        self._closed = threading.Event()\n"  # sync primitive
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def work(self):\n"
        "        with self._lock:\n"
        "            self._n += self.cfg.step\n"
        "            if self._closed.is_set():\n"
        "                return\n"
        "    def peek(self):\n"
        "        return (self.cfg.step, self._closed.is_set())\n"
    )
    findings = _check(tmp_path, "ptype_tpu/attrs13.py", src)
    assert not any("PT013" in f for f in findings), findings


def test_pt013_sees_condition_guards_and_closures(tmp_path):
    src = (
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self._items = []\n"
        "    def put(self, x):\n"
        "        with self._cond:\n"
        "            self._items.append(x)\n"
        "            self._items = list(self._items)\n"
        "    def spawn(self):\n"
        "        def run():\n"
        "            self._items = []\n"   # bare, on a thread body
        "        return run\n"
    )
    findings = _check(tmp_path, "ptype_tpu/cond13.py", src)
    assert any("PT013" in f and "spawn" in f for f in findings), findings


def test_pt013_silent_outside_package(tmp_path):
    findings = _check(tmp_path, "tests/t13.py", PT013_TOCTOU)
    assert not any("PT013" in f for f in findings), findings


def test_ptype_tpu_package_is_pt013_clean():
    """The sweep satellite: every PT013 the pass raises on the real
    tree is fixed or suppressed-with-justification."""
    found = [f for f in _walk_pkg_findings() if "PT013" in f]
    assert not found, found


# ------------------------------------------------------------------ PT014


def test_pt014_flags_sleep_and_dial_under_lock(tmp_path):
    src = (
        "import threading\n"
        "import time\n"
        "from ptype_tpu import rpc as rpc_mod\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def bad(self, node):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)\n"
        "            conn = rpc_mod._dial(node, 1.0)\n"
        "        return conn\n"
    )
    findings = _check(tmp_path, "ptype_tpu/blk14.py", src)
    assert sum("PT014" in f for f in findings) == 2, findings


def test_pt014_flags_event_wait_thread_join_subprocess(tmp_path):
    src = (
        "import subprocess\n"
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._closed = threading.Event()\n"
        "        self._thread = threading.Thread(target=print,\n"
        "                                        daemon=True)\n"
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            self._closed.wait(1.0)\n"
        "            self._thread.join(timeout=2)\n"
        "            subprocess.run(['true'])\n"
    )
    findings = _check(tmp_path, "ptype_tpu/blk14b.py", src)
    assert sum("PT014" in f for f in findings) == 3, findings


def test_pt014_allows_condition_wait_on_held_cond(tmp_path):
    src = (
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self._items = []\n"
        "    def get(self):\n"
        "        with self._cond:\n"
        "            while not self._items:\n"
        "                self._cond.wait(0.5)\n"   # the CV protocol
        "            return self._items.pop(0)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/cv14.py", src)
    assert not any("PT014" in f for f in findings), findings


def test_pt014_ignores_str_join_and_unlocked_calls(tmp_path):
    src = (
        "import threading\n"
        "import time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def ok(self, parts):\n"
        "        with self._lock:\n"
        "            label = ', '.join(parts)\n"    # not a thread join
        "        time.sleep(0.01)\n"                # outside the lock
        "        return label\n"
    )
    findings = _check(tmp_path, "ptype_tpu/ok14.py", src)
    assert not any("PT014" in f for f in findings), findings


def test_pt014_flags_chaos_seam_under_lock(tmp_path):
    src = (
        "import threading\n"
        "from ptype_tpu import chaos\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            f = chaos.hit('rpc.send', 'k')\n"
        "        return f\n"
    )
    findings = _check(tmp_path, "ptype_tpu/chaos14.py", src)
    assert any("PT014" in f and "chaos.hit" in f for f in findings), \
        findings


def test_ptype_tpu_package_is_pt014_clean():
    found = [f for f in _walk_pkg_findings() if "PT014" in f]
    assert not found, found


# ------------------------------------------------------------------ PT015


def test_pt015_flags_undaemonized_unjoined_thread(tmp_path):
    src = (
        "import threading\n"
        "class W:\n"
        "    def start(self):\n"
        "        self._thread = threading.Thread(target=print)\n"
        "        self._thread.start()\n"
    )
    findings = _check(tmp_path, "ptype_tpu/zombie15.py", src)
    assert any("PT015" in f for f in findings), findings


def test_pt015_passes_daemon_or_joined(tmp_path):
    src = (
        "import threading\n"
        "class W:\n"
        "    def start(self):\n"
        "        self._thread = threading.Thread(target=print,\n"
        "                                        daemon=True)\n"
        "        self._thread.start()\n"
        "class J:\n"
        "    def start(self):\n"
        "        self._thread = threading.Thread(target=print)\n"
        "        self._thread.start()\n"
        "    def close(self):\n"
        "        self._thread.join(timeout=5)\n"
        "class D:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=print)\n"
        "        self._t.daemon = True\n"
        "        self._t.start()\n"
    )
    findings = _check(tmp_path, "ptype_tpu/ok15.py", src)
    assert not any("PT015" in f for f in findings), findings


def test_pt015_passes_local_collection_join(tmp_path):
    src = (
        "import threading\n"
        "class P:\n"
        "    def round(self, items):\n"
        "        threads = []\n"
        "        for it in items:\n"
        "            t = threading.Thread(target=print, args=(it,))\n"
        "            threads.append(t)\n"
        "            t.start()\n"
        "        for t in threads:\n"
        "            t.join(timeout=1)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/pool15.py", src)
    assert not any("PT015" in f for f in findings), findings


def test_pt015_flags_fire_and_forget(tmp_path):
    src = (
        "import threading\n"
        "def kick():\n"
        "    threading.Thread(target=print).start()\n"
    )
    findings = _check(tmp_path, "ptype_tpu/fire15.py", src)
    assert any("PT015" in f for f in findings), findings


def test_ptype_tpu_package_is_pt015_clean():
    found = [f for f in _walk_pkg_findings() if "PT015" in f]
    assert not found, found


# ------------------------------------------------------------------ PT016


PT016_READ_AFTER_DONATE = (
    "import jax\n"
    "def build(step):\n"
    "    return jax.jit(step, donate_argnums=(1,))\n"
    "class E:\n"
    "    def __init__(self, step):\n"
    "        self._step = jax.jit(step, donate_argnums=(1,))\n"
    "    def run(self, params, bank, tok):\n"
    "        out = self._step(params, bank, tok)\n"
    "        return out, bank.sum()\n"      # bank was donated
)


def test_pt016_flags_read_after_donate(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/don16.py",
                      PT016_READ_AFTER_DONATE)
    assert any("PT016" in f and "'bank'" in f for f in findings), \
        findings


def test_pt016_passes_rebinding_idiom(tmp_path):
    src = PT016_READ_AFTER_DONATE.replace(
        "        out = self._step(params, bank, tok)\n"
        "        return out, bank.sum()\n",
        "        bank, out = self._step(params, bank, tok)\n"
        "        return out, bank.sum()\n")
    findings = _check(tmp_path, "ptype_tpu/ok16.py", src)
    assert not any("PT016" in f for f in findings), findings


def test_pt016_silent_without_donation(tmp_path):
    src = (
        "import jax\n"
        "class E:\n"
        "    def __init__(self, step):\n"
        "        self._step = jax.jit(step)\n"
        "    def run(self, params, bank):\n"
        "        out = self._step(params, bank)\n"
        "        return out, bank.sum()\n"
    )
    findings = _check(tmp_path, "ptype_tpu/nod16.py", src)
    assert not any("PT016" in f for f in findings), findings


def test_pt016_tracks_subscript_args(tmp_path):
    src = (
        "import jax\n"
        "class E:\n"
        "    def __init__(self, step):\n"
        "        self._step = jax.jit(step, donate_argnums=(0,))\n"
        "    def run(self, d):\n"
        "        out = self._step(d['kb'])\n"
        "        return out + d['kb']\n"
    )
    findings = _check(tmp_path, "ptype_tpu/sub16.py", src)
    assert any("PT016" in f for f in findings), findings


def test_ptype_tpu_package_is_pt016_clean():
    found = [f for f in _walk_pkg_findings() if "PT016" in f]
    assert not found, found


# ------------------------------------------------------------------ PT017


def test_pt017_flags_key_reuse(tmp_path):
    src = (
        "import jax\n"
        "def sample(key, logits):\n"
        "    a = jax.random.uniform(key, (4,))\n"
        "    b = jax.random.normal(key, (4,))\n"     # same key again
        "    return a, b\n"
    )
    findings = _check(tmp_path, "ptype_tpu/reuse17.py", src)
    assert sum("PT017" in f for f in findings) == 1, findings


def test_pt017_passes_split_rebind(tmp_path):
    src = (
        "import jax\n"
        "def sample(key, logits):\n"
        "    a = jax.random.uniform(key, (4,))\n"
        "    key, sub = jax.random.split(key)\n"     # rebound: fresh
        "    b = jax.random.normal(key, (4,))\n"
        "    c = jax.random.normal(sub, (4,))\n"
        "    return a, b, c\n"
    )
    findings = _check(tmp_path, "ptype_tpu/split17.py", src)
    assert not any("PT017" in f for f in findings), findings


def test_pt017_passes_fold_in_streams(tmp_path):
    src = (
        "import jax\n"
        "def rows(key, n):\n"
        "    out = []\n"
        "    for i in range(n):\n"
        "        k = jax.random.fold_in(key, i)\n"
        "        out.append(jax.random.uniform(k, ()))\n"
        "    return out\n"
    )
    findings = _check(tmp_path, "ptype_tpu/fold17.py", src)
    assert not any("PT017" in f for f in findings), findings


def test_pt017_tracks_alias_and_from_import_forms(tmp_path):
    src = (
        "import jax.random as jr\n"
        "from jax.random import gumbel\n"
        "def pick(key):\n"
        "    a = jr.categorical(key, None)\n"
        "    b = gumbel(key, (2,))\n"
        "    return a, b\n"
    )
    findings = _check(tmp_path, "ptype_tpu/alias17.py", src)
    assert sum("PT017" in f for f in findings) == 1, findings


def test_pt017_scopes_per_function(tmp_path):
    src = (
        "import jax\n"
        "def a(key):\n"
        "    return jax.random.uniform(key, ())\n"
        "def b(key):\n"
        "    return jax.random.uniform(key, ())\n"
    )
    findings = _check(tmp_path, "ptype_tpu/scope17.py", src)
    assert not any("PT017" in f for f in findings), findings


def test_ptype_tpu_package_is_pt017_clean():
    found = [f for f in _walk_pkg_findings() if "PT017" in f]
    assert not found, found


# ------------------------------------------------- suppression machinery


def test_ptlint_disable_suppresses_with_justification(tmp_path):
    src = (
        "import threading\n"
        "class Actor:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._draining = False\n"
        "    def drained(self):\n"
        "        with self._lock:\n"
        "            return self._draining\n"
        "    def begin_drain(self):\n"
        "        self._draining = True"
        "  # ptlint: disable=PT013 -- single writer thread\n"
    )
    findings = _check(tmp_path, "ptype_tpu/sup.py", src)
    assert not findings, findings


def test_ptlint_disable_without_justification_is_a_finding(tmp_path):
    src = PT013_TOCTOU.replace(
        "        self._draining = True\n",
        "        self._draining = True  # ptlint: disable=PT013\n")
    findings = _check(tmp_path, "ptype_tpu/nojust.py", src)
    codes = _codes(findings)
    assert "PTL002" in codes and "PT013" not in codes, findings


def test_unused_suppression_is_a_finding(tmp_path):
    src = ("def f(x):\n"
           "    return x  # ptlint: disable=PT014 -- no such thing\n")
    findings = _check(tmp_path, "ptype_tpu/stale.py", src)
    assert _codes(findings) == ["PTL001"], findings


def test_quoted_directive_in_docstring_is_prose(tmp_path):
    src = ('"""Docs: write `# ptlint: disable=PT013 -- why` to '
           'suppress."""\n'
           "X = 1\n")
    findings = _check(tmp_path, "ptype_tpu/prose.py", src)
    assert not findings, findings


def test_legacy_noqa_still_honored(tmp_path):
    src = PT013_TOCTOU.replace(
        "        self._draining = True\n",
        "        self._draining = True  # noqa: single writer\n")
    findings = _check(tmp_path, "ptype_tpu/noqa13.py", src)
    assert not any("PT013" in f for f in findings), findings


def test_repo_has_no_unjustified_suppressions():
    """Acceptance: zero un-justified suppressions anywhere ptlint
    runs (PTL002 would fire on them — and the full run is clean)."""
    findings, n = ptlint.run_paths([
        os.path.join(REPO, "ptype_tpu"), os.path.join(REPO, "tools")])
    bad = [f for f in findings if f.code in ("PTL001", "PTL002")]
    assert n > 0 and not bad, bad


# ------------------------------------------------ PT001–PT012 migration


GOLDEN_TREE = {
    # One fixture per migrated rule; expected (line, code) pins the
    # old tools/lint.py walker's behavior through the registry rebase.
    "train/leaf.py": (
        "def f(store, leaves):\n"
        "    for leaf in leaves:\n"
        "        store.push('k', leaf)\n",
        [(3, "PT001")]),
    "ptype_tpu/sleepy.py": (
        "import time\n"
        "def f(ready):\n"
        "    while not ready():\n"
        "        time.sleep(0.2)\n",
        [(4, "PT002")]),
    "ptype_tpu/bypass.py": (
        "def serve(cluster):\n"
        "    return cluster.new_client('llm')\n",
        [(2, "PT003")]),
    "ptype_tpu/noisy.py": (
        "def f(x):\n"
        "    print('dbg', x)\n",
        [(2, "PT004")]),
    "ptype_tpu/fam.py": (
        "def make():\n"
        "    return Counter('hits')\n",
        [(2, "PT005")]),
    "ptype_tpu/parallel/cast.py": (
        "import jax.numpy as jnp\n"
        "def ship(x):\n"
        "    return x.astype(jnp.int8)\n",
        [(3, "PT006")]),
    "train/opt.py": (
        "def step(optimizer, params):\n"
        "    return optimizer.init(params)\n",
        [(2, "PT007")]),
    "ptype_tpu/prof.py": (
        "import jax\n"
        "def grab(d):\n"
        "    jax.profiler.start_trace(d)\n",
        [(3, "PT008")]),
    "ptype_tpu/bank.py": (
        "from ptype_tpu.models.generate import init_cache\n"
        "def build(cfg):\n"
        "    return init_cache(cfg, 8)\n",
        [(3, "PT009")]),
    "ptype_tpu/serve_engine/stamp.py": (
        "import time\n"
        "def t():\n"
        "    return time.perf_counter()\n",
        # PT025 (tail forensics) overlaps PT010's domain by design:
        # an engine-side perf_counter is both a raw stamp and an
        # unattributed latency measurement.
        [(3, "PT010"), (3, "PT025")]),
    "ptype_tpu/serve_engine/draw.py": (
        "import jax\n"
        "def pick(key, lg):\n"
        "    return jax.random.categorical(key, lg)\n",
        [(3, "PT011")]),
    "ptype_tpu/sneaky.py": (
        "from ptype_tpu.actor import ActorServer\n"
        "def up():\n"
        "    return ActorServer('127.0.0.1', 0)\n",
        [(3, "PT012")]),
    "ptype_tpu/style.py": (
        "import os\n"                       # unused -> F401
        "def f(x, acc=[]):\n"               # B006
        "    if x == None:\n"               # E711
        "        return f''\n"              # F541
        "    try:\n"
        "        return x\n"
        "    except:\n"                     # E722
        "        pass\n",
        # No F821 fixture: an unbound load reads as an implicit
        # GLOBAL to symtable, which the pass (old and new alike)
        # deliberately skips — module dicts are dynamic.
        [(1, "F401"), (2, "B006"), (3, "E711"),
         (4, "F541"), (7, "E722")]),
}


def test_golden_migration_pt001_pt012(tmp_path):
    """The registry rebase is behavior-preserving: the fixture tree
    produces exactly the (line, code) set the monolithic walker
    produced (the PT017 key-free fixtures keep the new passes out of
    frame)."""
    for rel, (src, expected) in GOLDEN_TREE.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
        got = sorted(
            (f.line, f.code)
            for f in ptlint.check_file_findings(str(p)))
        assert got == sorted(expected), (rel, got, expected)


# --------------------------------------------------- CLI / JSON / budget


def test_json_output_shape(tmp_path):
    p = tmp_path / "ptype_tpu" / "j.py"
    p.parent.mkdir(parents=True)
    p.write_text("def f(x):\n    print(x)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.ptlint", "--json", str(p)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    out = json.loads(proc.stdout)
    assert out and out[0]["code"] == "PT004"
    assert set(out[0]) == {"path", "line", "code", "message"}


def test_make_lint_tier_exits_clean():
    """The tier-1 CI seam: ptlint over the ``make lint`` surface exits
    0 (no wall-clock assertion: a loaded host is not a lint finding;
    the subprocess timeout bounds a hung linter)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.ptlint", "ptype_tpu", "tools",
         "tests", "examples", "chip_smoke.py", "__graft_entry__.py"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_pt015_join_in_another_method_does_not_reach_local_thread(
        tmp_path):
    """A bare-name join in some OTHER method must not exempt a local
    fire-and-forget thread (the loose-fallback hole: `for t in
    self._threads: t.join()` in drain() says nothing about the `h`
    born in kick())."""
    src = (
        "import threading\n"
        "class W:\n"
        "    def kick(self):\n"
        "        h = threading.Thread(target=print)\n"
        "        h.start()\n"
        "    def drain(self):\n"
        "        for t in self._threads:\n"
        "            t.join(timeout=1)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/hole15.py", src)
    assert any("PT015" in f for f in findings), findings


# ------------------------------------------------------------------ PT018


PT018_HOT_SYNC = (
    "import jax.numpy as jnp\n"
    "class E:\n"
    "    def run(self, xs):\n"
    "        outs = []\n"
    "        for x in xs:\n"
    "            y = jnp.dot(x, x)\n"
    "            outs.append(float(y[0]))\n"   # device read per iter
    "        return outs\n"
)


def test_pt018_flags_device_read_in_loop(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/serve_engine/hot18.py",
                      PT018_HOT_SYNC)
    assert any("PT018" in f and "float(y[0])" in f for f in findings), \
        findings


def test_pt018_flags_item_and_device_get(tmp_path):
    src = (
        "import jax\n"
        "from jax import device_get as dg\n"
        "def drain(vals):\n"
        "    total = 0.0\n"
        "    for v in vals:\n"
        "        total += v.item()\n"
        "        dg(v)\n"
        "    return total\n"
    )
    findings = _check(tmp_path, "ptype_tpu/train/sync18.py", src)
    assert sum("PT018" in f for f in findings) == 2, findings


def test_pt018_silent_on_host_mirrors(tmp_path):
    """The engine idiom: np-assigned host state indexed in loops is
    NOT a device sync — the false-positive-free charter."""
    src = (
        "import numpy as np\n"
        "class E:\n"
        "    def step(self, nxt, slots):\n"
        "        nxt_host = np.array(nxt)\n"
        "        out = []\n"
        "        for s in slots:\n"
        "            out.append(int(nxt_host[s]))\n"
        "        return out\n"
    )
    findings = _check(tmp_path, "ptype_tpu/serve_engine/ok18.py", src)
    assert not any("PT018" in f for f in findings), findings


def test_pt018_flags_np_asarray_of_jit_result(tmp_path):
    src = (
        "import jax\n"
        "import numpy as np\n"
        "class E:\n"
        "    def __init__(self, f):\n"
        "        self._step = jax.jit(f)\n"
        "    def run(self, xs):\n"
        "        outs = []\n"
        "        for x in xs:\n"
        "            y = self._step(x)\n"
        "            outs.append(np.asarray(y))\n"
        "        return outs\n"
    )
    findings = _check(tmp_path, "ptype_tpu/models/jit18.py", src)
    assert any("PT018" in f and "np.asarray(y)" in f
               for f in findings), findings


def test_pt018_sanctioned_meter_seams_are_exempt(tmp_path):
    src = PT018_HOT_SYNC.replace("def run(", "def measure_run(")
    findings = _check(tmp_path, "ptype_tpu/serve_engine/meter18.py",
                      src)
    assert not any("PT018" in f for f in findings), findings


def test_pt018_silent_outside_hot_modules(tmp_path):
    findings = _check(tmp_path, "ptype_tpu/gateway/cool18.py",
                      PT018_HOT_SYNC)
    assert not any("PT018" in f for f in findings), findings


def test_ptype_tpu_package_is_pt018_clean():
    found = [f for f in _walk_pkg_findings() if "PT018" in f]
    assert not found, found


# ------------------------------------------------------------------ PT019


def test_pt019_flags_jit_of_lambda_per_call(tmp_path):
    src = (
        "import jax\n"
        "class E:\n"
        "    def step(self, x):\n"
        "        return jax.jit(lambda v: v * 2)(x)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/lam19.py", src)
    # ONE defect, ONE finding: the construct-and-call branch covers
    # the inner lambda-jit — no double count on the same expression.
    assert sum("PT019" in f for f in findings) == 1, findings


def test_pt019_flags_jit_in_loop_and_local_closure(tmp_path):
    src = (
        "import jax\n"
        "class E:\n"
        "    def rebuild(self, shapes, cfg):\n"
        "        progs = []\n"
        "        for s in shapes:\n"
        "            progs.append(jax.jit(self._fwd))\n"
        "        return progs\n"
        "    def score(self, x, cfg):\n"
        "        def fwd(v):\n"
        "            return v @ cfg.w\n"
        "        return jax.jit(fwd)(x)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/loop19.py", src)
    assert sum("PT019" in f for f in findings) >= 2, findings


def test_pt019_passes_init_builder_and_module_scope(tmp_path):
    src = (
        "import jax\n"
        "def _top(v):\n"
        "    return v + 1\n"
        "TOP = jax.jit(_top)\n"              # module scope: cached
        "class E:\n"
        "    def __init__(self, f, shapes):\n"
        "        self._step = jax.jit(lambda v: f(v))\n"
        "        self._progs = [jax.jit(f) for _ in shapes]\n"
        "    def _chunk_prog(self, C):\n"     # memoized builder idiom
        "        def run(p, t):\n"
        "            return p @ t\n"
        "        return jax.jit(run)\n"
        "def measure_push(f, x):\n"           # one-shot probe seam
        "    return jax.jit(lambda v: f(v))(x)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/ok19.py", src)
    assert not any("PT019" in f for f in findings), findings


def test_pt019_tracks_from_import_alias(tmp_path):
    src = (
        "from jax import jit as J\n"
        "class E:\n"
        "    def step(self, x):\n"
        "        return J(lambda v: v * 2)(x)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/alias19.py", src)
    assert any("PT019" in f for f in findings), findings


def test_ptype_tpu_package_is_pt019_clean():
    found = [f for f in _walk_pkg_findings() if "PT019" in f]
    assert not found, found


# ------------------------------------------------------------------ PT020


def test_pt020_flags_dtypeless_and_explicit_f64(tmp_path):
    src = (
        "import numpy as np\n"
        "def build(x):\n"
        "    a = np.zeros(4)\n"                     # dtype-less ctor
        "    b = np.array([0.5, 1.5])\n"            # float literals
        "    c = np.float64(x)\n"                   # explicit f64
        "    d = x.astype(np.float64)\n"            # f64 cast
        "    e = np.ones(3, dtype=np.float64)\n"    # f64 dtype kw
        "    return a, b, c, d, e\n"
    )
    findings = _check(tmp_path, "ptype_tpu/parallel/drift20.py", src)
    assert sum("PT020" in f for f in findings) == 5, findings


def test_pt020_passes_named_dtypes_and_int_literals(tmp_path):
    src = (
        "import numpy as np\n"
        "def build(rows, nb):\n"
        "    a = np.zeros((rows, nb), np.int32)\n"   # positional dtype
        "    b = np.ones(rows, np.float32)\n"
        "    c = np.full((2, 2), 7, np.int32)\n"
        "    d = np.array([1, 2, 3])\n"              # int literals ok
        "    e = np.asarray(a, dtype=np.float32)\n"
        "    return a, b, c, d, e\n"
    )
    findings = _check(tmp_path, "ptype_tpu/serve_engine/ok20.py", src)
    assert not any("PT020" in f for f in findings), findings


def test_pt020_tracks_numpy_alias(tmp_path):
    src = (
        "import numpy as N\n"
        "def build():\n"
        "    return N.zeros(4)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/models/alias20.py", src)
    assert any("PT020" in f for f in findings), findings


def test_pt020_silent_outside_device_adjacent_dirs(tmp_path):
    src = (
        "import numpy as np\n"
        "def build():\n"
        "    return np.zeros(4)\n"
    )
    findings = _check(tmp_path, "ptype_tpu/cool20.py", src)
    assert not any("PT020" in f for f in findings), findings


def test_ptype_tpu_package_is_pt020_clean():
    found = [f for f in _walk_pkg_findings() if "PT020" in f]
    assert not found, found

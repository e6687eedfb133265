"""PT001–PT012 (plus PT021–PT024): the house rules.

PT001–PT012 were migrated from tools/lint.py; each rule guards one
architectural seam this repo earned the hard way (the full rationale
per rule lives in docs/LINTING.md). Migration is behavior-preserving:
the golden-output test in tests/test_ptlint.py pins these against the
old walker's findings on a fixture tree. PT021 (KV wire serialization
outside the migration home, ISSUE 16) joins them here because it is
the same single-home family as PT008/PT011; PT022 (full-tree param
allgather in ``train/``, ISSUE 17) extends that family to the ZeRO-3
residency contract; PT023 (hard-coded flat ``"data"`` axis names
outside ``parallel/``, ISSUE 18) extends it to the topology plane's
axis-name discipline; PT024 (raw ``random.*``/``np.random.*`` draws
in ``loadgen/`` outside the seeded RNG home, ISSUE 19) extends it to
the traffic plane's replay discipline.
"""

from __future__ import annotations

import ast

from .core import FileContext, Finding, rule
from .scopes import ContextWalker, terminal_name

# --------------------------------------------------------------- PT001

#: Method/function names that dispatch one eager collective per call.
_EAGER_COLLECTIVES = frozenset({
    "push", "push_scatter", "all_reduce", "all_gather",
    "reduce_scatter", "quantized_all_reduce",
    "quantized_reduce_scatter", "all_to_all", "ring_shift",
})


class _PerLeafCollectiveCheck(ast.NodeVisitor):
    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.loop_depth = 0

    def _loop(self, node) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_AsyncFor = visit_While = _loop
    visit_ListComp = visit_SetComp = _loop
    visit_DictComp = visit_GeneratorExp = _loop

    def visit_Call(self, node: ast.Call) -> None:
        name = terminal_name(node.func)
        if self.loop_depth and name in _EAGER_COLLECTIVES:
            self.findings.append(self.ctx.finding(
                node, "PT001",
                f"eager collective {name!r} called in a per-leaf "
                f"loop; bucket it (TensorStore.push_tree / "
                f"collectives.tree_all_reduce)"))
        self.generic_visit(node)


@rule("PT001", "eager collective in a per-leaf loop (train/ only)",
      applies=lambda ctx: ctx.in_dir("train"))
def check_pt001(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _PerLeafCollectiveCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT002


class _SleepInLoopCheck(ast.NodeVisitor):
    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.loop_depth = 0

    def _loop(self, node) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_AsyncFor = visit_While = _loop

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if (self.loop_depth
                and isinstance(fn, ast.Attribute) and fn.attr == "sleep"
                and isinstance(fn.value, ast.Name)
                and fn.value.id in ("time", "_time")):
            self.findings.append(self.ctx.finding(
                node, "PT002",
                "bare time.sleep in a loop; use ptype_tpu.retry."
                "Backoff (jittered, capped) or an Event.wait deadline"))
        self.generic_visit(node)


@rule("PT002", "bare time.sleep in a loop (retry.py is the sleeper)",
      applies=lambda ctx: ctx.in_pkg and ctx.basename != "retry.py")
def check_pt002(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _SleepInLoopCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT003

_GATED_SERVICES = frozenset({"llm"})


@rule("PT003", "direct new_client('llm') bypasses the gateway",
      applies=lambda ctx: ctx.in_pkg and not ctx.in_dir("gateway"))
def check_pt003(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = terminal_name(node.func)
        if (name == "new_client" and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value in _GATED_SERVICES):
            findings.append(ctx.finding(
                node, "PT003",
                f"direct new_client({node.args[0].value!r}) bypasses "
                f"the inference gateway (admission control, shedding, "
                f"load-aware routing); use gateway.InferenceGateway "
                f"or a GatewayActor service"))
    return findings


# --------------------------------------------------------------- PT004


@rule("PT004", "bare print() in framework code",
      applies=lambda ctx: ctx.in_pkg and ctx.basename != "__main__.py")
def check_pt004(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            findings.append(ctx.finding(
                node, "PT004",
                "bare print() in framework code; use logs.get_logger "
                "(trace-correlated kv logging) or a trace span event"))
    return findings


# --------------------------------------------------------------- PT005

_METRIC_FAMILIES = frozenset({"Counter", "Timing", "Gauge", "Histogram"})
_METRICS_ALIASES = frozenset({"metrics", "metrics_mod"})


@rule("PT005", "metric family constructed outside MetricsRegistry",
      applies=lambda ctx: ctx.in_pkg and ctx.basename != "metrics.py")
def check_pt005(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = None
        if isinstance(fn, ast.Name) and fn.id in _METRIC_FAMILIES:
            name = fn.id
        elif (isinstance(fn, ast.Attribute)
              and fn.attr in _METRIC_FAMILIES
              and isinstance(fn.value, ast.Name)
              and fn.value.id in _METRICS_ALIASES):
            name = fn.attr
        if name is not None:
            findings.append(ctx.finding(
                node, "PT005",
                f"direct {name}() construction bypasses the "
                f"MetricsRegistry — the health sampler can't see it "
                f"(no series, no alerts); use "
                f"registry.{name.lower()}(name)"))
    return findings


# --------------------------------------------------------------- PT006

_QUANT_HELPER_PREFIXES = ("_q_", "quantize", "dequantize")


def _is_int8_arg(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant):
        return node.value == "int8"
    if isinstance(node, ast.Attribute) and node.attr == "int8":
        return True
    if (isinstance(node, ast.Call) and node.args
            and isinstance(node.args[0], ast.Constant)):
        return node.args[0].value == "int8"
    return False


class _RawInt8CastCheck(ContextWalker):
    def __init__(self, ctx, findings):
        super().__init__()
        self.ctx = ctx
        self.findings = findings

    def _sanctioned(self) -> bool:
        return any(name.startswith(_QUANT_HELPER_PREFIXES)
                   for name in self.fn_stack)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        dtype_args = list(node.args[:1]) + [
            kw.value for kw in node.keywords if kw.arg == "dtype"]
        if (isinstance(fn, ast.Attribute) and fn.attr == "astype"
                and any(_is_int8_arg(a) for a in dtype_args)
                and not self._sanctioned()):
            self.findings.append(self.ctx.finding(
                node, "PT006",
                "raw .astype(int8) narrowing outside the quantize "
                "helpers — an unscaled int8 cast destroys gradients "
                "(saturation + underflow); use collectives."
                "_q_int8_blockwise / quantize_leaf, which carry "
                "per-block absmax scales"))
        self.generic_visit(node)


@rule("PT006", "raw int8 cast outside the quantize helpers",
      applies=lambda ctx: ctx.in_pkg and ctx.in_dir("parallel"))
def check_pt006(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _RawInt8CastCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT007

_OPT_INIT_SANCTIONED = ("__init__", "init_", "_init")


class _FullTreeOptStateCheck(ContextWalker):
    def __init__(self, ctx, findings):
        super().__init__()
        self.ctx = ctx
        self.findings = findings

    def _sanctioned(self) -> bool:
        return any(name.startswith(_OPT_INIT_SANCTIONED)
                   for name in self.fn_stack)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if (isinstance(fn, ast.Attribute) and fn.attr == "init"
                and not self._sanctioned()):
            recv = terminal_name(fn.value)
            if recv is not None and (
                    "optimizer" in recv.lower()
                    or recv in ("opt", "_opt")):
                self.findings.append(self.ctx.finding(
                    node, "PT007",
                    f"full-tree optimizer state constructed outside "
                    f"the init helpers ({recv}.init) — replicated "
                    f"moments cap trainable model size; hot paths "
                    f"must use the sharded state (parallel/zero."
                    f"ZeroState, 1/N per replica) or the per-bucket "
                    f"states the init helpers set up"))
        self.generic_visit(node)


@rule("PT007", "full-tree optimizer.init outside init helpers",
      applies=lambda ctx: ctx.in_dir("train"))
def check_pt007(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _FullTreeOptStateCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT008


class _RawProfilerTraceCheck(ast.NodeVisitor):
    _VERBS = frozenset({"start_trace", "stop_trace"})

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.from_profiler: set[str] = set()

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module and node.module.endswith("profiler"):
            for a in node.names:
                if a.name in self._VERBS:
                    self.from_profiler.add(a.asname or a.name)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        hit = None
        if (isinstance(fn, ast.Attribute) and fn.attr in self._VERBS
                and isinstance(fn.value, ast.Attribute)
                and fn.value.attr == "profiler"):
            hit = fn.attr            # jax.profiler.start_trace(...)
        elif (isinstance(fn, ast.Attribute) and fn.attr in self._VERBS
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "profiler"):
            hit = fn.attr            # from jax import profiler
        elif (isinstance(fn, ast.Name)
                and fn.id in self.from_profiler):
            hit = fn.id              # from jax.profiler import ...
        if hit is not None:
            self.findings.append(self.ctx.finding(
                node, "PT008",
                f"raw jax.profiler.{hit} — the profiler is "
                f"process-global and this call races the managed "
                f"capture plane; go through health/profiling.py "
                f"(start/stop/capture or the ptype.Profile endpoint)"))
        self.generic_visit(node)


@rule("PT008", "raw jax.profiler start/stop outside the managed seam",
      applies=lambda ctx: ctx.in_pkg and ctx.basename != "profiling.py")
def check_pt008(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _RawProfilerTraceCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT009


@rule("PT009", "raw init_cache bank outside serve_engine/models",
      applies=lambda ctx: (ctx.in_pkg
                           and not ctx.in_dir("serve_engine")
                           and not ctx.in_dir("models")))
def check_pt009(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and terminal_name(node.func) == "init_cache"):
            findings.append(ctx.finding(
                node, "PT009",
                "raw init_cache full-reach bank allocation in "
                "serving code — resident KV must come from the paged "
                "block pool (serve_engine.BlockPool: ref-counted "
                "blocks, prefix reuse, LRU eviction), not a "
                "contiguous n_slots×reach bank"))
    return findings


# --------------------------------------------------------------- PT010


class _RawTimerCheck(ast.NodeVisitor):
    _VERBS = frozenset({"perf_counter", "time"})

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.mods: set[str] = set()
        self.funcs: dict[str, str] = {}

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name == "time":
                self.mods.add(a.asname or "time")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "time":
            for a in node.names:
                if a.name in self._VERBS:
                    self.funcs[a.asname or a.name] = a.name
        self.generic_visit(node)

    def _flag(self, node: ast.Call, verb: str) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT010",
            f"raw time.{verb} in serve_engine/ — engine latency "
            f"stamps must ride the serving ledger's seams "
            f"(health/serving.py: ingress/enqueued/head_refused/admitted/"
            f"chunk/first_token/tokens_emitted/iteration/retired), "
            f"the one timing home the histograms, span tree, and "
            f"seam-cost probe all derive from"))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if (isinstance(fn, ast.Attribute) and fn.attr in self._VERBS
                and isinstance(fn.value, ast.Name)
                and fn.value.id in (self.mods or {"time", "_time"})):
            self._flag(node, fn.attr)
        elif isinstance(fn, ast.Name) and fn.id in self.funcs:
            self._flag(node, self.funcs[fn.id])
        self.generic_visit(node)


@rule("PT010", "raw wall-clock reads beside the serving ledger",
      applies=lambda ctx: ctx.in_pkg and ctx.in_dir("serve_engine"))
def check_pt010(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _RawTimerCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT011


class _RawSamplingCheck(ast.NodeVisitor):
    _VERBS = frozenset({"categorical", "gumbel"})

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.rand_mods: set[str] = set()
        self.funcs: dict[str, str] = {}

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name == "jax.random" and a.asname:
                self.rand_mods.add(a.asname)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "jax":
            for a in node.names:
                if a.name == "random":
                    self.rand_mods.add(a.asname or "random")
        elif node.module == "jax.random":
            for a in node.names:
                if a.name in self._VERBS:
                    self.funcs[a.asname or a.name] = a.name
        self.generic_visit(node)

    def _flag(self, node: ast.Call, verb: str) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT011",
            f"direct jax.random.{verb} sampling in serve_engine/ — "
            f"acceptance sampling has one RNG home (models/generate."
            f"py: sample_token_rows/draft_propose_paged/"
            f"spec_accept_rows, the contract-tested helpers); a raw "
            f"draw here silently rots the exact-distribution "
            f"contract"))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in self._VERBS:
            base = fn.value
            if (isinstance(base, ast.Attribute)
                    and base.attr == "random"
                    and isinstance(base.value, ast.Name)
                    and base.value.id == "jax"):
                self._flag(node, fn.attr)   # jax.random.categorical
            elif (isinstance(base, ast.Name)
                    and base.id in self.rand_mods):
                self._flag(node, fn.attr)   # random.categorical / jr.
        elif isinstance(fn, ast.Name) and fn.id in self.funcs:
            self._flag(node, self.funcs[fn.id])
        self.generic_visit(node)


@rule("PT011", "ad-hoc sampling draw beside the RNG home",
      applies=lambda ctx: ctx.in_pkg and ctx.in_dir("serve_engine"))
def check_pt011(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _RawSamplingCheck(ctx, findings).visit(ctx.tree)
    return findings


# --------------------------------------------------------------- PT012


@rule("PT012", "ActorServer built outside the replica-lifecycle home",
      applies=lambda ctx: (ctx.in_pkg
                           and not ctx.in_dir("reconciler")
                           and ctx.basename != "serve.py"))
def check_pt012(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and terminal_name(node.func) == "ActorServer"):
            findings.append(ctx.finding(
                node, "PT012",
                "direct ActorServer construction outside the "
                "replica-lifecycle home — the elastic reconciler can "
                "neither drain nor replace a replica it didn't "
                "build; construct through reconciler.replica."
                "serve_actor / ReplicaHost"))
    return findings


# --------------------------------------------------------------- PT021


class _KVWireCheck(ast.NodeVisitor):
    """KV wire serialization outside the migration home.

    ``quantize_leaf``/``dequantize_leaf`` are the int8+EF codec's only
    entry points; in ``serve_engine/`` they may appear in exactly ONE
    module — ``migrate.py``, the wire between serving classes. A
    second call site forks the wire format: its residual store and the
    migrator's drift apart, and the error-feedback contract (repeated
    transfers of the same block don't accumulate bias) silently
    breaks. Same single-home discipline PT008 applies to collectives
    and PT011 to sampling. Catches the direct call, the module-
    attribute form (``collectives.quantize_leaf`` under any alias),
    and aliased from-imports.
    """

    _VERBS = frozenset({"quantize_leaf", "dequantize_leaf"})

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.mods: set[str] = set()
        self.funcs: dict[str, str] = {}

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name == "ptype_tpu.parallel.collectives" and a.asname:
                self.mods.add(a.asname)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module in ("ptype_tpu.parallel", "ptype_tpu"):
            for a in node.names:
                if a.name == "collectives":
                    self.mods.add(a.asname or "collectives")
        elif node.module in ("ptype_tpu.parallel.collectives",
                             "ptype_tpu.serve_engine.migrate"):
            for a in node.names:
                if a.name in self._VERBS:
                    self.funcs[a.asname or a.name] = a.name
        self.generic_visit(node)

    def _flag(self, node: ast.Call, verb: str) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT021",
            f"{verb} on the serving path outside serve_engine/"
            f"migrate.py — KV wire serialization has ONE home (the "
            f"migration module); a second codec call site forks the "
            f"wire format and breaks the per-block error-feedback "
            f"contract (residuals keyed by chain hash, one store)"))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in self._VERBS:
            base = fn.value
            if isinstance(base, ast.Name) and base.id in (
                    self.mods or {"collectives"}):
                self._flag(node, fn.attr)  # collectives.quantize_leaf
            elif (isinstance(base, ast.Attribute)
                    and base.attr == "collectives"):
                self._flag(node, fn.attr)  # parallel.collectives.q...
        elif isinstance(fn, ast.Name) and fn.id in self.funcs:
            self._flag(node, self.funcs[fn.id])
        self.generic_visit(node)


@rule("PT021", "KV wire serialization outside the migration home",
      applies=lambda ctx: (ctx.in_pkg and ctx.in_dir("serve_engine")
                           and ctx.basename != "migrate.py"))
def check_pt021(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _KVWireCheck(ctx, findings).visit(ctx.tree)
    return findings


# ------------------------------------------------------------------ PT022


class _ParamGatherCheck(ast.NodeVisitor):
    """Flag full-tree param materialization inside ``train/``.

    The ZeRO-3 residency contract (ISSUE 17) keeps params resident as
    flat P(axis) shards; the ONLY place a full tree may be assembled
    is ``parallel/zero.py`` (``ZeroState.gather_params`` riding
    ``_bucket_gather_fn``).  Anything in ``train/`` that re-gathers —
    a raw ``all_gather``, an ad-hoc ``.gather()`` on a scattered
    handle, or ``pull(..., gather=True)`` against the store — forks
    that contract and silently reinflates per-replica memory back to
    the replicated footprint.  Delegating to the sanctioned API
    (``self._zero.gather_params()``) is fine and is not flagged.
    """

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings

    def _flag(self, node: ast.Call, what: str) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT022",
            f"{what} in train/ — full-tree param materialization has "
            f"ONE home (parallel/zero.py: ZeroState.gather_params / "
            f"_bucket_gather_fn); an ad-hoc gather here reinflates "
            f"per-replica memory to the replicated footprint and "
            f"dodges the zero3.param_gather progaudit pin"))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        name = terminal_name(fn)
        if name == "all_gather":
            self._flag(node, "all_gather")
        elif isinstance(fn, ast.Attribute) and fn.attr == "gather":
            self._flag(node, ".gather()")
        elif name == "pull":
            for kw in node.keywords:
                if (kw.arg == "gather"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True):
                    self._flag(node, "pull(gather=True)")
                    break
        self.generic_visit(node)


@rule("PT022", "full-tree param allgather outside the ZeRO-3 home",
      applies=lambda ctx: ctx.in_pkg and ctx.in_dir("train"))
def check_pt022(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _ParamGatherCheck(ctx, findings).visit(ctx.tree)
    return findings


# ------------------------------------------------------------------ PT023

#: Callables whose positional axis-name argument makes a ``"data"``
#: literal a flat-axis collective construction.
_AXIS_CALLABLES = frozenset({
    "psum", "pmean", "psum_scatter", "all_gather", "all_to_all",
    "ppermute", "axis_index", "axis_size", "axis_n",
    "PartitionSpec", "P",
})

#: Keyword names that carry an axis name anywhere in the package.
_AXIS_KWARGS = frozenset({"axis", "mesh_axis", "axis_name"})

#: Callables whose dict-literal argument is mesh geometry.
_MESH_BUILDERS = frozenset({"build_mesh", "local_mesh"})


def _is_data(node) -> bool:
    return isinstance(node, ast.Constant) and node.value == "data"


class _FlatAxisLiteralCheck(ast.NodeVisitor):
    """Hard-coded ``"data"`` axis names outside ``parallel/``.

    The topology plane (ISSUE 18) made the data axis a VALUE, not a
    name: on a hierarchical mesh the flat ``"data"`` axis becomes the
    composite ``("inner", "outer")`` tuple, and every module that
    spells the literal instead of reading ``DATA_AXIS`` /
    ``topology.flat_axis`` / the store's ``.axis`` silently builds a
    1-D program that cannot ride the hierarchical decomposition —
    shardings stop matching, collectives launch over an axis the mesh
    no longer has. ``parallel/`` is the literal's one home
    (``topology.DATA_AXIS`` is defined there); everywhere else the
    axis name must flow from the topology descriptor or the object
    that owns the mesh. Catches the kwarg form (``axis="data"``),
    positional axis names handed to collective/sharding callables
    (``psum(x, "data")``, ``P("data")``), mesh-geometry dict keys
    (``build_mesh({"data": n})``), axis-name parameter defaults, and
    axis-keyed subscripts (``mesh.shape["data"]``,
    ``axis_sizes["data"]``).
    """

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings

    def _flag(self, node, how: str) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT023",
            f"hard-coded \"data\" axis name ({how}) outside "
            f"parallel/ — on a hierarchical mesh the flat axis is "
            f"the composite (\"inner\", \"outer\") tuple; spell it "
            f"as topology.DATA_AXIS / topology.flat_axis / the "
            f"owning object's .axis so the program rides the "
            f"topology plane instead of pinning a 1-D mesh"))

    def visit_Call(self, node: ast.Call) -> None:
        name = terminal_name(node.func)
        for kw in node.keywords:
            if kw.arg in _AXIS_KWARGS and _is_data(kw.value):
                self._flag(kw.value, f"{kw.arg}= keyword")
        if name in _AXIS_CALLABLES:
            for a in node.args:
                if _is_data(a):
                    self._flag(a, f"positional axis to {name}()")
        if name in _MESH_BUILDERS:
            for a in node.args:
                if isinstance(a, ast.Dict):
                    for k in a.keys:
                        if _is_data(k):
                            self._flag(k, f"mesh axis key in {name}()")
        self.generic_visit(node)

    def _defaults(self, node) -> None:
        args = node.args
        pos = args.posonlyargs + args.args
        for a, d in zip(pos[len(pos) - len(args.defaults):],
                        args.defaults):
            if a.arg in _AXIS_KWARGS and _is_data(d):
                self._flag(d, f"default for {a.arg}=")
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None and a.arg in _AXIS_KWARGS and _is_data(d):
                self._flag(d, f"default for {a.arg}=")
        self.generic_visit(node)

    visit_FunctionDef = visit_AsyncFunctionDef = _defaults

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if _is_data(node.slice):
            base = node.value
            attr = base.attr if isinstance(base, ast.Attribute) else (
                base.id if isinstance(base, ast.Name) else "")
            if attr == "shape" or "axis" in attr:
                self._flag(node, f"{attr}[\"data\"] subscript")
        self.generic_visit(node)


@rule("PT023", "hard-coded flat \"data\" axis name outside parallel/",
      applies=lambda ctx: ctx.in_pkg and not ctx.in_dir("parallel"))
def check_pt023(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _FlatAxisLiteralCheck(ctx, findings).visit(ctx.tree)
    return findings


# ------------------------------------------------------------------ PT024


class _RawTrafficRandomCheck(ast.NodeVisitor):
    """Raw ``random.*`` / ``np.random.*`` draws inside ``loadgen/``.

    A traffic trace is replay evidence — the capacity frontier, the
    spike drill, and any chaos-soak composition cite its seed — so
    determinism has ONE home: :mod:`ptype_tpu.loadgen.rng`
    (:class:`TraceRng`, forked streams, SHA-derived child seeds). A
    stray ``random.random()`` or ``np.random.poisson()`` anywhere
    else in the package silently breaks same-seed replay (module
    state shared across traces, process-salted hashing, draw-order
    coupling between schedule and population). Tracks plain imports,
    aliases (``import numpy.random as npr``), and ``from random
    import ...`` of draw functions.
    """

    #: from-imported stdlib draw verbs worth tracking by bare name.
    _VERBS = frozenset({
        "random", "randint", "randrange", "uniform", "choice",
        "choices", "shuffle", "sample", "expovariate", "gauss",
        "lognormvariate", "normalvariate", "paretovariate",
        "betavariate", "gammavariate", "triangular", "vonmisesvariate",
        "weibullvariate", "getrandbits",
    })

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        #: names bound to the random / numpy.random modules
        self.rand_mods: set[str] = set()
        #: names bound to numpy itself (np.random.* chains)
        self.np_mods: set[str] = set()
        #: bare names from-imported from the random module
        self.funcs: set[str] = set()

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            bound = a.asname or a.name.split(".")[0]
            if a.name == "random":
                self.rand_mods.add(bound)
            elif a.name in ("numpy", "numpy.random") and a.asname:
                (self.rand_mods if a.name == "numpy.random"
                 else self.np_mods).add(a.asname)
            elif a.name == "numpy":
                self.np_mods.add("numpy")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "random":
            for a in node.names:
                if a.name in self._VERBS or a.name == "Random":
                    self.funcs.add(a.asname or a.name)
        elif node.module == "numpy":
            for a in node.names:
                if a.name == "random":
                    self.rand_mods.add(a.asname or a.name)
        self.generic_visit(node)

    def _flag(self, node, what: str) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT024",
            f"raw {what} inside loadgen/ — every traffic draw must "
            f"flow through the seeded RNG home "
            f"(loadgen/rng.py TraceRng) or same-seed replay breaks"))

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute):
            base = f.value
            if (isinstance(base, ast.Name)
                    and base.id in self.rand_mods):
                self._flag(node, f"{base.id}.{f.attr}() draw")
            elif (isinstance(base, ast.Attribute)
                  and base.attr == "random"
                  and isinstance(base.value, ast.Name)
                  and base.value.id in self.np_mods):
                self._flag(
                    node, f"{base.value.id}.random.{f.attr}() draw")
        elif isinstance(f, ast.Name) and f.id in self.funcs:
            self._flag(node, f"{f.id}() draw (from random import)")
        self.generic_visit(node)


@rule("PT024", "raw random draw in loadgen/ outside the seeded RNG "
      "home",
      applies=lambda ctx: (ctx.in_pkg and ctx.in_dir("loadgen")
                           and ctx.basename != "rng.py"))
def check_pt024(ctx: FileContext) -> list[Finding]:
    findings: list[Finding] = []
    _RawTrafficRandomCheck(ctx, findings).visit(ctx.tree)
    return findings


# ------------------------------------------------------------------ PT025


class _AdHocLatencyCheck(ast.NodeVisitor):
    """Flags every ``perf_counter`` call — the caller scopes WHERE."""

    def __init__(self, ctx, findings):
        self.ctx = ctx
        self.findings = findings
        self.mods: set[str] = set()
        self.funcs: set[str] = set()

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name == "time":
                self.mods.add(a.asname or "time")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "time":
            for a in node.names:
                if a.name == "perf_counter":
                    self.funcs.add(a.asname or a.name)
        self.generic_visit(node)

    def _flag(self, node: ast.Call) -> None:
        self.findings.append(self.ctx.finding(
            node, "PT025",
            "ad-hoc perf_counter latency measurement in request-path "
            "code — attribution has ONE home: gateway legs time "
            "through gateway/slo.py Stopwatch (which feeds the "
            "stage_ms histograms, exemplars, and the stage-breach "
            "page), engine legs through the serving ledger's seams. "
            "A private timer is a latency number no waterfall, "
            "exemplar, or budget will ever see"))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if (isinstance(fn, ast.Attribute)
                and fn.attr == "perf_counter"
                and isinstance(fn.value, ast.Name)
                and fn.value.id in (self.mods or {"time", "_time"})):
            self._flag(node)
        elif isinstance(fn, ast.Name) and fn.id in self.funcs:
            self._flag(node)
        self.generic_visit(node)


@rule("PT025", "ad-hoc perf_counter latency measurement outside the "
      "sanctioned timing seams",
      applies=lambda ctx: (ctx.in_pkg
                           and (ctx.in_dir("gateway")
                                or ctx.in_dir("serve_engine"))
                           and ctx.basename != "slo.py"))
def check_pt025(ctx: FileContext) -> list[Finding]:
    # gateway/slo.py is exempt by scope: it IS the sanctioned home
    # (Stopwatch + SLOTracker). serve_engine/ additionally carries
    # PT010 (any raw wall-clock read); PT025 adds the latency-specific
    # story so a gateway file moved there keeps the same verdict.
    findings: list[Finding] = []
    _AdHocLatencyCheck(ctx, findings).visit(ctx.tree)
    return findings

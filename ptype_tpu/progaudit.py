"""Jaxpr-level program audits — the dispatch-discipline contract.

ptlint's PT018–PT020 police the PYTHON around the hot programs;
:mod:`ptype_tpu.jitwatch` watches them recompile at runtime. This
module closes the middle: it TRACES a hot program (``jax.make_jaxpr``
— no execution, no backend compile) and asserts invariants of the
program itself, the ones a green test suite cannot see breaking:

- **no host callbacks** — a ``pure_callback``/``io_callback``/
  ``debug_callback`` (or ``debug.print``) inside a hot program turns
  every dispatch into a host round-trip; fine in a notebook, fatal in
  a decode loop;
- **no f64** — a ``convert_element_type`` to float64 (or any f64
  intermediate) doubles HBM and wire bytes for the whole downstream
  program, usually smuggled in by a dtype-less numpy literal (PT020's
  runtime shadow);
- **donation consumed** — ``donate_argnums`` is a *request*; whether
  XLA actually aliases the buffer only shows in the lowering
  (``tf.aliasing_output`` / ``jax.buffer_donor``). The engine's bank
  donation is what keeps the KV pool from being copied per step — a
  silently-dropped donation is a 2x HBM regression with no failing
  test;
- **compiled temporaries under a ceiling** — the alias marker is in
  the lowering whether or not the compiled program copies anyway (a
  layer scan that took the banks as scanned inputs and outputs kept
  the marker and copied both banks every step). With
  ``max_temp_bytes`` the program is COMPILED (still not run) and its
  ``memory_analysis().temp_size_in_bytes`` must stay under the
  ceiling; the paged programs pin it below one bank;
- **collective-op count** — the bucketed collectives exist to make
  one bucket cost ONE launch; a refactor that un-fuses them (N psums
  for N leaves) keeps every parity test green and gives back the PR 1
  win. The audit counts collective primitives in the traced program
  and pins them to the bucket plan.

:func:`register` + :func:`audit_registered` keep a process-wide
registry of hot-program builders; :func:`register_default_programs`
installs the standing set (train-step grads, ZeRO shard-apply,
bucketed allreduce/reduce-scatter, the paged decode step and prefill
chunk, the fused spec window) that ``tests/test_progaudit.py`` audits
in the fast tier.

Stdlib + jax only at the bottom; model/mesh imports live inside the
default builders (lazy — auditing a custom program must not drag the
transformer stack in).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax

__all__ = [
    "AuditError", "AuditReport", "audit", "collect_primitives",
    "register", "registered", "audit_registered", "audit_all",
    "register_default_programs", "DEFAULT_PROGRAMS",
]

#: Primitive names that round-trip through the host per dispatch.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})

#: Cross-device collective primitives (the launch-count currency).
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter",
})

#: Lowering markers that prove a donated invar was actually aliased
#: (or at least accepted as a donor) by XLA.
_DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


class AuditError(AssertionError):
    """A hot program broke its dispatch contract; the message names
    every violated invariant."""


@dataclasses.dataclass
class AuditReport:
    """One audited program: counts, sites, and the verdict."""

    name: str
    problems: list[str]
    collectives: dict[str, int]
    callbacks: list[str]
    f64_sites: list[str]
    eqns: int
    donated_expected: int = 0
    donated_consumed: int = 0
    #: The compiled program's temporaries and their ceiling; None
    #: where the audit set no ceiling (nothing was compiled).
    temp_bytes: int | None = None
    max_temp_bytes: int | None = None

    @property
    def ok(self) -> bool:
        return not self.problems

    def raise_if_failed(self) -> "AuditReport":
        if self.problems:
            raise AuditError(
                f"progaudit[{self.name}]: "
                + "; ".join(self.problems))
        return self

    def to_dict(self) -> dict:
        return {
            "name": self.name, "ok": self.ok,
            "problems": list(self.problems),
            "collectives": dict(self.collectives),
            "callbacks": list(self.callbacks),
            "f64_sites": list(self.f64_sites),
            "eqns": self.eqns,
            "donated_expected": self.donated_expected,
            "donated_consumed": self.donated_consumed,
            "temp_bytes": self.temp_bytes,
            "max_temp_bytes": self.max_temp_bytes,
        }


# ------------------------------------------------------------- traversal


def _sub_jaxprs(eqn):
    """Every nested jaxpr an equation carries (pjit/scan/shard_map →
    params['jaxpr']; cond → params['branches']; custom_*: call
    jaxprs)."""
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):        # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns"):       # raw Jaxpr
            yield v
        elif isinstance(v, (list, tuple)):
            for b in v:
                if hasattr(b, "jaxpr"):
                    yield b.jaxpr
                elif hasattr(b, "eqns"):
                    yield b


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def collect_primitives(closed) -> dict[str, int]:
    """primitive name -> count over the whole (nested) jaxpr."""
    counts: dict[str, int] = {}
    for eqn in _walk_eqns(closed.jaxpr):
        counts[eqn.primitive.name] = counts.get(
            eqn.primitive.name, 0) + 1
    return counts


def _is_f64(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and str(dtype) == "float64"


# ----------------------------------------------------------------- audit


def audit(fn, args, *, name: str = "", donate_argnums=(),
          expect_collectives: int | dict | None = None,
          allow_f64: bool = False, static_argnums=(),
          check_donation: bool | None = None,
          max_temp_bytes: int | None = None) -> AuditReport:
    """Trace ``fn(*args)`` (args may be ShapeDtypeStructs — nothing
    executes) and audit the program. ``expect_collectives``: an int
    pins the TOTAL collective-primitive count, a dict pins per-prim
    counts (prims absent from the dict are unconstrained). With
    ``donate_argnums`` the program is additionally LOWERED (still no
    execution) and the donation must survive into the lowering text.
    With ``max_temp_bytes`` it is also COMPILED for this backend (not
    run) and its temporaries must come to less than that many bytes.
    Returns the report; call :meth:`AuditReport.raise_if_failed` to
    turn problems into a typed :class:`AuditError`."""
    problems: list[str] = []
    closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(*args)

    callbacks: list[str] = []
    f64_sites: list[str] = []
    collectives: dict[str, int] = {}
    n_eqns = 0
    for eqn in _walk_eqns(closed.jaxpr):
        n_eqns += 1
        prim = eqn.primitive.name
        if prim in CALLBACK_PRIMS or "callback" in prim:
            callbacks.append(prim)
        if prim in COLLECTIVE_PRIMS:
            collectives[prim] = collectives.get(prim, 0) + 1
        if prim == "convert_element_type" and _is_f64(
                eqn.outvars[0].aval):
            f64_sites.append("convert_element_type -> f64")
        else:
            for v in eqn.outvars:
                if _is_f64(getattr(v, "aval", None)):
                    f64_sites.append(f"{prim} produces f64")
                    break
    for v in closed.jaxpr.invars:
        if _is_f64(getattr(v, "aval", None)):
            f64_sites.append("f64 program input")

    if callbacks:
        problems.append(
            f"host callbacks in the program: {sorted(set(callbacks))} "
            f"(a host round-trip per dispatch)")
    if f64_sites and not allow_f64:
        problems.append(
            f"float64 in the program ({len(f64_sites)} sites, first: "
            f"{f64_sites[0]}) — 2x HBM/wire for every downstream op")
    if expect_collectives is not None:
        total = sum(collectives.values())
        if isinstance(expect_collectives, int):
            if total != expect_collectives:
                problems.append(
                    f"collective launch count {total} != expected "
                    f"{expect_collectives} (got {collectives}) — the "
                    f"bucket fusion contract")
        else:
            for prim, want in expect_collectives.items():
                got = collectives.get(prim, 0)
                if got != want:
                    problems.append(
                        f"{prim} count {got} != expected {want} "
                        f"(got {collectives})")

    donated_expected = donated_consumed = 0
    if check_donation is None:
        check_donation = bool(donate_argnums)
    check_donation = check_donation and bool(donate_argnums)
    lowered = None
    if check_donation or max_temp_bytes is not None:
        lowered = jax.jit(
            fn, donate_argnums=donate_argnums,
            static_argnums=static_argnums).lower(*args)
    if check_donation:
        flat_args = []
        for i in donate_argnums:
            flat_args.extend(jax.tree_util.tree_leaves(args[i]))
        donated_expected = len(flat_args)
        text = lowered.as_text()
        donated_consumed = sum(text.count(m) for m in
                               _DONATION_MARKERS)
        if donated_consumed < donated_expected:
            problems.append(
                f"donation not consumed in the lowering: "
                f"{donated_consumed}/{donated_expected} donated "
                f"buffers marked ({'/'.join(_DONATION_MARKERS)}) — "
                f"the banks are being COPIED per step")

    temp_bytes = None
    if max_temp_bytes is not None:
        temp_bytes = int(
            lowered.compile().memory_analysis().temp_size_in_bytes)
        if temp_bytes >= max_temp_bytes:
            problems.append(
                f"compiled temporaries {temp_bytes} B >= ceiling "
                f"{max_temp_bytes} B — the program holds a copy of "
                f"what it was given to update in place")

    return AuditReport(
        name=name or getattr(fn, "__name__", "<fn>"),
        problems=problems, collectives=collectives,
        callbacks=sorted(set(callbacks)), f64_sites=f64_sites,
        eqns=n_eqns, donated_expected=donated_expected,
        donated_consumed=donated_consumed, temp_bytes=temp_bytes,
        max_temp_bytes=max_temp_bytes)


# -------------------------------------------------------------- registry

#: name -> zero-arg builder returning an :class:`AuditReport`.
_REGISTRY: dict[str, Callable[[], AuditReport]] = {}

DEFAULT_PROGRAMS = (
    "train.grads", "zero.shard_apply", "zero1.shard_apply",
    "zero2.grad_reduce_scatter", "zero3.param_gather",
    "zero3.shard_apply", "collectives.bucket_allreduce",
    "collectives.bucket_reduce_scatter",
    "collectives.hier_allreduce",
    "collectives.hier_reduce_scatter", "serve.decode_step",
    "serve.prefill_chunk", "serve.spec_window", "serve.kv_pack",
    "serve.kv_unpack",
)


def register(name: str, builder: Callable[[], AuditReport]) -> None:
    _REGISTRY[name] = builder


def registered() -> list[str]:
    return sorted(_REGISTRY)


def audit_registered(name: str) -> AuditReport:
    if name not in _REGISTRY:
        raise KeyError(f"no registered hot program {name!r} "
                       f"(have: {registered()})")
    return _REGISTRY[name]()


def audit_all(raise_on_failure: bool = False) -> dict[str, AuditReport]:
    out = {}
    for name in registered():
        out[name] = audit_registered(name)
        if raise_on_failure:
            out[name].raise_if_failed()
    return out


# ---------------------------------------------------- default programs


def _tiny_setup(preset: str):
    import jax.numpy as jnp

    from ptype_tpu.models import transformer as tfm

    cfg = tfm.preset(preset, dtype=jnp.float32)
    params_avals = jax.eval_shape(
        lambda r: tfm.init_params(r, cfg), jax.random.PRNGKey(0))
    return cfg, params_avals


def _build_train_grads(preset: str, batch: int, seq: int):
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.models import transformer as tfm

        cfg, params_avals = _tiny_setup(preset)
        batch_avals = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}

        def grads(p, b):
            return jax.value_and_grad(tfm.loss_fn)(p, b, cfg)

        # The single-replica grad program is collective-free (the
        # wire is the Store's job) and must stay f32/bf16 end to end.
        return audit(grads, (params_avals, batch_avals),
                     name="train.grads", expect_collectives=0)

    return builder


def _build_zero_apply():
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import zero as zero_mod
        from ptype_tpu.parallel.mesh import build_mesh
        from ptype_tpu.parallel.topology import DATA_AXIS
        from ptype_tpu.train.trainer import default_optimizer_hparams

        n = jax.device_count()
        mesh = build_mesh({DATA_AXIS: n})
        shapes = ((4, 4), (8,))
        total = sum(1 if not s else int(__import__("math").prod(s))
                    for s in shapes)
        pad = (-total) % n
        elems = total + pad
        fn = zero_mod._shard_apply_fn(
            mesh, DATA_AXIS, shapes, "float32", pad,
            default_optimizer_hparams())
        f32 = jnp.float32
        avals = ([jax.ShapeDtypeStruct(s, f32) for s in shapes]
                 + [jax.ShapeDtypeStruct((elems,), f32)] * 4
                 + [jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), f32)])
        # ONE all_gather: the fused shard-apply's whole point (pack →
        # slice my shard → AdamW → gather) — a second gather means
        # the fusion regressed to per-leaf assembly.
        return audit(fn, avals, name="zero.shard_apply",
                     expect_collectives={"all_gather": 1})

    return builder


def _build_zero1_apply():
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import zero as zero_mod
        from ptype_tpu.parallel.mesh import build_mesh
        from ptype_tpu.parallel.topology import DATA_AXIS
        from ptype_tpu.train.trainer import default_optimizer_hparams

        n = jax.device_count()
        mesh = build_mesh({DATA_AXIS: n})
        shapes = ((4, 4), (8,))
        total = 24
        pad = (-total) % n
        elems = total + pad
        fn = zero_mod._shard_apply_full_fn(
            mesh, DATA_AXIS, shapes, "float32", pad,
            default_optimizer_hparams())
        f32 = jnp.float32
        avals = ([jax.ShapeDtypeStruct(s, f32) for s in shapes] * 2
                 + [jax.ShapeDtypeStruct((elems,), f32)] * 3
                 + [jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), f32)])
        # The ZeRO-1 rung: full grads in, ONE param all_gather out —
        # same fusion contract as zero.shard_apply.
        return audit(fn, avals, name="zero1.shard_apply",
                     expect_collectives={"all_gather": 1})

    return builder


def _build_zero2_grad_rs():
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import collectives as coll
        from ptype_tpu.parallel.mesh import build_mesh
        from ptype_tpu.parallel.topology import DATA_AXIS

        n = jax.device_count()
        mesh = build_mesh({DATA_AXIS: n})
        shapes = ((4, 4), (8,))
        pad = (-24) % n
        avals = [jax.ShapeDtypeStruct((n, *s), jnp.float32)
                 for s in shapes]
        fn = coll._bucket_reduce_scatter_fn(
            mesh, DATA_AXIS, "mean", shapes, "float32", pad, None,
            False, q_block=None)
        # ZeRO-2's whole point: grads arrive shard-resident from ONE
        # reduce_scatter per bucket and are NEVER allgathered — a
        # stray all_gather here silently rebuilds the full-grad
        # memory the rung exists to shed.
        return audit(fn, avals, name="zero2.grad_reduce_scatter",
                     expect_collectives={"reduce_scatter": 1,
                                         "all_gather": 0})

    return builder


def _build_zero3_gather():
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import zero as zero_mod
        from ptype_tpu.parallel.mesh import build_mesh
        from ptype_tpu.parallel.topology import DATA_AXIS

        n = jax.device_count()
        mesh = build_mesh({DATA_AXIS: n})
        shapes = ((4, 4), (8,))
        total = 24
        pad = (-total) % n
        fn = zero_mod._bucket_gather_fn(mesh, DATA_AXIS, shapes,
                                        "float32", pad)
        aval = jax.ShapeDtypeStruct((total + pad,), jnp.float32)
        # The just-in-time param materialization: ONE all_gather per
        # bucket, however many leaves it unpacks to — per-leaf gathers
        # un-fuse the forward's dispatch overlap.
        return audit(fn, (aval,), name="zero3.param_gather",
                     expect_collectives={"all_gather": 1})

    return builder


def _build_zero3_apply():
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import zero as zero_mod
        from ptype_tpu.train.trainer import default_optimizer_hparams

        n = jax.device_count()
        total = 24
        elems = total + (-total) % n
        fn = zero_mod._shard_apply3_fn(default_optimizer_hparams())
        f32 = jnp.float32
        flat = jax.ShapeDtypeStruct((elems,), f32)
        args = (flat, flat, flat, flat, flat,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), f32))
        # ZeRO-3's update is purely elementwise on the resident flats
        # (the one all_gather lives in zero3.param_gather), and the
        # param/moment buffers are donated — a dropped donation
        # doubles the rung's resident footprint mid-step.
        return audit(fn, args, name="zero3.shard_apply",
                     donate_argnums=(0, 2, 3), expect_collectives=0)

    return builder


def _build_bucket_collective(kind: str):
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import collectives as coll
        from ptype_tpu.parallel.mesh import build_mesh
        from ptype_tpu.parallel.topology import DATA_AXIS

        n = jax.device_count()
        mesh = build_mesh({DATA_AXIS: n})
        shapes = ((4, 4), (8,))
        pad = (-24) % n
        avals = [jax.ShapeDtypeStruct((n, *s), jnp.float32)
                 for s in shapes]
        if kind == "allreduce":
            fn = coll._bucket_all_reduce_fn(
                mesh, DATA_AXIS, "mean", shapes, "float32", pad, None,
                False, q_block=None)
            expect = {"psum": 1}
            name = "collectives.bucket_allreduce"
        else:
            fn = coll._bucket_reduce_scatter_fn(
                mesh, DATA_AXIS, "sum", shapes, "float32", pad, None,
                False, q_block=None)
            expect = {"reduce_scatter": 1}
            name = "collectives.bucket_reduce_scatter"
        # N leaves, ONE launch: the bucket contract PR 1 measured
        # 2-3x from; per-leaf regressions show up as count N.
        return audit(fn, avals, name=name, expect_collectives=expect)

    return builder


def _build_hier_collective(kind: str):
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.parallel import collectives as coll
        from ptype_tpu.parallel.topology import Topology

        n = jax.device_count()
        no = 2 if n % 2 == 0 and n >= 4 else 1
        topo = Topology(n_outer=no, n_inner=n // no)
        mesh = topo.mesh()
        shapes = ((4, 4), (8,))
        pad = (-24) % n
        avals = [jax.ShapeDtypeStruct((n, *s), jnp.float32)
                 for s in shapes]
        if kind == "allreduce":
            fn = coll._hier_bucket_all_reduce_fn(
                mesh, "mean", shapes, "float32", pad,
                None, None, False, None, None)
            # The per-LEG launch pins (ISSUE 18): inner
            # reduce-scatter, ONE outer exchange (psum over the
            # slow leg — the only cross-domain launch), inner
            # allgather. An extra psum means a leg regressed to a
            # flat composite-axis collective and the slow-leg wire
            # win is gone while every parity test stays green.
            expect = ({"reduce_scatter": 1, "psum": 1,
                       "all_gather": 1} if topo.hierarchical
                      else None)
            name = "collectives.hier_allreduce"
        else:
            fn = coll._hier_bucket_reduce_scatter_fn(
                mesh, "sum", shapes, "float32", pad,
                None, None, False, None, None)
            # Two reduce-scatters (psum_scatter lowers to the
            # reduce_scatter primitive): inner then outer chunk.
            # No gather leg — ZeRO consumes the flat shard as-is.
            expect = ({"reduce_scatter": 2} if topo.hierarchical
                      else None)
            name = "collectives.hier_reduce_scatter"
        return audit(fn, avals, name=name, expect_collectives=expect)

    return builder


def _bank_aval(cfg, n_blocks: int, block_tokens: int):
    """One float32 K (or V) bank of a paged pool, as a shape. float32
    because the CPU backend widens a bfloat16 scatter's whole operand,
    which would read as a copy of the bank that the chip never makes."""
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(
        (cfg.n_layers, n_blocks, block_tokens, cfg.kv_heads,
         cfg.head_dim), jnp.float32)


def _nbytes(aval) -> int:
    return math.prod(aval.shape) * aval.dtype.itemsize


def _build_paged_program(name: str, preset: str, n_slots: int,
                         n_blocks: int, block_tokens: int,
                         chunk: int):
    """``serve.decode_step`` / ``serve.prefill_chunk``: the engine's
    two paged programs as it jits them, banks donated. The pool is far
    wider than a row's table (``cfg.max_seq`` tokens), so a bank
    dominates every other temporary and the ceiling, a quarter of one
    bank, tells an in-place update from a copy (the programs hold
    about an eighth: activations, and the chunk's gathered table)."""
    def builder() -> AuditReport:
        import jax.numpy as jnp
        import numpy as np

        from ptype_tpu.models import generate as gen

        cfg, params_avals = _tiny_setup(preset)
        B, nb = n_slots, cfg.max_seq // block_tokens
        bank = _bank_aval(cfg, n_blocks, block_tokens)
        i32 = jnp.int32

        def decode_step(params, banks, tok, pos, tables, wr_b, wr_o,
                        live_list):
            return gen.decode_step_banks(params, tok, pos, cfg, banks,
                                         tables, wr_b, wr_o,
                                         live_list=live_list)[:2]

        def prefill_chunk(params, banks, tokens, start, length, table):
            return gen.prefill_chunk_banks(
                params, tokens, start, length, cfg, banks, table)[:2]

        row = jax.ShapeDtypeStruct((B,), i32)
        scalar = jax.ShapeDtypeStruct((), i32)
        # The live rows' block list, as the engine hands it to the
        # step: the banks are read inside the tile loop, a second place
        # where the compiler could take a copy of one.
        lst, _ = gen.live_block_list(
            np.zeros((B, nb), np.int32), np.zeros(B, np.int32),
            np.zeros(B, bool), block_tokens)
        fn, rest = {
            "serve.decode_step": (
                decode_step,
                (row, row, jax.ShapeDtypeStruct((B, nb), i32), row,
                 row, (jax.ShapeDtypeStruct(lst.shape, i32), scalar))),
            "serve.prefill_chunk": (
                prefill_chunk,
                (jax.ShapeDtypeStruct((1, chunk), i32), scalar, scalar,
                 jax.ShapeDtypeStruct((nb,), i32))),
        }[name]
        # Banks donated (the engine's donate_argnums shape): a dropped
        # donation, or a loop that cannot alias them, copies the whole
        # KV pool every step.
        return audit(fn, (params_avals, {"k": bank, "v": bank}) + rest,
                     name=name, donate_argnums=(1,), expect_collectives=0,
                     max_temp_bytes=_nbytes(bank) // 4)

    return builder


def _build_spec_window(preset: str, k: int, n_blocks: int):
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.models import generate as gen
        from ptype_tpu.models import transformer as tfm
        from ptype_tpu.serve_engine import (PagedGeneratorActor,
                                            SpecConfig)

        cfg = tfm.preset(preset, dtype=jnp.float32)
        params = jax.jit(lambda r: tfm.init_params(r, cfg))(
            jax.random.PRNGKey(0))
        dp, dcfg = gen.truncated_draft_params(params, cfg, n_layers=1)
        eng = PagedGeneratorActor(
            cfg, params=params, n_slots=2, block_tokens=16,
            n_blocks=n_blocks,
            spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=k,
                            adaptive=False))
        try:
            W = k + 1
            B, nb = eng.n_slots, eng.nb
            i32, f32 = jnp.int32, jnp.float32
            bank = _bank_aval(cfg, eng.pool.n_blocks, eng.block_tokens)
            dbank = _bank_aval(dcfg, eng.pool.n_blocks,
                               eng.block_tokens)
            run = eng._window_prog(W, sampled=False)
            args = (
                params, dp,
                jax.ShapeDtypeStruct((B,), i32),        # tok
                jax.ShapeDtypeStruct((B,), i32),        # pos
                bank, bank, dbank, dbank,
                jax.ShapeDtypeStruct((B, nb), i32),     # tables
                jax.ShapeDtypeStruct((B, nb), i32),     # dtables
                jax.ShapeDtypeStruct((B,), i32),        # nalloc
                jax.ShapeDtypeStruct((B,), i32),        # dnalloc
                jax.ShapeDtypeStruct((B,), jnp.bool_),  # active
                jax.ShapeDtypeStruct((B, 2), jnp.uint32),  # keys
                jax.ShapeDtypeStruct((B,), i32),        # sctr
                jax.ShapeDtypeStruct((B,), f32),        # temps
                jax.ShapeDtypeStruct((B,), i32),        # topk
                jax.ShapeDtypeStruct((B,), f32),        # topp
            )
            # The REAL engine window program: fused draft scan +
            # batched verify + accept, both pools' banks donated,
            # ONE dispatch per window, no collectives, no f64, and no
            # copy of a pool: all temporaries under one DRAFT bank,
            # the smaller of the two kinds.
            return audit(run, args, name="serve.spec_window",
                         donate_argnums=(4, 5, 6, 7),
                         expect_collectives=0,
                         max_temp_bytes=_nbytes(dbank))
        finally:
            eng.close()

    return builder


def _build_kv_pack(preset: str, n_blocks: int, block_tokens: int):
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.models import transformer as tfm
        from ptype_tpu.serve_engine.migrate import make_pack_prog

        cfg = tfm.preset(preset, dtype=jnp.float32)
        kvh = cfg.n_kv_heads or cfg.n_heads
        hd = cfg.d_model // cfg.n_heads
        blk = jax.ShapeDtypeStruct(
            (cfg.n_layers, block_tokens, kvh, hd), jnp.float32)
        # Residuals donated (consumed into the pre-quantization sum,
        # replaced by the new per-block error): a dropped donation
        # doubles the wire path's live residual memory per transfer.
        return audit(make_pack_prog(), (blk, blk),
                     name="serve.kv_pack", donate_argnums=(1,),
                     expect_collectives=0)

    return builder


def _build_kv_unpack(preset: str, n_blocks: int, block_tokens: int):
    def builder() -> AuditReport:
        import jax.numpy as jnp

        from ptype_tpu.models import transformer as tfm
        from ptype_tpu.serve_engine.migrate import (make_pack_prog,
                                                    make_unpack_prog)

        cfg = tfm.preset(preset, dtype=jnp.float32)
        kvh = cfg.n_kv_heads or cfg.n_heads
        hd = cfg.d_model // cfg.n_heads
        shape = (cfg.n_layers, block_tokens, kvh, hd)
        blk = jax.ShapeDtypeStruct(shape, jnp.float32)
        # The wire avals come from the pack program itself, so the
        # audited unpack consumes exactly what pack emits.
        q, s, _ = jax.eval_shape(make_pack_prog(), blk, blk)
        bank = jax.ShapeDtypeStruct(
            (cfg.n_layers, n_blocks, block_tokens, kvh, hd),
            jnp.float32)
        args = (bank,
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(s.shape, s.dtype),
                jax.ShapeDtypeStruct((), jnp.int32))
        # Banks donated (scatter-in-place): a dropped donation copies
        # the decode replica's WHOLE KV pool per imported block.
        return audit(make_unpack_prog(shape, jnp.float32), args,
                     name="serve.kv_unpack", donate_argnums=(0,),
                     expect_collectives=0)

    return builder


def register_default_programs(preset: str = "tiny", batch: int = 4,
                              seq: int = 16, spec_k: int = 3) -> None:
    """Install the standing hot-program registry (idempotent): the
    five program families the ROADMAP's perf wins live in. The
    fast-tier contract test audits every one; ``audit_all()`` is the
    operator surface."""
    register("train.grads", _build_train_grads(preset, batch, seq))
    register("zero.shard_apply", _build_zero_apply())
    register("zero1.shard_apply", _build_zero1_apply())
    register("zero2.grad_reduce_scatter", _build_zero2_grad_rs())
    register("zero3.param_gather", _build_zero3_gather())
    register("zero3.shard_apply", _build_zero3_apply())
    register("collectives.bucket_allreduce",
             _build_bucket_collective("allreduce"))
    register("collectives.bucket_reduce_scatter",
             _build_bucket_collective("reduce_scatter"))
    register("collectives.hier_allreduce",
             _build_hier_collective("allreduce"))
    register("collectives.hier_reduce_scatter",
             _build_hier_collective("reduce_scatter"))
    for name in ("serve.decode_step", "serve.prefill_chunk"):
        register(name, _build_paged_program(
            name, preset, n_slots=2, n_blocks=256, block_tokens=16,
            chunk=32))
    register("serve.spec_window",
             _build_spec_window(preset, spec_k, n_blocks=256))
    register("serve.kv_pack",
             _build_kv_pack(preset, n_blocks=12, block_tokens=16))
    register("serve.kv_unpack",
             _build_kv_unpack(preset, n_blocks=12, block_tokens=16))

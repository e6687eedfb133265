"""Runtime recompile watchdog + transfer discipline — the dynamic half
of the dispatch-discipline plane (ptlint PT018–PT020 are the static
passes, :mod:`ptype_tpu.progaudit` the program contract).

A retrace hazard that slips past the lint — a dtype that flaps between
weak and strong, a shape that wobbles, a function object rebuilt per
call — shows up at runtime as the SAME program compiling again with
the SAME signature. jax logs every backend compile when
``jax_log_compiles`` is on; this module hooks that seam and keeps
per-function books:

- **disarmed** (default): no jax config touched, zero cost — the
  factory pattern of :mod:`ptype_tpu.lockcheck`;
- **armed** (:func:`enable`, or ``PTYPE_JITWATCH=1`` at import):
  every backend compile is counted per ``(function, signature)``. A
  compile of a signature already compiled is a **recompile** — the
  cache SHOULD have hit — and bumps the ``jit.recompiles`` counter
  plus a per-function ``jit.fn.<name>`` gauge (bounded by the
  function-name universe, like lockcheck's lock names), which the
  health sampler turns into the series the ``recompile-storm`` rule
  pages on, NAMING the function. A storm (the same signature
  compiled ≥ ``storm_threshold`` times) dumps through the flight
  recorder the moment it is detected.

Transfer discipline rides along: :func:`hot_region` arms
``jax.transfer_guard`` (host→device AND device→host, implicit
transfers only) around a hot dispatch region — a numpy array or
python scalar smuggled into a jitted call raises AT THE CALL instead
of silently re-uploading per step. :func:`sanctioned_transfer` is the
typed exemption seam for the places a transfer IS the contract (the
train data leg, a meter's host sync); every pass through it is
counted (``jit.sanctioned_transfers``), so "zero *unsanctioned*
transfers" is enforced by construction inside armed regions.

Steady-state contract for the armed test tiers (chaos soak, serve,
train): warm up, :func:`mark_steady`, run the loop, then
``recompiles_since_steady() == {}`` — a steady-state engine compiles
NOTHING.

Stdlib-only at import; jax is touched only by :func:`enable` and the
armed guards (a lean coordinator process never pays the import).
"""

from __future__ import annotations

import contextlib
import logging
import os
import re
import threading
import time

__all__ = [
    "enable", "disable", "active", "JitWatch", "hot_region",
    "sanctioned_transfer", "ENV_VAR", "TRANSFER_ENV_VAR",
    "STORM_ENV_VAR",
]

ENV_VAR = "PTYPE_JITWATCH"
#: Guard level for hot regions: "disallow" (default — an unsanctioned
#: implicit transfer raises), "log", or "off" (recompile counting
#: only).
TRANSFER_ENV_VAR = "PTYPE_JITWATCH_TRANSFERS"
STORM_ENV_VAR = "PTYPE_JITWATCH_STORM"
DEFAULT_STORM_THRESHOLD = 3

#: The pxla compile log line: "Compiling jit(<name>) with global
#: shapes and types [...]. Argument mapping: (...)." — one WARNING per
#: backend compile (i.e. per trace-cache miss). The books are keyed by
#: the bare <name>. The SIGNATURE is shapes+types AND the argument
#: mapping: the same shapes under different shardings are legitimately
#: distinct programs, not a recompile.
_COMPILE_RE = re.compile(
    r"Compiling jit\((.+?)\) with global shapes and types (.*?Argument "
    r"mapping:.*)$", re.DOTALL)
_COMPILE_LOGGER = "jax._src.interpreters.pxla"


class _CompileFilter(logging.Filter):
    """Feeds parsed compile records into the watchdog. Installed as a
    logging FILTER (not a handler): when ``swallow`` is set — we
    armed ``jax_log_compiles`` ourselves, for the hook, not the
    console — the record is consumed here and never reaches any
    handler; an operator who had compile logs on already keeps
    them."""

    def __init__(self, watch: "JitWatch", swallow: bool):
        super().__init__()
        self._watch = watch
        self._swallow = swallow

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            m = _COMPILE_RE.search(record.getMessage())
            if m is not None:
                self._watch.on_compile(m.group(1), m.group(2))
        except Exception:  # noqa: BLE001 — a watchdog must never
            pass           # break the dispatch it watches
        return not self._swallow


class JitWatch:
    """Per-process compile books + steady-state marking."""

    def __init__(self, storm_threshold: int | None = None,
                 transfer_level: str | None = None,
                 ignored_fns: frozenset | None = None):
        if storm_threshold is None:
            storm_threshold = int(os.environ.get(
                STORM_ENV_VAR, DEFAULT_STORM_THRESHOLD))
        self.storm_threshold = int(storm_threshold)
        self.transfer_level = (transfer_level
                               or os.environ.get(TRANSFER_ENV_VAR,
                                                 "disallow"))
        #: jax's EAGER op-dispatch wrappers (jit(broadcast_in_dim),
        #: jit(convert_element_type) ...) legitimately re-compile the
        #: same input signature with different STATIC params — the
        #: log line can't tell those apart, so they are excluded from
        #: the recompile/storm books (raw compiles still counted).
        self.ignored_fns = (ignored_fns if ignored_fns is not None
                            else frozenset())
        self._mu = threading.Lock()
        #: (fn, signature) -> compile count. Distinct signatures are
        #: legit specializations (a new prefill chunk width); the SAME
        #: signature compiling twice means the cache was re-keyed.
        self._sigs: dict[tuple[str, str], int] = {}
        self._fn_compiles: dict[str, int] = {}
        self._fn_recompiles: dict[str, int] = {}
        self._storms: list[dict] = []
        self._steady_at: float | None = None
        self._steady_since: dict[str, int] = {}
        self._sanctioned: dict[str, int] = {}
        self._hot_regions = 0

    # -------------------------------------------------------- tracking

    def _is_internal(self, fn_name: str) -> bool:
        return fn_name.startswith("_") or fn_name in self.ignored_fns

    def on_compile(self, fn_name: str, signature: str) -> None:
        storm = None
        internal = self._is_internal(fn_name)
        with self._mu:
            key = (fn_name, signature)
            n = self._sigs.get(key, 0) + 1
            self._sigs[key] = n
            self._fn_compiles[fn_name] = \
                self._fn_compiles.get(fn_name, 0) + 1
            if self._steady_at is not None:
                self._steady_since[fn_name] = \
                    self._steady_since.get(fn_name, 0) + 1
            recompile = n > 1 and not internal
            if recompile:
                self._fn_recompiles[fn_name] = \
                    self._fn_recompiles.get(fn_name, 0) + 1
            if n == self.storm_threshold and not internal:
                storm = {
                    "kind": "recompile-storm", "fn": fn_name,
                    "signature": signature[:256], "compiles": n,
                    "thread": threading.current_thread().name,
                    "t": time.time(),
                }
                self._storms.append(storm)
        self._publish(fn_name, recompile)
        if storm is not None:
            self._emit(storm)

    def _publish(self, fn_name: str, recompile: bool) -> None:
        """Metric families the sampler serializes and the
        recompile-storm rule / ``obs jit`` read. Lazy metrics import:
        the watchdog must stay importable below everything."""
        try:
            from ptype_tpu.metrics import metrics

            metrics.counter("jit.compiles").add(1)
            if recompile:
                metrics.counter("jit.recompiles").add(1)
                with self._mu:
                    count = self._fn_recompiles.get(fn_name, 0)
                metrics.gauge(f"jit.fn.{fn_name}").set(float(count))
        except Exception:  # noqa: BLE001 — never break a compile
            pass

    @staticmethod
    def _emit(finding: dict) -> None:
        """Flight-recorder seam (the lockcheck pattern): an event on
        the active span plus a rate-limited ring dump naming the
        function — the post-mortem artifact."""
        try:
            from ptype_tpu import trace

            trace.add_event("jitwatch.storm",
                            **{k: str(v) for k, v in finding.items()
                               if k not in ("kind", "t")})
            trace.maybe_dump(
                f"recompile-storm: {finding['fn']} compiled "
                f"{finding['compiles']}x with one signature")
        except Exception:  # noqa: BLE001
            pass

    def note_sanctioned(self, reason: str) -> None:
        with self._mu:
            self._sanctioned[reason] = \
                self._sanctioned.get(reason, 0) + 1
        try:
            from ptype_tpu.metrics import metrics

            metrics.counter("jit.sanctioned_transfers").add(1)
        except Exception:  # noqa: BLE001
            pass

    def note_hot_region(self) -> None:
        with self._mu:
            self._hot_regions += 1

    # ------------------------------------------------------ steady state

    def mark_steady(self) -> None:
        """Warmup is over: every compile FROM NOW ON is a steady-state
        discipline violation (``recompiles_since_steady``)."""
        with self._mu:
            self._steady_at = time.time()
            self._steady_since = {}

    def recompiles_since_steady(self) -> dict[str, int]:
        """fn -> compiles (of ANY signature) since ``mark_steady`` —
        the armed tiers assert this is ``{}``: a steady-state hot loop
        compiles nothing, new shape or not."""
        with self._mu:
            return dict(self._steady_since)

    # ------------------------------------------------------ inspection

    def compiles(self) -> dict[str, int]:
        with self._mu:
            return dict(self._fn_compiles)

    def recompiles(self) -> dict[str, int]:
        """fn -> same-signature recompile count (compiles the cache
        should have served)."""
        with self._mu:
            return dict(self._fn_recompiles)

    def storms(self) -> list[dict]:
        with self._mu:
            return list(self._storms)

    def sanctioned(self) -> dict[str, int]:
        with self._mu:
            return dict(self._sanctioned)

    def report(self) -> dict:
        with self._mu:
            return {
                "compiles": dict(self._fn_compiles),
                "recompiles": dict(self._fn_recompiles),
                "signatures": len(self._sigs),
                "storms": list(self._storms),
                "storm_threshold": self.storm_threshold,
                "steady_since": dict(self._steady_since),
                "steady_marked": self._steady_at is not None,
                "sanctioned_transfers": dict(self._sanctioned),
                "hot_regions": self._hot_regions,
                "transfer_level": self.transfer_level,
            }


# ------------------------------------------------------------ module API

_watch: JitWatch | None = None
_filters: list[tuple[str, logging.Filter]] = []
_prior_log_compiles: bool | None = None
#: Loggers jax_log_compiles elevates to WARNING. The pxla one carries
#: the "Compiling <fn> ..." line the hook parses; the dispatch one is
#: pure timing noise — both are swallowed while WE armed the config.
_NOISY_LOGGERS = ("jax._src.dispatch", _COMPILE_LOGGER)


def _eager_wrapper_names() -> frozenset:
    """Public jax.lax / jax.numpy names: the functions jax's EAGER op
    dispatch compiles under (``jit(broadcast_in_dim)`` on a concrete
    array). Bounded, computed once per enable."""
    import jax
    import jax.numpy as jnp

    return frozenset(n for n in dir(jax.lax) + dir(jnp)
                     if not n.startswith("_"))


def enable(storm_threshold: int | None = None,
           transfer_level: str | None = None) -> JitWatch:
    """Arm the watchdog process-wide: turns ``jax_log_compiles`` on
    and hooks the compile-log seam. Re-enabling replaces the books.
    Returns the fresh watchdog."""
    global _watch, _prior_log_compiles
    import jax

    disable()
    _watch = JitWatch(storm_threshold, transfer_level,
                      ignored_fns=_eager_wrapper_names())
    _prior_log_compiles = bool(jax.config.jax_log_compiles)
    jax.config.update("jax_log_compiles", True)
    swallow = not _prior_log_compiles
    for name in _NOISY_LOGGERS:
        filt = _CompileFilter(_watch, swallow)
        logging.getLogger(name).addFilter(filt)
        _filters.append((name, filt))
    return _watch


def disable() -> None:
    """Disarm: detach the hook, restore the prior compile-log config."""
    global _watch, _prior_log_compiles
    for name, filt in _filters:
        logging.getLogger(name).removeFilter(filt)
    _filters.clear()
    if _prior_log_compiles is not None:
        try:
            import jax

            jax.config.update("jax_log_compiles",
                              _prior_log_compiles)
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
        _prior_log_compiles = None
    _watch = None


def active() -> JitWatch | None:
    return _watch


@contextlib.contextmanager
def hot_region(name: str):
    """Dispatch-discipline guard around a hot program call. Disarmed:
    free. Armed: ``jax.transfer_guard`` at the watchdog's level (the
    default ``disallow`` makes an unsanctioned IMPLICIT transfer —
    a numpy array or python scalar fed to a jitted call, a stray
    ``jnp.zeros`` constant — raise at the call site, naming it),
    explicit transfers (``jnp.asarray``/``device_put``/the engine's
    metered host syncs) stay legal. ``name`` is for the books."""
    w = _watch
    if w is None or w.transfer_level in ("off", ""):
        yield
        return
    import jax

    w.note_hot_region()
    with jax.transfer_guard_host_to_device(w.transfer_level), \
            jax.transfer_guard_device_to_host(w.transfer_level):
        yield


@contextlib.contextmanager
def sanctioned_transfer(reason: str):
    """The typed exemption seam: a region where a transfer IS the
    contract (the train data leg, a meter host sync). Counted per
    pass (``jit.sanctioned_transfers`` + per-reason books) so the
    exemptions stay auditable."""
    w = _watch
    if w is None:
        yield
        return
    import jax

    w.note_sanctioned(reason)
    with jax.transfer_guard("allow"):
        yield


def _maybe_enable_from_env() -> None:
    if os.environ.get(ENV_VAR, "").lower() in ("1", "true", "on"):
        enable()


_maybe_enable_from_env()

"""What the harness asks of an architecture, and nothing else.

A configuration's file states its ``family``; the module or package of
that name under ``benchmark/families/`` (found by name, as a metric's
reader is) holds everything that depends on the architecture. The
drivers (``serve_cell``, ``train_cell``) and the readers reach it only
through ``of(cfg)``; ``manifest.check`` refuses a configuration whose
family is missing or lacks one of these. ``cfg`` is the configuration
file's dict throughout. A family provides:

``program_config(cfg, max_seq, param_dtype)``
    The object the program's ``Trainer`` / ``PagedGeneratorActor``
    takes, for an engine's reach or a trained length. The one place the
    benchmark names the program's fields for this family, with the
    family's own refusals of what the program cannot hold.
``tree(cfg, seed, dtype_name, sharding=None)``
    The program's weights from the seed, in the program's layout and in
    the type named, on the device in one jitted call. How the reference
    regenerates them (layer by layer, say) is the family's own affair.
``served_logits(cfg, seed, dtype_name, tokens, idx, modes=("f32",))``
    The plain reference, serving: a full forward of each row of
    ``tokens`` (R, T) from weights it makes from the seed itself, giving
    for each mode the logits (R, n, V) at positions ``idx`` (R, n).
    Modes: ``f32`` (float32 at ``highest``: the reference), ``bf16``,
    ``fp8`` (the controls put these in the program's place).
``train_steps(cfg, hp, params, batches, mode, micro_rows, rows=None,
frozen_state=False)``
    The plain reference, training: follow ``len(batches)`` steps from
    ``params`` under the optimizer settings ``hp``; gives the losses,
    the first gradient as the optimizer got it, and the parameters after
    the last step. ``rows`` and ``frozen_state`` plant the faults.

The work counts are the yardstick's numerators: what the algorithm
needs, from shapes alone. They take what the cell saw, not a digest of
it, so that a family whose keys do not all cost the same (a window, a
top-k selection, a recurrent state) can count its own:

``decode_needed_bytes(cfg, row_contexts, shared_tokens)``
    HBM bytes one decode iteration must read; ``row_contexts`` has one
    context length per live row, ``shared_tokens`` the tokens those
    lengths count more than once (rows sharing a cached prefix).
``forward_flops(cfg, n_tokens, contexts)``
    Forward FLOPs of ``n_tokens`` tokens, ``contexts`` giving for each
    the keys it attends to, itself included (a decode iteration: one a
    row; a prefill chunk at ``pos``: ``pos+1 .. pos+n``).
``train_flops_per_token(cfg, seq)``
    Forward + backward FLOPs per trained token, recomputation left out.
``cache_bytes_per_token(cfg)``
    Bytes of cache one token holds across all layers.
``flash_train_floor_s(cfg, batch, seq, peaks)``
    The kernels' needed FLOPs and bytes as the least seconds a train
    step's attention could take: ``{"floor_s", "bound"}``.

A family that cannot be trained, or has no such kernel, still states the
function and raises ``SystemExit`` from it with the reason. A new family
may build on another's pieces (``benchmark.families.dense.reference``
has the rounding modes, RMSNorm, rotary positions and AdamW)."""

from __future__ import annotations

import importlib
import re

CONTRACT = ("program_config", "tree", "served_logits", "train_steps",
            "decode_needed_bytes", "forward_flops", "train_flops_per_token",
            "cache_bytes_per_token", "flash_train_floor_s")
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


def of(cfg: dict):
    """The family module of a configuration."""
    name = cfg.get("family")
    if not (isinstance(name, str) and _NAME.match(name)):
        raise SystemExit(f"benchmark: the configuration states no family "
                         f"(got {name!r})")
    try:
        return importlib.import_module("benchmark.families." + name)
    except ModuleNotFoundError as e:
        if e.name != "benchmark.families." + name:
            raise
        raise SystemExit(f"benchmark: no family {name!r} under "
                         f"benchmark/families/") from None


def faults(cfg: dict) -> list[str]:
    """Why this configuration's family cannot serve the harness, as
    sentences; empty when it can."""
    try:
        mod = of(cfg)
    except SystemExit as e:
        return [str(e).removeprefix("benchmark: ")]
    return [f"family {cfg['family']!r} lacks {fn}" for fn in CONTRACT
            if not callable(getattr(mod, fn, None))]

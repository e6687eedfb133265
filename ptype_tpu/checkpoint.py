"""Checkpoint / resume — sharded, async, Store-aware.

The reference had no application-level checkpointing; durability was
etcd's raft data-dir (SURVEY.md §5 "Checkpoint/resume": Store contents
survive restarts via ``data-dir``). The TPU-native equivalent owed there:
"first-class sharded checkpoint of the Store's parameter space
(Orbax-style async save of jax.Array shards), resume = Join + Store
pull". This module provides both tiers:

- :class:`Checkpointer` — save/restore any jax pytree. Each leaf is
  written **per addressable shard** (device→host copy of exactly this
  process's shards), so an 8B FSDP state never materializes unsharded.
  Restore takes a sharding pytree and ``device_put``s each leaf back
  into placement, and verifies the merged manifest covers every element
  (a partial save fails loudly, never zero-fills). ``async_save``
  snapshots to host synchronously (cheap, device→host DMA) and writes
  files on a background thread — the train loop resumes while bytes hit
  disk.

  **Cross-host**: in multi-controller runs every process calls ``save``
  — each writes only the shards whose ``replica_id`` is 0 (exactly one
  owner per shard box globally) plus its own ``manifest.p<i>.json``
  into the shared step dir; process 0 barriers on all N manifests, then
  commits the marker. ``restore`` merges every per-process manifest and
  can re-place into a different mesh/process set (reshard-on-restore).
- :class:`StoreCheckpoint` — the Store tier: persists a TensorStore
  namespace (values + spec/epoch manifest) into the platform
  ``data_dir``; ``resume()`` re-puts every key with its binding, which
  is exactly "Join + Store pull".

Layout (one directory per step, manifest-first like an orbax step dir):

    <dir>/step_<N>/manifest.json                (single-process saves)
    <dir>/step_<N>/manifest.p<i>.json           (one per process)
    <dir>/step_<N>/<flat-key>[.p<i>].shard<j>.npy
    <dir>/step_<N>/.complete          (commit marker, written last)
"""

from __future__ import annotations

import glob as _glob
import json
import os
import shutil
import threading
import time
import zlib
from typing import Any

import jax
import numpy as np

from ptype_tpu import chaos, logs, retry
from ptype_tpu.errors import CheckpointError, ClusterError

log = logs.get_logger("checkpoint")

_MANIFEST = "manifest.json"
_COMPLETE = ".complete"


def _flat_key(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            part = str(p.key)
        elif hasattr(p, "idx"):
            part = str(p.idx)
        else:
            part = str(p)
        # Keys become filenames: store keys like "params/w" must not
        # introduce directories.
        parts.append(part.replace("/", "%2F"))
    return ".".join(parts) or "_root"


def _proc_info() -> tuple[int, int]:
    """(process_index, process_count) — (0, 1) when jax is absent or
    single-controller."""
    try:
        import jax

        return jax.process_index(), jax.process_count()
    except Exception:  # noqa: BLE001 — control-plane-only processes
        return 0, 1


class Checkpointer:
    """Sharded pytree checkpoints under ``directory``.

    ``barrier_timeout`` bounds how long process 0 waits for the other
    processes' manifests before declaring a multi-controller save
    failed (no commit marker is written — the step stays invisible).
    """

    def __init__(self, directory: str, keep: int = 3,
                 barrier_timeout: float = 120.0):
        self.directory = directory
        self.keep = keep
        self.barrier_timeout = barrier_timeout
        os.makedirs(directory, exist_ok=True)
        self._pending: threading.Thread | None = None

    # ------------------------------------------------------------- save

    def save(self, step: int, tree: Any,
             extras: dict[str, str] | None = None) -> str:
        """Synchronous save; returns the step directory. ``extras`` are
        additional ``{filename: json-text}`` committed WITH the step
        (written before the completion marker). Waits for any pending
        async save first — one writer at a time per Checkpointer.

        Runs as a ``checkpoint.save/<step>`` region through the
        metrics.annotate seam — the goodput ledger's checkpoint leg
        and (when tracing is armed) a span, so a blocking save is
        attributable instead of reading as stall."""
        from ptype_tpu.metrics import annotate

        with annotate(f"checkpoint.save/{step}"):
            self.wait()
            host = self._snapshot(tree)
            return self._write(step, host, extras)

    def async_save(self, step: int, tree: Any) -> None:
        """Snapshot now (device→host), write in the background. At most
        one pending write: a second call waits for the first (backpressure
        rather than unbounded host copies). A failed background write
        (e.g. the multi-controller barrier timeout) re-raises from the
        NEXT ``wait``/``save``/``async_save`` — it must not die silently
        with the daemon thread while training continues uncheckpointed."""
        from ptype_tpu.metrics import annotate

        # Only the BLOCKING leg (drain + device→host snapshot) is the
        # step's checkpoint cost; the background write overlaps compute
        # and must not be attributed against it.
        with annotate(f"checkpoint.snapshot/{step}"):
            self.wait()
            host = self._snapshot(tree)

        def run():
            try:
                self._write(step, host)
            except Exception as e:  # noqa: BLE001 — re-raised on wait()
                self._pending_error = e

        self._pending = threading.Thread(
            target=run, name=f"ckpt-{step}", daemon=True,
        )
        self._pending.start()

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        err = getattr(self, "_pending_error", None)
        if err is not None:
            self._pending_error = None
            raise ClusterError(f"async checkpoint save failed: {err}") \
                from err

    def _snapshot(self, tree: Any) -> list[tuple[str, list, dict]]:
        """Pull this process's OWNED shards to host memory.

        Ownership = ``replica_id == 0``: replication (full or partial)
        puts identical shards on several devices — possibly on several
        hosts — and exactly one replica of each shard box has id 0, so
        the union of every process's snapshot tiles each array exactly
        once with no coordination. Returns
        [(key, [(start, np_array), ...], meta)].
        """
        pid, _ = _proc_info()
        out = []
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in leaves:
            key = _flat_key(path)
            arr = jax.numpy.asarray(leaf) if np.isscalar(leaf) else leaf
            shards = []
            if isinstance(arr, jax.Array) and arr.addressable_shards:
                # Belt and braces: replica_id==0 already picks one owner
                # per box; the box-dedup guards against exotic shardings
                # that alias boxes within a replica.
                seen: set[tuple] = set()
                for s in arr.addressable_shards:
                    if s.replica_id != 0:
                        continue
                    start = _index_start(s.index, arr.shape)
                    box = (start, tuple(s.data.shape))
                    if box in seen:
                        continue
                    seen.add(box)
                    shards.append((list(start), np.asarray(s.data)))
                dtype = str(arr.dtype)
            else:
                # Host-side leaves are identical everywhere: process 0
                # owns them.
                if pid == 0:
                    shards = [([0] * np.ndim(arr), np.asarray(arr))]
                dtype = str(np.asarray(arr).dtype)
            meta = {"shape": list(np.shape(arr)), "dtype": dtype}
            out.append((key, shards, meta))
        return out

    def _write(self, step: int, host: list,
               extras: dict[str, str] | None = None) -> str:
        pid, nproc = _proc_info()
        if nproc == 1:
            return self._write_single(step, host, extras)
        return self._write_multi(step, host, extras, pid, nproc)

    def _write_single(self, step: int, host: list,
                      extras: dict[str, str] | None) -> str:
        final = self._step_dir(step)
        # Unique per process AND per write: a sync save racing a stale
        # async writer must never share (or rmtree) the other's tmp dir.
        self._seq = getattr(self, "_seq", 0) + 1
        tmp = f"{final}.tmp.{os.getpid()}.{self._seq}"
        os.makedirs(tmp)
        manifest = {"step": step, "leaves": {}}
        for key, shards, meta in host:
            files = []
            for i, (start, data) in enumerate(shards):
                fname = f"{key}.shard{i}.npy"
                files.append(_save_shard(tmp, fname, start, data))
            manifest["leaves"][key] = {**meta, "shards": files}
        with open(os.path.join(tmp, _MANIFEST), "w") as f:
            json.dump(manifest, f)
        for fname, text in (extras or {}).items():
            with open(os.path.join(tmp, fname), "w") as f:
                f.write(text)
        f = chaos.hit("checkpoint.commit", str(step))
        if f is not None and f.action == "crash":
            # Crash between shard write and the commit rename: every
            # shard and the manifest are on disk in the tmp dir, but
            # the step never becomes visible — exactly the state a
            # process death here leaves behind. restore() must fall
            # back to the previous complete step.
            raise CheckpointError(
                f"chaos: crashed before committing step {step} "
                f"(uncommitted shards left in {tmp})")
        with open(os.path.join(tmp, _COMPLETE), "w") as f:
            f.write("ok\n")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._gc()
        log.info("checkpoint saved", kv={"step": step, "dir": final})
        chaos.note_ok("checkpoint.save", final)
        return final

    def _write_multi(self, step: int, host: list,
                     extras: dict[str, str] | None,
                     pid: int, nproc: int) -> str:
        """Cross-host save into a SHARED step dir: every process writes
        its owned shards + ``manifest.p<pid>.json`` (each file committed
        via tmp+rename); process 0 barriers on all N manifests and then
        writes the completion marker. A crashed peer ⇒ barrier timeout ⇒
        no marker ⇒ restore ignores the step (never a silent partial)."""
        final = self._step_dir(step)
        os.makedirs(final, exist_ok=True)
        if os.path.exists(os.path.join(final, _COMPLETE)):
            # A COMMITTED checkpoint of this step already exists.
            # Re-writing in place would delete its marker/manifests
            # before the new save commits — a peer crash at the
            # barrier would then have destroyed good committed state.
            # Keep the committed copy; a caller that truly wants a
            # fresh save of the same step deletes the dir first.
            # Guard against SILENT divergence: if what we were asked to
            # save has a different parameter space than what is
            # committed (keys/shapes/dtypes), keeping the old copy
            # would hide a real bug — refuse loudly. Equal-structure
            # re-saves keep the committed copy with a warning (values
            # are not compared; that would need a full read-back).
            mf_path = os.path.join(final, f"manifest.p{pid}.json")
            committed = None
            try:
                with open(mf_path) as f:
                    committed = json.load(f).get("leaves", {})
            except (OSError, ValueError):
                pass  # pre-guard layout or unreadable: keep-and-warn
            if committed is not None:
                mine = json.loads(json.dumps(
                    {key: meta for key, _, meta in host}))
                theirs = {k: {a: b for a, b in v.items()
                              if a != "shards"}
                          for k, v in committed.items()}
                if mine != theirs:
                    raise ClusterError(
                        f"checkpoint step {step} is already committed "
                        f"with a different parameter space — refusing "
                        f"to silently keep the stale copy; delete "
                        f"{final} to re-save this step")
            log.warning(
                "checkpoint step already committed; keeping the "
                "committed copy (tensor values are not compared)",
                kv={"step": step, "dir": final, "process": pid})
            return final
        # Stale-attempt debris (a previous save of this step that timed
        # out or crashed) must never satisfy the barrier: process 0
        # clears EVERY old manifest before writing anything; peers
        # clear their own. A peer's fresh manifest caught in process
        # 0's sweep surfaces as a barrier timeout — loud failure,
        # never a silent merge of two attempts' shards.
        if pid == 0:
            for p in _glob.glob(
                    os.path.join(_glob.escape(final), "manifest*.json")):
                os.unlink(p)
            _rm_f(os.path.join(final, _COMPLETE))
        else:
            _rm_f(os.path.join(final, f"manifest.p{pid}.json"))
        manifest = {"step": step, "process": pid,
                    "num_processes": nproc, "leaves": {}}
        for key, shards, meta in host:
            files = []
            for i, (start, data) in enumerate(shards):
                fname = f"{key}.p{pid}.shard{i}.npy"
                files.append(_save_shard(final, fname, start, data))
            manifest["leaves"][key] = {**meta, "shards": files}
        mf_name = f"manifest.p{pid}.json"
        mf_json = json.dumps(manifest)
        _atomic_write(final, mf_name, mf_json)
        deadline = time.monotonic() + self.barrier_timeout
        if pid == 0:
            # glob.escape: a checkpoint dir containing [ ? * (legal
            # POSIX path chars) must not turn the pattern into a
            # character class that matches nothing — that presents as
            # a spurious barrier timeout only on multi-host runs.
            pat = os.path.join(_glob.escape(final), "manifest.p*.json")
            barrier_bo = retry.Backoff(base=0.05, cap=0.25)
            while len(_glob.glob(pat)) < nproc:
                if time.monotonic() > deadline:
                    # Leave the dir clearly incomplete for the next
                    # attempt: drop our own manifest too.
                    _rm_f(os.path.join(final, "manifest.p0.json"))
                    raise ClusterError(
                        f"checkpoint step {step}: only "
                        f"{len(_glob.glob(pat))}/{nproc} process "
                        f"manifests arrived within {self.barrier_timeout}s"
                        " — not committing"
                    )
                barrier_bo.sleep()
            f = chaos.hit("checkpoint.commit", str(step))
            if f is not None and f.action == "crash":
                # Crash after every shard landed but before the commit
                # marker: the step must stay invisible to restore().
                raise CheckpointError(
                    f"chaos: crashed before committing step {step} "
                    f"(no {_COMPLETE} marker written)")
            for fname, text in (extras or {}).items():
                _atomic_write(final, fname, text)
            _atomic_write(final, _COMPLETE, "ok\n")
            self._gc()
        else:
            # Hold until process 0 commits, RE-ASSERTING our manifest:
            # a peer that outran process 0 has its manifest swept by
            # p0's stale-debris cleanup — rewriting it (idempotent,
            # shards unchanged) turns that race into at most a ~1 s
            # delay instead of a spurious barrier timeout.
            marker = os.path.join(final, _COMPLETE)
            mf_path = os.path.join(final, mf_name)
            commit_bo = retry.Backoff(base=0.2, cap=0.5)
            while not os.path.exists(marker):
                if time.monotonic() > deadline:
                    raise ClusterError(
                        f"checkpoint step {step}: process 0 did not "
                        f"commit within {self.barrier_timeout}s")
                if not os.path.exists(mf_path):
                    _atomic_write(final, mf_name, mf_json)
                commit_bo.sleep()
        log.info("checkpoint shards saved",
                 kv={"step": step, "dir": final, "process": pid})
        chaos.note_ok("checkpoint.save", final)
        return final

    # ---------------------------------------------------------- restore

    def steps(self) -> list[int]:
        """Complete checkpoint steps, ascending."""
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and os.path.exists(
                os.path.join(self.directory, name, _COMPLETE)
            ):
                try:
                    out.append(int(name[5:]))
                except ValueError:
                    continue
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    def restore(self, treedef_like: Any, step: int | None = None,
                shardings: Any | None = None) -> Any:
        """Rebuild the pytree saved at ``step`` (default: latest).

        ``treedef_like`` supplies the tree structure (e.g. an abstract
        state from ``jax.eval_shape`` or a live pytree); ``shardings``,
        when given, is a matching pytree of NamedSharding for device
        placement (the resume-into-mesh path).

        Runs as a ``checkpoint.restore/<step>`` region (annotate seam:
        goodput ledger checkpoint leg + trace span) — a mid-run
        restore blocks the loop and must be attributable."""
        from ptype_tpu.metrics import annotate

        if step is None:
            step = self.latest_step()
            if step is None:
                raise ClusterError(
                    f"no complete checkpoint under {self.directory}"
                )
        with annotate(f"checkpoint.restore/{step}"):
            return self._restore(treedef_like, step, shardings)

    def _restore(self, treedef_like: Any, step: int,
                 shardings: Any | None) -> Any:
        sdir = self._step_dir(step)
        manifest = _merged_manifest(sdir, step)

        leaves, treedef = jax.tree_util.tree_flatten_with_path(treedef_like)
        shard_leaves = (
            jax.tree_util.tree_leaves(shardings) if shardings is not None
            else [None] * len(leaves)
        )
        if len(shard_leaves) != len(leaves):
            raise ClusterError(
                "restore: shardings tree does not match state tree"
            )
        out = []
        for (path, _), sh in zip(leaves, shard_leaves):
            key = _flat_key(path)
            entry = manifest["leaves"].get(key)
            if entry is None:
                raise ClusterError(
                    f"restore: checkpoint {step} has no leaf {key!r}"
                )
            dtype = _resolve_dtype(entry["dtype"])
            full = np.zeros(entry["shape"], dtype=dtype)
            if full.ndim == 0:
                # The shard file holds (1,): np.ascontiguousarray gave
                # the scalar a dimension at save time, and int() of a
                # (1,) jax array is an error on this installation.
                full = _load_shard(sdir, entry["shards"][0],
                                   dtype).reshape(())
            else:
                _check_tiling(key, entry["shards"], entry["shape"])
                for rec in entry["shards"]:
                    data = _load_shard(sdir, rec, dtype)
                    sl = tuple(
                        slice(st, st + sz)
                        for st, sz in zip(rec["start"], data.shape)
                    )
                    full[sl] = data
            arr = jax.device_put(full, sh) if sh is not None else (
                jax.numpy.asarray(full)
            )
            out.append(arr)
        chaos.note_ok("checkpoint.restore", str(step))
        return jax.tree_util.tree_unflatten(treedef, out)

    # ----------------------------------------------------------- intern

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}")

    def _gc(self) -> None:
        steps = self.steps()
        for old in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self._step_dir(old), ignore_errors=True)


def _save_shard(dirpath: str, fname: str, start: list,
                data: np.ndarray) -> dict:
    """Write one shard file (tmp+rename — shared multi-writer dirs must
    never expose partial files) and return its manifest record, which
    carries a crc32 of the logical bytes so restore can tell disk
    corruption from a clean load."""
    raw = data.dtype.kind == "V"
    tmp = os.path.join(dirpath, f".tmp.{fname}.{os.getpid()}")
    with open(tmp, "wb") as f:
        if raw:
            # Extension dtypes (bfloat16 & friends) have no npy cast
            # path: np.save writes them as opaque void and restore
            # cannot assign them back. Persist the raw bytes; the
            # manifest keeps the logical dtype and restore views them
            # back through it.
            payload = data.tobytes()
            crc = zlib.crc32(payload) & 0xFFFFFFFF
            np.save(f, np.frombuffer(payload, np.uint8))
        else:
            # crc32 over the array's own buffer — no tobytes() copy
            # (a multi-GB shard must not transiently double in memory).
            data = np.ascontiguousarray(data)
            crc = zlib.crc32(data) & 0xFFFFFFFF
            np.save(f, data)
    os.replace(tmp, os.path.join(dirpath, fname))
    cf = chaos.hit("checkpoint.shard", fname)
    if cf is not None and cf.action == "corrupt":
        _corrupt_file(os.path.join(dirpath, fname))
    return {"file": fname, "start": start,
            "shape": list(data.shape), "raw": raw, "crc32": crc}


def _corrupt_file(path: str) -> None:
    """Chaos ``checkpoint.shard``/``corrupt``: flip one byte in the
    middle of the file AFTER the manifest checksum was computed — the
    bit-rot restore must catch, never silently load."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1) or b"\x00"
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))


def _atomic_write(dirpath: str, fname: str, text: str) -> None:
    tmp = os.path.join(dirpath, f".tmp.{fname}.{os.getpid()}")
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, os.path.join(dirpath, fname))


def _rm_f(path: str) -> None:
    try:
        os.unlink(path)
    except FileNotFoundError:
        pass


def _index_start(index: tuple, shape: tuple) -> tuple[int, ...]:
    """Shard slice → start offsets (None start = 0)."""
    out = []
    for sl, _ in zip(index, shape):
        out.append(0 if sl.start is None else int(sl.start))
    return tuple(out)


def _merged_manifest(sdir: str, step: int) -> dict:
    """Union of the step's manifests: the single-writer ``manifest.json``
    and/or every per-process ``manifest.p<i>.json``. Leaf shard lists
    concatenate (file names are process-unique); duplicate boxes (e.g. a
    legacy save's replicated copies) keep the first occurrence so the
    tiling check still holds."""
    paths = sorted(
        p for p in _glob.glob(
            os.path.join(_glob.escape(sdir), "manifest*.json")))
    if not paths:
        raise ClusterError(f"restore: step {step} has no manifest")
    per_proc = [p for p in paths
                if os.path.basename(p) != "manifest.json"]
    if per_proc and len(per_proc) != len(paths):
        raise ClusterError(
            f"restore: step {step} mixes a single-writer manifest.json "
            f"with per-process manifests — two save modes' debris")
    merged: dict[str, dict] = {}
    expected_nproc: int | None = None
    for path in paths:
        with open(path) as f:
            m = json.load(f)
        nproc = m.get("num_processes")
        if nproc is not None:
            if expected_nproc is None:
                expected_nproc = nproc
            elif nproc != expected_nproc:
                raise ClusterError(
                    f"restore: step {step} manifests disagree on "
                    f"num_processes ({expected_nproc} vs {nproc}) — "
                    "mixed save attempts")
        for key, entry in m["leaves"].items():
            tgt = merged.setdefault(
                key, {k: v for k, v in entry.items() if k != "shards"})
            tgt.setdefault("shards", []).extend(entry["shards"])
    if expected_nproc is not None and len(per_proc) != expected_nproc:
        raise ClusterError(
            f"restore: step {step} has {len(per_proc)} process manifests "
            f"but the save ran with num_processes={expected_nproc} — "
            "incomplete (uncommitted?) save")
    for entry in merged.values():
        seen: set[tuple] = set()
        uniq = []
        for rec in entry["shards"]:
            box = (tuple(rec["start"]), tuple(rec["shape"]))
            if box in seen:
                continue
            seen.add(box)
            uniq.append(rec)
        entry["shards"] = uniq
    return {"step": step, "leaves": merged}


def _resolve_dtype(name: str) -> np.dtype:
    """Manifest dtype string → dtype, including ml_dtypes extension
    types (bfloat16 etc.) that plain numpy may not resolve by name."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _load_shard(sdir: str, rec: dict, dtype: np.dtype) -> np.ndarray:
    try:
        loaded = np.load(os.path.join(sdir, rec["file"]))
    except (OSError, ValueError) as e:
        # Unreadable/garbled npy (corruption can land in the header):
        # same contract as a checksum mismatch — name the shard.
        raise CheckpointError(
            f"restore: shard {rec['file']!r} is corrupt "
            f"(unreadable: {e})") from e
    want = rec.get("crc32")
    if want is not None:
        # Checksum the loaded buffer in place (raw shards: the uint8
        # payload BEFORE the extension-dtype view, matching what save
        # hashed) — no tobytes() copy of a possibly multi-GB shard.
        got = zlib.crc32(np.ascontiguousarray(loaded)) & 0xFFFFFFFF
        if got != want:
            raise CheckpointError(
                f"restore: shard {rec['file']!r} is corrupt: crc32 "
                f"{got:#010x} != manifest {want:#010x}")
    if rec.get("raw"):
        loaded = loaded.view(dtype).reshape(rec["shape"])
    return np.asarray(loaded)


def _check_tiling(key: str, shards: list[dict], shape: list[int]) -> None:
    """Shards must tile the array exactly: total element count matches
    AND no two boxes overlap (a raw count can be satisfied by overlaps
    masking gaps). O(n²) boxes, n = shard count — tiny."""
    total = int(np.prod(shape)) if shape else 1
    boxes = [(tuple(r["start"]), tuple(r["shape"])) for r in shards]
    covered = sum(int(np.prod(s)) for _, s in boxes)
    overlap = any(
        all(a0 < b0 + bs and b0 < a0 + as_
            for a0, as_, b0, bs in zip(sa, za, sb, zb))
        for i, (sa, za) in enumerate(boxes)
        for sb, zb in boxes[i + 1:]
    )
    if covered != total or overlap:
        raise ClusterError(
            f"restore: leaf {key!r} shards cover {covered} of {total} "
            f"elements{' with overlaps' if overlap else ''} — corrupt "
            "or partial checkpoint (saved from a different process set?)"
        )


class ZeroCheckpoint:
    """Checkpoint tier for ZeRO-1 sharded optimizer state
    (parallel/zero.ZeroState): the per-bucket flat Adam moments are
    jax Arrays sharded over the data axis, so :class:`Checkpointer`
    already writes them as per-replica shard files with a crc32 each
    (the existing per-shard machinery, reused verbatim) — one
    ``bucketNNNNN.{mu,nu}.shard<r>.npy`` per replica shard. The shard
    PLAN rides the step's atomic commit as ``zero_plan.json`` (written
    before ``.complete``), which is what makes restore **reshardable**:
    bucket slots are replica-count-independent, only the tail pads
    depend on N, so a state saved from 8 replicas restores onto 4 (or
    4 onto 8) by strip-pad → re-pad → re-place (ZeroState.
    load_state_tree). A corrupt shard surfaces as
    :class:`~ptype_tpu.errors.CheckpointError` naming the file, same
    contract as every other restore path."""

    def __init__(self, directory: str, keep: int = 3):
        self._ckpt = Checkpointer(directory, keep=keep)

    def latest_step(self) -> int | None:
        return self._ckpt.latest_step()

    def save(self, step: int, zero_state) -> str:
        """Persist the sharded moments + schedule count + plan
        manifest as one committed step dir."""
        return self._ckpt.save(
            step, zero_state.state_tree(),
            extras={"zero_plan.json": json.dumps(
                zero_state.plan.manifest())})

    def restore_into(self, zero_state, step: int | None = None) -> int:
        """Load a saved step INTO an existing ZeroState (whose plan
        defines the restoring replica count), resharding when the
        saved N differs. Returns the restored step. Raises
        CheckpointError on plan mismatch or shard corruption,
        ClusterError when there is nothing to restore."""
        step = step if step is not None else self._ckpt.latest_step()
        if step is None:
            raise ClusterError(
                f"ZeroCheckpoint: no complete step under "
                f"{self._ckpt.directory}")
        sdir = self._ckpt._step_dir(step)
        try:
            with open(os.path.join(sdir, "zero_plan.json")) as f:
                saved_plan = json.load(f)
        except (OSError, ValueError) as e:
            raise CheckpointError(
                f"ZeroCheckpoint: step {step} has no readable "
                f"zero_plan.json ({e}) — not a sharded-optimizer "
                f"checkpoint") from e
        n_buckets = len(saved_plan.get("buckets", []))
        skeleton = {
            "buckets": {f"{i:05d}": {"mu": 0, "nu": 0}
                        for i in range(n_buckets)},
            "count": 0,
        }
        if getattr(zero_state, "pflat", None) is not None:
            # ZeRO-3: the restoring state holds resident param shards,
            # so pull the saved ones too (state_tree emits them on
            # save; Checkpointer.restore only loads skeleton leaves).
            skeleton["pbuckets"] = {f"{i:05d}": {"p": 0}
                                    for i in range(n_buckets)}
        tree = self._ckpt.restore(skeleton, step=step)
        zero_state.load_state_tree(tree, saved_plan)
        return step


class StoreCheckpoint:
    """Persist / resume a TensorStore namespace (the Store tier).

    Resume is "Join + Store pull" (SURVEY.md §5): a fresh member calls
    ``resume()`` and the parameter space reappears with its bindings —
    the durability role etcd's data-dir played for the reference Store.
    """

    def __init__(self, store, directory: str, keep: int = 3,
                 keys_prefix: str | None = None):
        from ptype_tpu.parallel.tensorstore import TensorStore  # typing

        assert isinstance(store, TensorStore)
        self.store = store
        #: Persist only keys under this prefix (e.g. ``"params/"``) —
        #: a training store also holds transient grads/* whose bytes
        #: match the params'; checkpointing them doubles every save for
        #: state the next step overwrites.
        self.keys_prefix = keys_prefix
        self._ckpt = Checkpointer(directory, keep=keep)

    def latest_step(self) -> int | None:
        """Latest complete step on disk, or None — the is-there-
        anything-to-resume probe (real restore errors then propagate
        from :meth:`resume` instead of being conflated with 'empty')."""
        return self._ckpt.latest_step()

    def save(self, step: int | None = None) -> str:
        from ptype_tpu.parallel.tensorstore import spec_to_json

        keys = self.store.keys()
        if self.keys_prefix:
            keys = [k for k in keys if k.startswith(self.keys_prefix)]
        tree = {k: self.store.get(k) for k in keys}
        step = step if step is not None else max(
            (self.store.epoch(k) for k in keys), default=0
        )
        meta = {
            k: {"spec": spec_to_json(self.store.binding(k).spec),
                "epoch": self.store.epoch(k)}
            for k in keys
        }
        # Meta rides the step's atomic commit (written before .complete),
        # so a crash can never leave a "complete" step resume() rejects.
        return self._ckpt.save(
            step, tree, extras={"store_meta.json": json.dumps(meta)}
        )

    def resume(self, step: int | None = None) -> list[str]:
        """Load the latest (or given) step back into the store; returns
        the restored keys."""
        from ptype_tpu.parallel.tensorstore import spec_from_json

        step = step if step is not None else self._ckpt.latest_step()
        if step is None:
            raise ClusterError("StoreCheckpoint: nothing to resume from")
        sdir = self._ckpt._step_dir(step)
        with open(os.path.join(sdir, "store_meta.json")) as f:
            meta = json.load(f)
        # 0 (not None — None is an empty pytree, not a leaf) marks slots.
        skeleton = {k: 0 for k in meta}
        tree = self._ckpt.restore(skeleton, step=step)
        for key, value in tree.items():
            spec = spec_from_json(meta[key]["spec"])
            self.store.put(key, value, spec=spec,
                           epoch=int(meta[key].get("epoch", 0)))
        return sorted(tree)

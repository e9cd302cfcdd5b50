"""Checkpoint save/restore with elastic resharding + the streaming
checkpointer (ISSUE 10 tentpole).

Format: one .npz per checkpoint (flattened pytree with '/'-joined path
keys) + a meta.json (step, phase/round/chunk cursor, config fingerprint).
Writes are atomic (tmp + rename) and a keep-last-k window is enforced —
the two properties that make checkpoint/restart safe under preemption.

Elasticity: arrays are stored unsharded; ``restore`` device_puts every
leaf onto the *target* shardings, so a checkpoint taken on one mesh
restores onto any other (scale up/down) as long as shapes match. The
streaming engine exploits exactly this: its sharded update steps keep
node/sketch/agg state replicated (core/stream.py), so a stream checkpoint
written on one device count resumes bit-identically on any other — the
restored arrays are plain host numpy, re-``device_put`` by the first
jitted update that consumes them.

``StreamCheckpointer`` is the engine-facing half: ``core/stream.py`` calls
``boundary()`` after every completed chunk update, and the checkpointer
decides whether to persist (every ``every_chunks`` boundaries, at round
boundaries when ``every_chunks`` is 0, or immediately after a SIGTERM —
``install_preemption_handler``). ``on_boundary`` is the chaos hook
(``resilience/chaos.KillSwitch``) used to kill runs at deterministic
points in tests and ``benchmarks/resilience_bench.py``.
"""
from __future__ import annotations

import hashlib
import json
import os
import signal
import tempfile
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np

SEP = "/"


class Preempted(RuntimeError):
    """Raised at a chunk boundary after the preemption-triggered checkpoint
    was written (``StreamCheckpointer.exit_on_preempt``) — the launcher
    catches it and exits cleanly; ``--resume`` picks the run back up."""


class CheckpointMismatchError(RuntimeError):
    """A checkpoint's config fingerprint does not match the resuming run —
    resuming would silently produce garbage, so it is an error instead."""


def _flatten(tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        key = SEP.join(_key_str(k) for k in path)
        arr = np.asarray(leaf)
        if arr.dtype.kind == "V":  # ml_dtypes (bfloat16 etc.): npz-opaque
            arr = arr.view(np.uint16 if arr.dtype.itemsize == 2 else np.uint8)
        flat[key] = arr
    return flat


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def save(ckpt_dir: str, step: int, tree: Any, extra: dict | None = None,
         keep: int = 3) -> str:
    """Atomically write checkpoint ``step``; prune to the newest ``keep``."""
    os.makedirs(ckpt_dir, exist_ok=True)
    flat = _flatten(tree)
    final = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    os.close(fd)
    fd_m, tmp_meta = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    os.close(fd_m)
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **flat)
        # Meta lands (atomically) BEFORE the npz rename: a kill between the
        # two leaves an orphaned meta (invisible — discovery keys off .npz)
        # rather than a meta-less npz that readers would mis-trust.
        meta = {"step": step, **(extra or {})}
        with open(tmp_meta, "w") as f:
            json.dump(meta, f)
        os.replace(tmp_meta, final + ".meta.json")
        os.replace(tmp, final)  # atomic on POSIX
    finally:
        for t in (tmp, tmp_meta):
            if os.path.exists(t):
                os.unlink(t)
    _prune(ckpt_dir, keep)
    return final


def _prune(ckpt_dir: str, keep: int) -> None:
    names = os.listdir(ckpt_dir)
    ckpts = sorted(
        f for f in names if f.startswith("step_") and f.endswith(".npz")
    )
    for old in ckpts[:-keep]:
        os.unlink(os.path.join(ckpt_dir, old))
        meta = os.path.join(ckpt_dir, old + ".meta.json")
        if os.path.exists(meta):
            os.unlink(meta)
    # Orphaned metas (kill before the npz rename, pruned/corrupt npz).
    for f in names:
        if (f.startswith("step_") and f.endswith(".npz.meta.json")
                and not os.path.exists(
                    os.path.join(ckpt_dir, f[: -len(".meta.json")]))):
            try:
                os.unlink(os.path.join(ckpt_dir, f))
            except FileNotFoundError:
                pass


def latest_step(ckpt_dir: str) -> int | None:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [
        int(f[len("step_") : -len(".npz")])
        for f in os.listdir(ckpt_dir)
        if f.startswith("step_") and f.endswith(".npz")
    ]
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, like: Any, shardings: Any | None = None):
    """Rebuild the pytree of ``like`` (a pytree of arrays or
    ShapeDtypeStructs). ``shardings`` (matching pytree of NamedSharding)
    re-shards onto the CURRENT mesh — elastic restore."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    data = np.load(path)
    leaves_with_path = jax.tree_util.tree_leaves_with_path(like)
    vals = []
    for kpath, leaf in leaves_with_path:
        key = SEP.join(_key_str(k) for k in kpath)
        arr = data[key]
        want = np.dtype(leaf.dtype) if not hasattr(leaf.dtype, "itemsize") else leaf.dtype
        if arr.dtype.kind == "u" and np.dtype(want).kind == "V":
            arr = arr.view(want)  # round-trip ml_dtypes (bfloat16) storage
        assert tuple(arr.shape) == tuple(leaf.shape), (key, arr.shape, leaf.shape)
        vals.append(arr)
    treedef = jax.tree_util.tree_structure(like)
    tree = jax.tree_util.tree_unflatten(treedef, vals)
    if shardings is not None:
        tree = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), tree, shardings
        )
    else:
        tree = jax.tree_util.tree_map(jax.device_put, tree)
    meta_path = path + ".meta.json"
    meta = {}
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    return tree, meta


def load_arrays(ckpt_dir: str, step: int) -> tuple[dict, dict]:
    """Load checkpoint ``step`` as a flat ``{key: host ndarray}`` dict plus
    its meta — the shape-agnostic reader the streaming resume path uses
    (it knows its own state shapes; no ``like`` pytree needed)."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = {}
    meta_path = path + ".meta.json"
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    return arrays, meta


def restore_latest_valid(
    ckpt_dir: str,
    valid: Callable[[dict, dict], bool] | None = None,
) -> tuple[dict, dict] | None:
    """Newest loadable checkpoint as ``(arrays, meta)``, or None if the
    directory holds none. A corrupt newest file (impossible via the atomic
    rename, but disks bit-rot) is deleted — npz and its meta together —
    and the walk continues back through the keep-last-k window. ``valid``
    (arrays, meta) lets callers demand semantic completeness (e.g. the
    stream resume cursor keys) with the same walk-back-on-failure."""
    step = latest_step(ckpt_dir)
    while step is not None:
        bad = False
        try:
            arrays, meta = load_arrays(ckpt_dir, step)
            if valid is None or valid(arrays, meta):
                return arrays, meta
            bad = True  # loadable but incomplete → walk back
        except Exception:  # partial/corrupt → try the previous one
            bad = True
        if bad:
            path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
            for p in (path, path + ".meta.json"):
                try:
                    os.unlink(p)
                except FileNotFoundError:
                    pass
        step = latest_step(ckpt_dir)
    return None


def config_fingerprint(**kwargs) -> str:
    """Short sha256 over the repr of the run parameters that must match for
    a resume to be bit-identical (graph extents, chunk size, stage configs).
    Dataclass reprs are deterministic, so equal configs hash equal."""
    blob = json.dumps(
        {k: repr(v) for k, v in sorted(kwargs.items())}, sort_keys=True
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


@dataclass
class StreamCheckpointer:
    """Chunk-boundary checkpoint cadence + SIGTERM handling for the
    streaming engine (``core/stream.py`` calls ``boundary`` after every
    completed chunk update; ``stream_pipeline(..., resume=)`` restores).

    ``every_chunks`` > 0 saves every that-many boundaries; 0 saves at
    round/pass boundaries only. A SIGTERM (``install_preemption_handler``)
    forces a save at the next boundary regardless of cadence, and with
    ``exit_on_preempt`` raises ``Preempted`` right after it so launchers
    under systemd/SLURM exit cleanly with a final checkpoint on disk.

    ``fingerprint`` is stamped into every meta.json; ``stream_pipeline``
    fills it from its own config and refuses to resume from a checkpoint
    whose fingerprint differs (``CheckpointMismatchError``).

    ``on_boundary(phase, round, chunk)`` fires at *every* boundary, after
    any save — the deterministic fault-injection hook
    (``resilience.chaos.KillSwitch``).
    """

    ckpt_dir: str
    every_chunks: int = 0
    keep: int = 3
    fingerprint: str = ""
    exit_on_preempt: bool = False
    on_boundary: Callable | None = None
    _seq: int = field(default=0, repr=False)
    _preempted: bool = field(default=False, repr=False)
    saves: int = field(default=0, repr=False)

    def install_preemption_handler(self) -> None:
        """SIGTERM (the cloud/cluster preemption signal) ⇒ checkpoint at
        the next chunk boundary. Returns via ``signal.signal``'s contract;
        call from the main thread."""
        def handler(signum, frame):
            self._preempted = True

        signal.signal(signal.SIGTERM, handler)

    def want_save(self, at_round_boundary: bool) -> bool:
        if self._preempted:
            return True
        if self.every_chunks > 0:
            return self._seq % self.every_chunks == 0
        return at_round_boundary

    def boundary(self, phase: str, rnd: int, chunk: int,
                 at_round_boundary: bool, payload: Callable[[], dict]) -> None:
        """One completed chunk update. ``(rnd, chunk)`` is the *resume
        cursor* (the next unprocessed chunk, round-boundary normalized);
        ``payload`` lazily materializes the host-side state dict so
        non-saving boundaries cost nothing."""
        self._seq += 1
        preempted = self._preempted
        if self.want_save(at_round_boundary):
            self.save(phase, rnd, chunk, payload())
        if preempted and self.exit_on_preempt:
            raise Preempted(
                f"preempted: checkpoint written at {phase} round {rnd} "
                f"chunk {chunk} under {self.ckpt_dir}"
            )
        if self.on_boundary is not None:
            self.on_boundary(phase, rnd, chunk)

    def save(self, phase: str, rnd: int, chunk: int, arrays: dict) -> str:
        path = save(
            self.ckpt_dir, self._seq, arrays,
            extra={"phase": phase, "round": rnd, "chunk": chunk,
                   "fingerprint": self.fingerprint},
            keep=self.keep,
        )
        self.saves += 1
        self._preempted = False
        return path

    def seed(self, meta: dict) -> None:
        """Continue the save sequence past a restored checkpoint's step.
        Without this a resumed process restarts ``_seq`` at 0 while the
        pre-kill ``step_`` files are still on disk: ``_prune`` keeps the
        lexically newest names, so every post-resume save would be deleted
        on arrival (and ``latest_step`` would keep answering with the
        stale pre-kill checkpoint) until the counter caught up."""
        self._seq = max(self._seq, int(meta.get("step", 0)))

    def restore_latest(self) -> tuple[dict, dict] | None:
        found = restore_latest_valid(self.ckpt_dir)
        if found is not None:
            self.seed(found[1])
        return found

"""Per-chain checkpoint store.

Chains share nothing (the paper's communication-free property), so the
checkpoint layout is **per-chain**: one .npz per chain per step plus a tiny
manifest.  Consequences the tests verify:

  * a chain failure never corrupts other chains' state — restart restores
    the survivors and the failed chain alone re-inits (fault isolation),
  * elastic rescale: restore onto MORE chains (new ones init fresh) or
    FEWER chains (a prefix of the ensemble) without touching the rest,
  * atomicity: writes go to a temp dir, fsync'd, then os.replace'd; a
    half-written checkpoint is never visible under its final name.  The
    OVERWRITE path first renames the old step aside (never `rmtree`s the
    live dir — a crash between delete and publish would lose BOTH
    versions), publishes, fsyncs the parent directory so the rename is
    durable, and only then deletes the aside copy,
  * kill-anywhere leaves garbage that is swept, never trusted: orphaned
    `.tmp_*` write dirs and `.prev_*` aside dirs are reclaimed on manager
    init and at every GC (a `.prev_*` whose final step vanished is the
    crash-between-aside-and-publish window — it is renamed BACK, which
    restores the old checkpoint).

`AsyncCheckpointManager` moves the `np.savez` cost off the training loop:
the caller's `maybe_save` takes a host snapshot (device_get — the only
part that must see a quiescent state) and a background thread publishes
it through the same atomic `save_checkpoint`.  Bounded staleness: a new
save is not ACCEPTED until the previous one is durable, so at any point
the newest published step is at most one save interval behind the
training loop — resume after a crash loses at most one EM round.

Format: flat {pytree-path: array} in numpy .npz — no pickle, portable.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import zipfile

import jax
import jax.numpy as jnp
import numpy as np

#: tmp dirs owned by an in-flight save_checkpoint of THIS process — the
#: stale-garbage sweep must never reclaim a dir another thread (e.g. the
#: async writer) is still filling.
_ACTIVE_TMP: set = set()
_ACTIVE_LOCK = threading.Lock()


#: What restoring a missing, torn (truncated .npz) or mislabelled
#: checkpoint raises — file-format errors only.  Restore paths that
#: fall back to a fresh init catch exactly these, so a device or runtime
#: fault during a restore surfaces instead of passing for a torn file.
TORN_CHECKPOINT_ERRORS = (OSError, KeyError, ValueError, zipfile.BadZipFile)


class CheckpointNotFoundError(FileNotFoundError):
    """A requested checkpoint step does not exist (never written, or
    already garbage-collected).  Subclasses FileNotFoundError so callers
    that catch the raw OSError family keep working, but the message — and
    the `step` / `available_steps` attributes — name what WAS requested
    and what the store actually holds, so a serving reload or a restart
    path surfaces an actionable error instead of a bare ENOENT."""

    def __init__(self, ckpt_dir: str, step: int, available: list):
        self.step = step
        self.available_steps = list(available)
        super().__init__(
            f"no checkpoint for step {step} under {ckpt_dir!r}; "
            f"available steps: {self.available_steps or 'none'}")


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in flat}, treedef


def _chain_slice(tree, i):
    return jax.tree.map(lambda x: x[i] if hasattr(x, "ndim") and x.ndim > 0
                        else x, tree)


def _fsync_dir(path: str):
    """fsync a DIRECTORY so a rename inside it is durable — os.replace
    alone only orders the rename in page cache; a power cut could undo
    a 'published' checkpoint without this."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _list_steps(ckpt_dir: str) -> list:
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                  if d.startswith("step_"))


def _step_dir(ckpt_dir: str, step: int) -> str:
    """Resolve a step's directory or raise the typed not-found error."""
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    if not os.path.isdir(d):
        raise CheckpointNotFoundError(ckpt_dir, step, _list_steps(ckpt_dir))
    return d


def save_checkpoint(ckpt_dir: str, step: int, state: dict, *,
                    n_chains: int | None = None, extra: dict | None = None):
    """state: pytree whose array leaves have a leading chain dim (scalars
    like the opt step counter are replicated into every chain file)."""
    if n_chains is None:
        n_chains = jax.tree.leaves(state)[0].shape[0]
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    aside = os.path.join(ckpt_dir, f".prev_step_{step:08d}")
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_")
    with _ACTIVE_LOCK:
        _ACTIVE_TMP.add(tmp)
    try:
        for i in range(n_chains):
            flat, _ = _flatten(_chain_slice(state, i))
            path = os.path.join(tmp, f"chain_{i:03d}.npz")
            with open(path, "wb") as f:
                np.savez(f, **flat)
                f.flush()
                os.fsync(f.fileno())
        manifest = {"step": step, "n_chains": n_chains,
                    "extra": extra or {}}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        # publish: the OLD step (if any) is renamed ASIDE, never deleted
        # before the new one lands — a crash in the aside→publish window
        # leaves the old version recoverable (`_sweep_stale` renames it
        # back), so no window loses both versions.
        if os.path.isdir(aside):        # stale aside from an older crash
            shutil.rmtree(aside)
        had_old = os.path.exists(final)
        if had_old:
            os.replace(final, aside)
        os.replace(tmp, final)          # atomic publish
        _fsync_dir(ckpt_dir)            # make the rename(s) durable
        if had_old:
            shutil.rmtree(aside, ignore_errors=True)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE_TMP.discard(tmp)
    return final


def latest_step(ckpt_dir: str) -> int | None:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
             if d.startswith("step_")
             and os.path.exists(os.path.join(ckpt_dir, d, "manifest.json"))]
    return max(steps) if steps else None


def list_chains(ckpt_dir: str, step: int) -> list[int]:
    d = _step_dir(ckpt_dir, step)
    return sorted(int(f.split("_")[1].split(".")[0])
                  for f in os.listdir(d) if f.startswith("chain_"))


def _load_manifest(step_dir: str, step: int) -> dict:
    """Read + validate a step's manifest (handle closed promptly — the
    old `json.load(open(...))` leaked the fd until GC).  A manifest whose
    recorded step disagrees with the directory name means a torn or
    hand-copied checkpoint; restoring it silently would resume training
    from the wrong point, so fail loudly instead."""
    mpath = os.path.join(step_dir, "manifest.json")
    if not os.path.exists(mpath):
        ckpt_dir = os.path.dirname(step_dir)
        raise CheckpointNotFoundError(ckpt_dir, step, _list_steps(ckpt_dir))
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("step") != step:
        raise ValueError(
            f"checkpoint manifest in {step_dir} records step "
            f"{manifest.get('step')!r}, expected {step} — torn or "
            "mislabelled checkpoint")
    return manifest


def _unflatten_into(template_chain, flat):
    paths, treedef = jax.tree_util.tree_flatten_with_path(template_chain)
    leaves = []
    for path, tmpl in paths:
        key = jax.tree_util.keystr(path)
        arr = flat[key]
        leaves.append(jnp.asarray(arr, dtype=tmpl.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def read_manifest(ckpt_dir: str, step: int) -> dict:
    """Public validated-manifest read — what a serving-tier reload uses
    to vet a checkpoint before paying to load any chain file.  Raises
    `CheckpointNotFoundError` (naming the available steps) on a missing/
    GC'd step, ValueError on a torn/mislabelled manifest."""
    return _load_manifest(_step_dir(ckpt_dir, step), step)


def restore_checkpoint(ckpt_dir: str, step: int, template):
    """Restore all chains recorded in the manifest; template is a pytree
    with the target leading chain dim (its values are ignored).  The
    manifest's chain count must MATCH the template's — a hot-reloading
    service that silently changed ensemble size mid-stream would break
    every [M]-shaped jit signature downstream; elastic rescale is the
    explicit `restore_elastic` path."""
    d = _step_dir(ckpt_dir, step)
    manifest = _load_manifest(d, step)
    n = manifest["n_chains"]
    target = jax.tree.leaves(template)[0].shape[0]
    if n != target:
        raise ValueError(
            f"checkpoint at step {step} holds {n} chains, template "
            f"expects {target} — use restore_elastic for rescale")
    chains = []
    tmpl0 = _chain_slice(template, 0)
    for i in range(n):
        with np.load(os.path.join(d, f"chain_{i:03d}.npz")) as z:
            chains.append(_unflatten_into(tmpl0, dict(z)))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *chains)
    return stacked, manifest


def restore_chain(ckpt_dir: str, step: int, chain: int, template_chain):
    """Restore ONE chain's pytree slice (no leading chain dim) — the
    supervisor's restart path: a failed chain re-reads its own file and
    nobody else's.  Raises on a missing/corrupt/truncated file; the
    caller decides the fallback (fresh init per the recovery policy)."""
    d = _step_dir(ckpt_dir, step)
    _load_manifest(d, step)
    with np.load(os.path.join(d, f"chain_{chain:03d}.npz")) as z:
        return _unflatten_into(template_chain, dict(z))


def restore_elastic(ckpt_dir: str, step: int, template, init_fn,
                    *, missing_ok: bool = True):
    """Elastic restore onto `template`'s chain count.

    Fewer target chains → restore a prefix.  More → missing chains come
    from `init_fn(chain_index)` (fresh ensemble members).  Corrupt or
    missing chain files likewise fall back to init_fn (fault isolation).
    """
    d = _step_dir(ckpt_dir, step)
    manifest = _load_manifest(d, step)
    target = jax.tree.leaves(template)[0].shape[0]
    tmpl0 = _chain_slice(template, 0)
    chains, restored = [], []
    for i in range(target):
        path = os.path.join(d, f"chain_{i:03d}.npz")
        try:
            with np.load(path) as z:
                chains.append(_unflatten_into(tmpl0, dict(z)))
            restored.append(i)
        except TORN_CHECKPOINT_ERRORS:
            if not missing_ok:
                raise
            chains.append(init_fn(i))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *chains)
    return stacked, {"restored_chains": restored, "step": manifest["step"],
                     "extra": manifest.get("extra", {})}


def sweep_stale(ckpt_dir: str) -> dict:
    """Reclaim crash garbage under `ckpt_dir` — safe to call any time
    (a single-writer store; in-flight tmp dirs of THIS process are
    registered and skipped):

      * `.tmp_*`  — a save killed mid-write; the dir never published, so
        it is pure garbage → removed,
      * `.prev_step_X` with `step_X` PRESENT — the crash hit after
        publish but before aside cleanup → the aside is garbage,
      * `.prev_step_X` with `step_X` MISSING — the crash hit between
        rename-aside and publish; the aside holds the only complete copy
        of that step → renamed BACK (the old checkpoint is restored).

    Returns {"removed_tmp": n, "removed_aside": n, "recovered": [steps]}.
    """
    out = {"removed_tmp": 0, "removed_aside": 0, "recovered": []}
    if not os.path.isdir(ckpt_dir):
        return out
    with _ACTIVE_LOCK:
        active = set(_ACTIVE_TMP)
    for name in os.listdir(ckpt_dir):
        path = os.path.join(ckpt_dir, name)
        if name.startswith(".tmp_") and path not in active:
            shutil.rmtree(path, ignore_errors=True)
            out["removed_tmp"] += 1
        elif name.startswith(".prev_step_"):
            final = os.path.join(ckpt_dir, name[len(".prev_"):])
            if os.path.isdir(final):
                shutil.rmtree(path, ignore_errors=True)
                out["removed_aside"] += 1
            else:
                os.replace(path, final)
                out["recovered"].append(int(name.rsplit("_", 1)[1]))
    return out


class CheckpointManager:
    """Keeps the last `keep` checkpoints, saves every `interval` steps.
    Crash garbage (orphaned `.tmp_*` / `.prev_*` dirs from a killed
    writer) is swept on init and at every GC — see `sweep_stale`."""

    def __init__(self, ckpt_dir: str, interval: int = 100, keep: int = 3):
        self.dir = ckpt_dir
        self.interval = interval
        self.keep = keep
        os.makedirs(ckpt_dir, exist_ok=True)
        sweep_stale(ckpt_dir)

    def maybe_save(self, step: int, state, extra=None):
        if step % self.interval:
            return None
        path = save_checkpoint(self.dir, step, state, extra=extra)
        self._gc()
        return path

    def latest_durable(self) -> int | None:
        """Newest PUBLISHED step — what a restart can actually restore
        (an in-flight write is invisible until its atomic publish)."""
        return latest_step(self.dir)

    def flush(self):
        """Synchronous manager: every accepted save is already durable."""

    def close(self):
        self.flush()

    def _gc(self):
        sweep_stale(self.dir)
        steps = sorted(int(d.split("_")[1]) for d in os.listdir(self.dir)
                       if d.startswith("step_"))
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)


class AsyncCheckpointManager(CheckpointManager):
    """Background-writer checkpointing with a bounded-staleness
    guarantee (DESIGN.md §Elastic-training).

    `maybe_save` splits the save into the part that must block the
    training loop — `jax.device_get(state)`, a host snapshot of the
    round-boundary state — and the part that must not: serializing +
    fsync'ing the .npz files, which a daemon thread runs through the
    same crash-consistent `save_checkpoint` (atomic publish untouched,
    so kill-mid-write still never corrupts a published step).

    **Bounded staleness.**  A new save is not accepted until the
    previous one is DURABLE (`maybe_save` waits on the in-flight write
    before taking the next snapshot).  At any instant the newest
    published step is therefore at most one save interval older than
    the loop — with the elastic runtime's save-every-round cadence,
    resume after a crash loses at most ONE EM round.  The wait is
    normally free: the write overlaps the following round's compute,
    which is the whole point.

    **Graceful drain.**  `flush()` blocks until the in-flight write is
    published (the SIGTERM → flush → exit-resumable path); `close()`
    flushes and stops the writer.  A writer-thread failure is re-raised
    on the next `maybe_save`/`flush` — an async checkpoint that cannot
    persist must not fail silently.
    """

    def __init__(self, ckpt_dir: str, interval: int = 1, keep: int = 3):
        super().__init__(ckpt_dir, interval=interval, keep=keep)
        self._job = None            # (step, snapshot, extra) or None
        self._job_ready = threading.Event()   # a job is queued
        self._job_done = threading.Event()    # no job queued or writing
        self._job_done.set()
        self._stop = False
        self._error = None
        self._lock = threading.Lock()
        self.stats = {"writes": 0, "waits": 0, "wait_s": 0.0}
        self._thread = threading.Thread(
            target=self._writer, name="ckpt-writer", daemon=True)
        self._thread.start()

    # ---- writer thread ------------------------------------------------
    def _writer(self):
        while True:
            self._job_ready.wait()
            with self._lock:
                if self._stop and self._job is None:
                    return
                job, self._job = self._job, None
                self._job_ready.clear()
            if job is None:
                continue
            step, snap, extra = job
            try:
                save_checkpoint(self.dir, step, snap, extra=extra)
                self._gc()
                self.stats["writes"] += 1
            except BaseException as e:  # noqa: BLE001 — surfaced to caller
                with self._lock:
                    self._error = e
            finally:
                self._job_done.set()

    def _raise_pending_error(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise err

    # ---- caller API ----------------------------------------------------
    def maybe_save(self, step: int, state, extra=None):
        """Snapshot `state` to host and enqueue the durable write.
        Returns the final path the write WILL publish (None off-interval).
        Blocks only until the PREVIOUS write is durable (staleness bound)
        and the host copy is taken — never for this write itself."""
        if step % self.interval:
            return None
        if not self._job_done.is_set():
            import time
            t0 = time.time()
            self._job_done.wait()
            self.stats["waits"] += 1
            self.stats["wait_s"] += time.time() - t0
        self._raise_pending_error()
        # the host-copy double buffer: np.array FORCES a fresh host
        # allocation per leaf (device_get alone can alias the caller's
        # buffer on CPU backends, which a donated/mutated buffer would
        # then corrupt mid-write); the writer owns this snapshot until
        # its publish, independent of anything the loop does next.
        snap = jax.tree.map(lambda x: np.array(jax.device_get(x)), state)
        with self._lock:
            self._job = (step, snap, extra)
            self._job_done.clear()
            self._job_ready.set()
        return os.path.join(self.dir, f"step_{step:08d}")

    def flush(self):
        """Block until the in-flight write (if any) is published —
        the graceful-drain half of the preemption protocol."""
        self._job_done.wait()
        self._raise_pending_error()

    def close(self):
        self.flush()
        with self._lock:
            self._stop = True
            self._job_ready.set()
        self._thread.join(timeout=30.0)
        self._raise_pending_error()

"""Checkpoint / resume for RCA training and replay state (orbax).

The reference has no checkpointing — each experiment is run-to-completion and
the archive folder is the only persisted state (SURVEY.md §5).  Training a
GNN RCA model is iterative, so this framework adds real checkpoint/resume:
params + opt_state + step counter via orbax-checkpoint (arrays) with the
pytree structure pickled alongside (optax states are namedtuples, which a
bare orbax restore would flatten into lists/dicts), plus a pure-pickle
fallback for environments without orbax.

Crash-safety contract: each save writes the full state into a fresh
``v<step>`` version directory FIRST, then atomically publishes it by
``os.replace``-ing ``meta.json`` (whose ``version`` field names the live
directory), then garbage-collects older versions.  A kill at any point
leaves ``meta.json`` referencing a complete state — the previous one if the
new version wasn't published yet — so a checkpointed run is always
resumable.  Restore also accepts the legacy flat layout (state files next
to meta.json) for checkpoints written before versioning.
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
from pathlib import Path
from typing import Any, Optional, Tuple


def _try_orbax():
    try:
        import orbax.checkpoint as ocp
        return ocp
    except ImportError:
        return None


def _write_state(state_dir: Path, host) -> str:
    """Write (params, opt_state) into state_dir; returns backend name."""
    import jax
    state_dir.mkdir(parents=True, exist_ok=True)
    ocp = _try_orbax()
    if ocp is not None:
        leaves, treedef = jax.tree_util.tree_flatten(host)
        target = (state_dir / "state.orbax").resolve()
        if target.exists():        # same-step re-save of an unpublished dir
            shutil.rmtree(target)
        ocp.PyTreeCheckpointer().save(target, leaves)
        with open(state_dir / "treedef.pkl", "wb") as f:
            pickle.dump(treedef, f)
        return "orbax"
    with open(state_dir / "state.pkl", "wb") as f:
        pickle.dump(host, f)
    return "pickle"


def save_train_state(path: Path, params: Any, opt_state: Any,
                     step: int, meta: Optional[dict] = None) -> str:
    """Persist a training state; returns the backend used ("orbax"/"pickle").

    Writes ``path/v<step>/`` first, publishes it by atomically replacing
    ``path/meta.json``, then removes superseded version dirs — see the
    module docstring's crash-safety contract."""
    import jax
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    host = jax.tree_util.tree_map(jax.device_get, (params, opt_state))
    version = f"v{step}"
    backend = _write_state(path / version, host)
    # publish: meta written to a temp file then atomically moved into place;
    # caller meta must not clobber the step/version keys
    tmp = path / "meta.json.tmp"
    tmp.write_text(json.dumps({**(meta or {}),
                               "step": step, "version": version}))
    os.replace(tmp, path / "meta.json")
    # GC superseded versions (and any legacy flat state files)
    for old in path.glob("v*"):
        if old.name != version and old.is_dir():
            shutil.rmtree(old, ignore_errors=True)
    for legacy in ("state.orbax", "state.pkl", "treedef.pkl"):
        p = path / legacy
        if p.is_dir():
            shutil.rmtree(p, ignore_errors=True)
        elif p.exists():
            p.unlink()
    return backend


def restore_train_state(path: Path) -> Tuple[Any, Any, int, dict]:
    """Restore (params, opt_state, step, meta) with original pytree structure."""
    import jax
    path = Path(path)
    meta = json.loads((path / "meta.json").read_text())
    step = int(meta.pop("step", 0))
    state_dir = path / meta.pop("version") if "version" in meta else path
    orbax_dir = state_dir / "state.orbax"
    if orbax_dir.exists():
        ocp = _try_orbax()
        if ocp is None:
            raise RuntimeError(
                f"{path} was written with orbax-checkpoint, which is not "
                "importable here — install orbax-checkpoint or restore on a "
                "machine that has it.")
        leaves = ocp.PyTreeCheckpointer().restore(orbax_dir.resolve())
        with open(state_dir / "treedef.pkl", "rb") as f:
            treedef = pickle.load(f)
        params, opt_state = jax.tree_util.tree_unflatten(treedef, leaves)
        return params, opt_state, step, meta
    with open(state_dir / "state.pkl", "rb") as f:
        params, opt_state = pickle.load(f)
    return params, opt_state, step, meta


def has_checkpoint(path) -> bool:
    """True when a published AND restorable checkpoint exists at ``path``.

    A meta.json alone is not enough: a legacy (pre-versioning) save killed
    between its meta and state writes leaves a torn checkpoint, and an
    always-pass-resume job must start fresh on it rather than crash in
    restore."""
    path = Path(path)
    meta_file = path / "meta.json"
    if not meta_file.exists():
        return False
    try:
        meta = json.loads(meta_file.read_text())
    except (OSError, ValueError):
        return False
    state_dir = path / meta["version"] if "version" in meta else path
    # an orbax state needs its treedef companion to be restorable
    return ((state_dir / "state.orbax").exists()
            and (state_dir / "treedef.pkl").exists()) \
        or (state_dir / "state.pkl").exists()

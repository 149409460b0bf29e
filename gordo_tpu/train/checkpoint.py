"""Mid-fit checkpoint / resume.

Reference status (SURVEY.md §6.4): the reference checkpoints only at the
model-artifact level (``serializer.dump`` + the config-hash build cache);
there is no mid-training checkpointing.  The TPU build keeps the artifact
cache (it is load-bearing for fleet re-runs) and adds optional mid-fit
checkpointing for long fits: the epoch loop is chunked, and after each
chunk ``(params, opt_state, history, epochs_done)`` land on disk via Orbax.

Contracts:

- **Determinism**: per-epoch shuffle keys are derived once from the fit
  seed (``jax.random.split(rng, epochs)``) and indexed per chunk, so a
  resumed fit is **bit-identical** to an uninterrupted one
  (tests/test_checkpoint.py).  Resuming with a larger ``cfg.epochs``
  continues the same key sequence (``split(k, n)`` is prefix-stable).
- **Identity**: the checkpoint records a fingerprint of (module, config
  minus epochs, data, seed); a checkpoint that does not match the current
  fit is ignored, never silently reused — a cloned CV fold or a refit on
  new data with the same ``checkpoint_dir`` retrains from scratch.
- **Atomicity**: the whole checkpoint (tree + state + history) is staged
  in a temp dir and ``os.replace``d into place; a crash mid-save loses at
  most the newest chunk, never yields a mixed-epoch state.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import shutil
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu import compile as compile_plane

from gordo_tpu.train.fit import (
    TrainConfig,
    _pad_batches,
    init_params,
    make_optimizer,
    make_stateful_fit_fn,
)
from gordo_tpu.utils.trees import to_host

logger = logging.getLogger(__name__)

STATE_FILE = "state.json"
PAYLOAD_DIR = "ckpt"


def fit_fingerprint(module, cfg: TrainConfig, X, y, rng: jax.Array) -> str:
    """Identity of one logical fit, *excluding* ``epochs`` (resuming with a
    larger epoch budget is the supported continuation case; everything else
    changing means the checkpoint belongs to a different fit)."""
    h = hashlib.md5()
    h.update(repr(module).encode())
    h.update(repr(dataclasses.replace(cfg, epochs=0)).encode())
    h.update(np.asarray(jax.random.key_data(rng)).tobytes())
    for arr in (X, y):
        arr = np.asarray(arr)
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _save_tree(path: str, tree: Any) -> None:
    import orbax.checkpoint as ocp

    ocp.PyTreeCheckpointer().save(os.path.abspath(path), to_host(tree))


def _load_tree(path: str, target: Any = None) -> Any:
    import orbax.checkpoint as ocp

    # restoring against a concrete target preserves pytree node types
    # (optax opt_states are NamedTuples; a bare restore yields dicts)
    return ocp.PyTreeCheckpointer().restore(
        os.path.abspath(path), item=to_host(target) if target is not None else None
    )


def save_checkpoint(
    ckpt_dir: str,
    params: Any,
    opt_state: Any,
    history: np.ndarray,
    epochs_done: int,
    fingerprint: str = "",
) -> None:
    """Atomically persist the full fit state under ``ckpt_dir/ckpt``."""
    os.makedirs(ckpt_dir, exist_ok=True)
    tmp = os.path.join(ckpt_dir, PAYLOAD_DIR + ".tmp")
    final = os.path.join(ckpt_dir, PAYLOAD_DIR)
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    _save_tree(os.path.join(tmp, "tree"), {"params": params, "opt_state": opt_state})
    np.save(os.path.join(tmp, "history.npy"), np.asarray(history, np.float32))
    with open(os.path.join(tmp, STATE_FILE), "w") as f:
        json.dump({"epochs_done": int(epochs_done), "fingerprint": fingerprint}, f)
    # Keep a valid payload on disk at every instant: the previous checkpoint
    # is moved aside (one atomic rename), the new one renamed in, and only
    # then is the old one deleted.  A crash anywhere in between leaves either
    # `ckpt` or `ckpt.old` restorable (load_checkpoint falls back to .old).
    # A stale `.old` (from a crash that left ONLY it behind) must survive
    # until the new payload is in place — never delete it up front.
    old = final + ".old"
    if os.path.exists(final):
        if os.path.exists(old):
            shutil.rmtree(old)
        os.replace(final, old)
    os.replace(tmp, final)
    if os.path.exists(old):
        shutil.rmtree(old)


def load_checkpoint(
    ckpt_dir: str, target: Any = None, fingerprint: Optional[str] = None
) -> Optional[Tuple[Any, Any, np.ndarray, int]]:
    """Restore ``(params, opt_state, history, epochs_done)`` or None.

    ``target``: example ``{"params", "opt_state"}`` tree (fresh init) used
    to restore exact pytree node types.  A ``fingerprint`` mismatch returns
    None — stale checkpoints are never silently reused.
    """
    payload = os.path.join(ckpt_dir, PAYLOAD_DIR)
    if not os.path.exists(os.path.join(payload, STATE_FILE)):
        # a crash mid-save may have left only the moved-aside previous payload
        payload = os.path.join(ckpt_dir, PAYLOAD_DIR + ".old")
    state_path = os.path.join(payload, STATE_FILE)
    if not os.path.exists(state_path):
        return None
    with open(state_path) as f:
        state = json.load(f)
    if fingerprint is not None and state.get("fingerprint") != fingerprint:
        logger.warning(
            "Checkpoint in %s belongs to a different fit "
            "(config/data/seed changed); retraining from scratch", ckpt_dir,
        )
        return None
    tree = _load_tree(os.path.join(payload, "tree"), target)
    history = np.load(os.path.join(payload, "history.npy"))
    return tree["params"], tree["opt_state"], history, int(state["epochs_done"])


# Static-keyed like fit._fit_jit so CV folds / repeat fits with the same
# (module, cfg, shapes) reuse one compiled executable per chunk size.
@compile_plane.jit(
    name="train.stateful_fit",
    static_argnames=("module", "cfg", "steps", "bs"),
)
def _stateful_fit_jit(module, cfg: TrainConfig, steps: int, bs: int,
                      params, opt_state, X, y, w, epoch_keys):
    return make_stateful_fit_fn(module, cfg, steps, bs)(
        params, opt_state, X, y, w, epoch_keys
    )


def fit_checkpointed(
    module,
    X,
    y,
    cfg: TrainConfig,
    ckpt_dir: str,
    checkpoint_every: int = 10,
    rng: Optional[jax.Array] = None,
) -> Tuple[Any, np.ndarray]:
    """Fit with a checkpoint every ``checkpoint_every`` epochs; resumes
    from ``ckpt_dir`` iff it holds a checkpoint of THIS fit.  Same RNG
    derivation as ``train.fit.fit`` → same final params when never
    interrupted."""
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)

    init_rng, fit_rng = jax.random.split(rng)
    epoch_keys = jax.random.split(fit_rng, cfg.epochs)
    Xp, yp, w, steps, bs = _pad_batches(X, y, cfg.batch_size)
    fingerprint = fit_fingerprint(module, cfg, X, y, rng)

    params = init_params(module, init_rng, X[:1])
    opt_state = make_optimizer(cfg).init(params)
    resumed = load_checkpoint(
        ckpt_dir,
        target={"params": params, "opt_state": opt_state},
        fingerprint=fingerprint,
    )
    if resumed is not None and resumed[3] > cfg.epochs:
        # the fingerprint deliberately excludes epochs, so a re-run with a
        # SMALLER epoch budget can match an over-trained checkpoint; using
        # it would break the "same params as an uninterrupted fit" contract
        logger.warning(
            "Checkpoint in %s has %d epochs done > budget %d; "
            "retraining from scratch", ckpt_dir, resumed[3], cfg.epochs,
        )
        resumed = None
    if resumed is not None:
        params, opt_state, hist_arr, epochs_done = resumed
        history = list(np.asarray(hist_arr))
        logger.info("Resuming fit at epoch %d from %s", epochs_done, ckpt_dir)
    else:
        epochs_done, history = 0, []

    while epochs_done < cfg.epochs:
        chunk = min(checkpoint_every, cfg.epochs - epochs_done)
        keys = epoch_keys[epochs_done : epochs_done + chunk]
        params, opt_state, chunk_hist = _stateful_fit_jit(
            module, cfg, steps, bs, params, opt_state, Xp, yp, w, keys
        )
        epochs_done += chunk
        history.extend(np.asarray(chunk_hist).tolist())
        save_checkpoint(
            ckpt_dir, params, opt_state,
            np.asarray(history, np.float32), epochs_done, fingerprint,
        )
    assert len(history) == cfg.epochs, (
        f"history has {len(history)} entries for a {cfg.epochs}-epoch fit"
    )
    return params, np.asarray(history, dtype=np.float32)

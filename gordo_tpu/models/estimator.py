"""sklearn-contract estimators wrapping Flax modules.

Reference equivalent: ``gordo_components/model/models.py`` —
``KerasBaseEstimator`` / ``KerasAutoEncoder`` / ``KerasLSTMAutoEncoder`` /
``KerasLSTMForecast``.  Same contract: construct with ``kind=<registered
factory name>`` plus kwargs; the network is built from ``X.shape`` at fit
time; fit/predict/score/get_params/get_metadata like any sklearn estimator;
pickling carries host-side weights (reference used HDF5-bytes
``__getstate__``; here params are a host numpy pytree).

TPU-native: fit is one jitted XLA program (``gordo_tpu.train.fit``),
predict is a jitted apply.  The estimator exposes its pure pieces
(``module_``, ``params_``) so the fleet engine and the serving scorer can
batch many estimators into single device programs.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gordo_tpu.models.base import GordoBase
from gordo_tpu.ops.scalers import as_float2d
from gordo_tpu.ops.metrics import explained_variance_score
from gordo_tpu.ops.windows import make_sequences, make_windows, sequences_to_rows
from gordo_tpu.registry import lookup_factory
from gordo_tpu.train.fit import TrainConfig, fit as fit_model
from gordo_tpu.utils.args import ParamsMixin, capture_args
from gordo_tpu.utils.trees import param_count, to_host


@functools.lru_cache(maxsize=256)
def _predict_jit_for(module):
    """One jitted apply per structurally-distinct module (flax modules are
    frozen dataclasses: equal factory output hashes equal)."""
    from gordo_tpu import compile as compile_plane

    return compile_plane.jit(module.apply, name="estimator.predict")


class BaseJaxEstimator(ParamsMixin, GordoBase):
    """Common machinery; subclasses define windowing/targets."""

    model_type = "AutoEncoder"  # factory-registry type to resolve `kind` in

    @capture_args
    def __init__(self, kind: str = "feedforward_hourglass", **kwargs):
        self.kind = kind
        self.kwargs = kwargs
        self.params_: Optional[Any] = None
        self.module_: Optional[Any] = None
        self.history_: Optional[np.ndarray] = None
        self.fit_seconds_: Optional[float] = None
        self._predict_jit = None
        self._factory_kwargs_built: Dict[str, Any] = {}

    # -- windowing hooks -----------------------------------------------------
    #: rows of the input consumed before the first prediction row
    offset = 0

    def _make_inputs(self, X: jnp.ndarray) -> jnp.ndarray:
        return X

    def _make_targets(self, X: jnp.ndarray, y: Optional[jnp.ndarray]) -> jnp.ndarray:
        return X if y is None else y

    def _sample_weights(self, X: jnp.ndarray) -> Optional[jnp.ndarray]:
        """Weight of every training sample (or position); None: all 1."""
        return None

    def _rows_from_outputs(self, out: jnp.ndarray, n_rows: int) -> jnp.ndarray:
        """The model's outputs over ``_make_inputs`` as one row per
        predicted input row."""
        return out

    # -- estimator surface ---------------------------------------------------
    def fit(self, X, y=None, **fit_kwargs):
        t0 = time.time()
        X = as_float2d(X)
        y_arr = None if y is None else as_float2d(y)

        merged = {**self.kwargs, **fit_kwargs}
        checkpoint_dir = merged.pop("checkpoint_dir", None)
        checkpoint_every = int(merged.pop("checkpoint_every", 10) or 10)
        cfg, factory_kwargs = TrainConfig.from_kwargs(merged)
        inputs = self._make_inputs(X)
        targets = self._make_targets(X, y_arr)
        weights = self._sample_weights(X)

        factory = lookup_factory(self.model_type, self.kind)
        built_kwargs = dict(
            n_features=int(X.shape[1]),
            n_features_out=int(targets.shape[-1]),
            **factory_kwargs,
        )
        self.module_ = factory(**built_kwargs)
        self._factory_kwargs_built = built_kwargs
        self._train_cfg = cfg

        seed = int(factory_kwargs.get("seed", 0) or 0)
        if checkpoint_dir and weights is not None:
            raise NotImplementedError(
                f"{type(self).__name__} has no checkpointed fit: "
                "train.checkpoint carries no per-position weights"
            )
        if checkpoint_dir:
            # mid-fit checkpoint/resume for long fits (SURVEY.md §6.4)
            from gordo_tpu.train.checkpoint import fit_checkpointed

            params, history = fit_checkpointed(
                self.module_, inputs, targets, cfg,
                ckpt_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                rng=jax.random.PRNGKey(seed),
            )
        else:
            params, history = fit_model(
                self.module_, inputs, targets, cfg, rng=jax.random.PRNGKey(seed),
                w=weights,
            )
        self.params_ = params
        self.history_ = np.asarray(history)
        self._predict_jit = None
        self.fit_seconds_ = time.time() - t0
        return self

    def _rebuild_module(self):
        factory = lookup_factory(self.model_type, self.kind)
        self.module_ = factory(**self._factory_kwargs_built)

    def predict(self, X) -> np.ndarray:
        if self.params_ is None:
            raise RuntimeError(f"{type(self).__name__} is not fitted")
        if self.module_ is None:
            self._rebuild_module()
        X = as_float2d(X)
        inputs = self._make_inputs(X)
        if self._predict_jit is None:
            # shared across instances, keyed on the (hashable, structurally
            # equal) flax module — same reasoning as _fit_jit: a fleet of
            # same-architecture estimators must hit ONE traced program, not
            # re-trace and re-compile per instance (the Nth identical
            # XLA:CPU recompile also segfaulted jax 0.9 under accumulated
            # compile state)
            self._predict_jit = _predict_jit_for(self.module_)
        out = self._predict_jit({"params": self.params_}, inputs)
        return np.asarray(self._rows_from_outputs(out, int(X.shape[0])))

    def score(self, X, y=None, sample_weight=None) -> float:
        """Explained variance of the model's output vs its targets
        (reference: ``KerasAutoEncoder.score``)."""
        X = as_float2d(X)
        y_arr = None if y is None else as_float2d(y)
        targets = self._make_targets(X, y_arr)
        pred = self.predict(X)
        return float(explained_variance_score(targets, pred))

    def get_metadata(self) -> Dict[str, Any]:
        meta: Dict[str, Any] = {
            "model_type": type(self).__name__,
            "kind": self.kind,
            "parameters": {**self.kwargs},
        }
        if self.params_ is not None:
            meta.update(
                {
                    "num_params": param_count(self.params_),
                    "fit_seconds": self.fit_seconds_,
                    "history": {
                        "loss": [
                            float(v)
                            for v in ([] if self.history_ is None else self.history_)
                        ],
                    },
                }
            )
        return meta

    # -- pickling (device-independent artifacts) ----------------------------
    def __getstate__(self):
        state = dict(self.__dict__)
        state["params_"] = to_host(state.get("params_"))
        state["module_"] = None  # rebuilt from factory on demand
        state["_predict_jit"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)


class AutoEncoder(BaseJaxEstimator):
    """Feedforward reconstruction AE (reference: ``KerasAutoEncoder``).

    Target is the estimator's own (already pipeline-transformed) input;
    score is explained variance of the reconstruction.
    """

    model_type = "AutoEncoder"


class LSTMAutoEncoder(BaseJaxEstimator):
    """Windowed LSTM reconstruction (reference: ``KerasLSTMAutoEncoder``).

    Windows X into ``lookback_window``-length subsequences on device; the
    model reconstructs the window's final timestep, so predictions start at
    row ``lookback_window - 1`` of the input (``offset``).
    """

    model_type = "LSTMAutoEncoder"

    def __init__(self, kind: str = "lstm_hourglass", **kwargs):
        super().__init__(kind=kind, **kwargs)

    @property
    def lookback_window(self) -> int:
        return int(self.kwargs.get("lookback_window", 1))

    @property
    def offset(self) -> int:
        return self.lookback_window - 1

    def _make_inputs(self, X):
        return make_windows(X, self.lookback_window)

    def _make_targets(self, X, y):
        base = X if y is None else y
        return base[self.lookback_window - 1:]


class LSTMForecast(LSTMAutoEncoder):
    """Windowed LSTM one-step-ahead forecast (reference:
    ``KerasLSTMForecast``): window ``X[t-L:t]`` predicts ``X[t]``, so
    predictions start at row ``lookback_window`` of the input."""

    @property
    def offset(self) -> int:
        return self.lookback_window

    def _make_inputs(self, X):
        return make_windows(X[:-1], self.lookback_window)

    def _make_targets(self, X, y):
        base = X if y is None else y
        return base[self.lookback_window:]


class SequenceForecast(BaseJaxEstimator):
    """Next-row forecast by a sequence backbone that reads the series once
    (``models/factories/backbone.py``): every position of a ``context``-row
    sequence forecasts its next row.  Four kinds, presets of one module:
    ``kimi_linear`` (delta-rule linear attention with latent attention every
    fourth layer), ``glm_moe_lite`` (rotary latent attention in every layer,
    and a multi-token-prediction module that is trained on the row after next
    beside the main head: the loss is the next row's error plus
    ``mtp_weight`` times the module's), ``lfm2_moe`` (gated short
    convolutions in three layers of four, grouped-query attention in the
    fourth, expert layers with no shared expert) and ``afmoe`` (gated
    grouped-query attention over a window in three layers of four and over
    the whole prefix in the fourth, every part's output normalised too).  Prediction, held-out forecasts,
    thresholds and scoring read the main head alone and do not run the
    module; the artifact keeps its weights (the ``mtp_`` parameters) and
    :meth:`get_metadata` says what they are.

    Training cuts the rows into sequences of ``context`` rows at ``stride``
    (``ops.windows.make_sequences``); the target of the position that reads
    row r is row r + 1, and padding weighs 0 in the loss.  Prediction covers
    every row from ``offset`` = 1 on exactly once
    (``ops.windows.sequences_to_rows``): a row's forecast has every earlier
    row as context up to row ``context``, and from there on at least
    ``context - stride`` rows, so at worst row 1 is forecast from row 0 alone.

    The fleet builder runs it (window mode ``"sequence"``); the stacked
    scorer, the streaming scorer and the backfill runner do not carry a
    sequence model's state yet and refuse it by name.
    """

    model_type = "SequenceForecast"
    offset = 1

    def __init__(self, kind: str = "kimi_linear", **kwargs):
        super().__init__(kind=kind, **kwargs)

    @property
    def context(self) -> int:
        return int(self.kwargs.get("context", 1024))

    @property
    def stride(self) -> int:
        return int(self.kwargs.get("stride", self.context // 2 or 1))

    def _make_inputs(self, X):
        return make_sequences(X, X, self.context, self.stride)[0]

    def _make_targets(self, X, y):
        base = X if y is None else y
        return make_sequences(X, base, self.context, self.stride)[1]

    def _sample_weights(self, X):
        return make_sequences(X, X, self.context, self.stride)[2]

    def _rows_from_outputs(self, out, n_rows):
        return sequences_to_rows(out, n_rows, self.context, self.stride)

    def score(self, X, y=None, sample_weight=None) -> float:
        X = as_float2d(X)
        truth = (X if y is None else as_float2d(y))[self.offset:]
        return float(explained_variance_score(truth, self.predict(X)))

    def get_metadata(self) -> Dict[str, Any]:
        meta = super().get_metadata()
        held = sorted(k for k in (self.params_ or {}) if k.startswith("mtp_"))
        if held:
            if self.module_ is None:
                self._rebuild_module()
            meta["multi_token_prediction"] = {
                "modules": int(self.module_.cfg.mtp_depth),
                "loss_weight": float(self.module_.cfg.mtp_weight),
                "parameters": held,
                "trained_on": "the row after next, beside the main head's next row",
                "used_by": "training alone: predict, thresholds and scores "
                           "read the main head and do not run the module",
            }
        return meta


# Parity aliases (reference class names).
KerasAutoEncoder = AutoEncoder
KerasLSTMAutoEncoder = LSTMAutoEncoder
KerasLSTMForecast = LSTMForecast

"""The layout a fit carries its parameters in (ISSUE 27): an LSTM module's
packed tree (three fused arrays a layer) wherever the optimiser is
elementwise, the public tree otherwise, and the same numbers either way.

The packed layout is switched off HERE by replacing
``train.fit.packed_layout`` (the program has no option for it); every
comparison is the same fit, or the same build, made both ways."""

import json
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu import artifacts, compile as compile_plane, telemetry
from gordo_tpu.builder.fleet_build import build_project
from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.models.factories.lstm import lstm_hourglass, lstm_symmetric
from gordo_tpu.train import fit as fit_mod
from gordo_tpu.train.fit import (
    ELEMENTWISE_OPTIMIZERS,
    TrainConfig,
    batch_geometry,
    make_fit_fn,
    packed_layout,
)
from gordo_tpu.workflow.config import Machine

from tests.lstm_detectors import BATCH, LOOKBACK, N_TAGS
from tests.test_build_pipeline import _scrub_timings, _strip_meta

ROWS = 70
MODULES = {
    "hourglass": lambda **kw: lstm_hourglass(N_TAGS, **kw),
    "symmetric": lambda **kw: lstm_symmetric(N_TAGS, dims=(8, 4), **kw),
}
#: leaves of the public tree and of the packed one: 12 and 3 a layer, the head's 2
LEAVES = {"hourglass": (74, 20), "symmetric": (50, 14)}


def layout_counts():
    series = telemetry.REGISTRY.get("gordo_fit_layout_total")
    return {layout: series.value(layout) for layout in ("packed", "public")}


def counted(before):
    return {k: v - before[k] for k, v in layout_counts().items()}


def backbone_traces():
    """``gordo_kda_solve_total`` + ``gordo_mla_attention_total``: only a
    sequence backbone counts there."""
    total = 0.0
    for name, rules in (("gordo_kda_solve_total", ("block_inverse",)),
                        ("gordo_mla_attention_total", ("causal_blocks", "whole"))):
        series = telemetry.REGISTRY.get(name)
        if series is not None:
            total += sum(series.value(rule) for rule in rules)
    return total


def public_only(module, cfg):
    """What replaces ``train.fit.packed_layout`` for the parent's fit:
    every module carries its public tree."""
    return False


def windows(rows=ROWS):
    X = jax.random.uniform(jax.random.PRNGKey(0), (rows, LOOKBACK, N_TAGS))
    return X, X[:, -1]


def init(module):
    return module.init(jax.random.PRNGKey(5), windows()[0][:1])["params"]


def run_fit(module, cfg):
    """One whole fit through ``make_fit_fn``, jitted anew so the layout in
    force when it is traced is the one it runs in."""
    X, y = windows()
    steps, bs, n_pad = batch_geometry(ROWS, cfg.batch_size)
    X = jnp.concatenate([X, jnp.zeros((n_pad,) + X.shape[1:])])
    y = jnp.concatenate([y, jnp.zeros((n_pad,) + y.shape[1:])])
    w = jnp.concatenate([jnp.ones(ROWS), jnp.zeros(n_pad)])
    params, history = jax.jit(make_fit_fn(module, cfg, steps, bs))(
        init(module), X, y, w, jax.random.PRNGKey(9))
    return jax.tree.map(np.asarray, params), np.asarray(history)


@pytest.mark.parametrize("family", sorted(MODULES))
def test_pack_and_unpack_are_inverse_leaf_for_leaf(family):
    module = MODULES[family]()
    params = init(module)
    packed = module.pack(params)
    n_public, n_packed = LEAVES[family]
    assert len(jax.tree.leaves(params)) == n_public
    assert len(jax.tree.leaves(packed)) == n_packed
    d = int(module.dims[0])
    layer = packed["OptimizedLSTMCell_0"]
    assert layer["kernel_i"].shape == (N_TAGS, 4 * d)
    assert layer["kernel_h"].shape == (d, 4 * d) and layer["bias"].shape == (4 * d,)
    # flax's gate order i, f, g, o
    assert np.array_equal(layer["kernel_h"][:, 2 * d:3 * d],
                          params["OptimizedLSTMCell_0"]["hg"]["kernel"])
    back = module.unpack(packed)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert np.array_equal(a, b), path


@pytest.mark.parametrize("family", sorted(MODULES))
def test_apply_packed_is_the_modules_forward(family):
    module = MODULES[family]()
    params = init(module)
    X, _ = windows(9)
    public = module.apply({"params": params}, X)
    assert np.array_equal(module.apply_packed(module.pack(params), X), public)
    single = module.apply({"params": params}, X[0])  # one window
    assert single.shape == (N_TAGS,)
    assert np.array_equal(module.apply_packed(module.pack(params), X[0]), single)


def test_the_head_written_out_is_flax_dense():
    """``apply_packed`` spells ``nn.Dense(out_dim, dtype=float32)`` out, and
    ``__call__`` declares its parameters without it: same names,
    initial values (the RNG follows the path) and results."""
    import flax.linen as nn

    from gordo_tpu.models.factories.lstm import LSTMAutoEncoderModule

    class Reference(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(N_TAGS, dtype=jnp.float32, name="out")(x[:, -1, :])

    head_only = LSTMAutoEncoderModule(dims=(), funcs=(), out_dim=N_TAGS)
    X, _ = windows(9)
    ours = head_only.init(jax.random.PRNGKey(4), X[:1])["params"]
    flax = Reference().init(jax.random.PRNGKey(4), X[:1])["params"]
    assert jax.tree.structure(ours) == jax.tree.structure(flax)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(flax)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    flax["out"]["bias"] = flax["out"]["bias"] + 0.25
    assert np.array_equal(head_only.apply({"params": flax}, X),
                          Reference().apply({"params": flax}, X))


@pytest.mark.parametrize("optimizer", sorted(ELEMENTWISE_OPTIMIZERS))
@pytest.mark.parametrize("family", sorted(MODULES))
def test_a_packed_fit_gives_the_public_fits_bits(family, optimizer, monkeypatch):
    module = MODULES[family]()
    cfg = TrainConfig(epochs=3, batch_size=16, optimizer=optimizer)
    assert packed_layout(module, cfg)
    before = layout_counts()
    params, history = run_fit(module, cfg)
    assert counted(before) == {"packed": 1, "public": 0}
    monkeypatch.setattr(fit_mod, "packed_layout", public_only)
    before = layout_counts()
    ref_params, ref_history = run_fit(module, cfg)
    assert counted(before) == {"packed": 0, "public": 1}
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    assert np.array_equal(history, ref_history)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ref_params)[0],
                            jax.tree.leaves(params)):
        assert np.array_equal(a, b), path
    assert np.all(np.isfinite(history)) and history[-1] < history[0]


def test_bfloat16_compute_agrees_to_1e6(monkeypatch):
    """The one place the two layouts part on XLA:CPU, by one float32 ulp in
    one element of one leaf after two epochs of nadam: bfloat16 recurrent
    compute, which the CPU backend emulates in float32 with rounding
    converts whose placement follows the surrounding fusion, and the
    fusions differ where the public layout concatenates and slices.  The
    float32 fits above are equal to the bit for all six optimisers."""
    module = MODULES["hourglass"](compute_dtype="bfloat16")
    cfg = TrainConfig(epochs=3, batch_size=16, optimizer="nadam")
    params, history = run_fit(module, cfg)
    monkeypatch.setattr(fit_mod, "packed_layout", public_only)
    ref_params, ref_history = run_fit(module, cfg)
    np.testing.assert_allclose(history, ref_history, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def test_lamb_takes_the_public_layout_and_the_counter_says_so():
    module = MODULES["hourglass"]()
    cfg = TrainConfig(epochs=1, batch_size=16, optimizer="lamb")
    assert not packed_layout(module, cfg)
    before = layout_counts()
    with telemetry.span("test.fit_layout") as sp:
        params, history = run_fit(module, cfg)
    assert counted(before) == {"packed": 0, "public": 1}
    # parameters + lamb's mu and nu + its step count
    assert sp["fit_traces"] == 1 and sp["carry_leaves"] == 3 * 74 + 1
    assert len(jax.tree.leaves(params)) == 74 and np.isfinite(history).all()


@pytest.mark.parametrize("kwargs", [
    (("mask", lambda params: jax.tree.map(lambda p: p.ndim > 1, params)),),
    (("learning_rate", lambda step: 1e-3),),
])
def test_optimizer_kwargs_that_can_address_a_leaf_take_the_public_layout(kwargs):
    module = MODULES["hourglass"]()
    assert packed_layout(module, TrainConfig(optimizer="adamw"))
    assert packed_layout(module, TrainConfig(
        optimizer="adamw", optimizer_kwargs=(("weight_decay", 1e-4),)))
    assert not packed_layout(module, TrainConfig(
        optimizer="adamw", optimizer_kwargs=kwargs))


def test_a_feedforward_module_takes_the_public_layout():
    module = feedforward_hourglass(N_TAGS)
    cfg = TrainConfig(epochs=1, batch_size=16)
    assert not hasattr(module, "pack") and not packed_layout(module, cfg)
    X = jax.random.uniform(jax.random.PRNGKey(0), (64, N_TAGS))
    params = module.init(jax.random.PRNGKey(1), X[:1])["params"]
    before = layout_counts()
    jax.jit(make_fit_fn(module, cfg, 4, 16))(
        params, X, X, jnp.ones(64), jax.random.PRNGKey(2))
    assert counted(before) == {"packed": 0, "public": 1}


# ---------------------------------------------------------------------------
# the fleet build: three chunks of two LSTM machines, both layouts
# ---------------------------------------------------------------------------

N_CHUNKS = 3


def lstm_machines(prefix):
    model = {"gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {"gordo_tpu.pipeline.Pipeline": {"steps": [
            "gordo_tpu.ops.scalers.MinMaxScaler",
            {"gordo_tpu.models.estimator.LSTMAutoEncoder": {
                "lookback_window": LOOKBACK, "epochs": 2, "batch_size": BATCH}},
        ]}}}}
    return [
        Machine.from_config({
            "name": f"{prefix}-{i}",
            "dataset": {
                "type": "RandomDataset",
                "tag_list": [f"{prefix}-{i}-{j}" for j in range(N_TAGS)],
                "train_start_date": "2017-12-25T06:00:00Z",
                "train_end_date": "2017-12-26T08:00:00Z",
            },
            "model": model,
        })
        for i in range(2 * N_CHUNKS)
    ]


def build(out, log):
    patch = pytest.MonkeyPatch()
    patch.setenv("GORDO_SPAN_LOG", str(log))
    # the fleet program is cached by module and config, not by layout
    compile_plane.REGISTRY.clear()
    before, traces_before = layout_counts(), backbone_traces()
    try:
        result = build_project(
            lstm_machines("fl"), str(out), max_bucket_size=2,
            artifact_format="v2",
        )
    finally:
        patch.undo()
        compile_plane.REGISTRY.clear()
    assert not result.failed and not result.demoted
    with open(log) as f:
        spans = [json.loads(line) for line in f]
    return {"result": result, "out": out, "counted": counted(before),
            "backbone_traces": backbone_traces() - traces_before,
            "enqueues": [s for s in spans if s["span"] == "gordo.build.enqueue"]}


@pytest.fixture(scope="module")
def builds(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fit_layout")
    packed = build(tmp / "packed", tmp / "packed.jsonl")
    patch = pytest.MonkeyPatch()
    patch.setattr(fit_mod, "packed_layout", public_only)
    try:
        public = build(tmp / "public", tmp / "public.jsonl")
    finally:
        patch.undo()
    return {"packed": packed, "public": public}


def test_both_layouts_write_the_same_packs(builds):
    on, off = (artifacts.open_store(str(builds[k]["out"]))
               for k in ("packed", "public"))
    assert sorted(on.names()) == sorted(off.names()) and len(on.names()) == 6
    for name in on.names():
        a, b = on.load_model(name), off.load_model(name)
        est_a, est_b = a.base_estimator._final, b.base_estimator._final
        assert len(jax.tree.leaves(est_a.params_)) == 74
        for x, y in zip(jax.tree.leaves(est_a.params_),
                        jax.tree.leaves(est_b.params_)):
            assert np.array_equal(x, y), name
        assert np.array_equal(est_a.history_, est_b.history_), name
        assert np.array_equal(a.feature_thresholds_, b.feature_thresholds_), name
        assert a.aggregate_threshold_ == b.aggregate_threshold_, name
        # and everything else a pack holds
        _scrub_timings(a)
        _scrub_timings(b)
        assert pickle.dumps(a) == pickle.dumps(b), name
        assert _strip_meta(on.load_metadata(name)) == _strip_meta(
            off.load_metadata(name)), name


@pytest.mark.parametrize("layout,leaves", [("packed", 20), ("public", 74)])
def test_the_layout_is_counted_where_the_program_is_traced(builds, layout, leaves):
    built = builds[layout]
    # one trace of fleet.exact: three folds and the final fit
    other = "public" if layout == "packed" else "packed"
    assert built["counted"] == {layout: 4, other: 0}
    # no KDA solve, no attention core: and no `kda_solve_*`, `mla_attn_*` below
    assert built["backbone_traces"] == 0
    carry = 4 * (3 * leaves + 1)  # parameters, Adam's mu and nu, its count
    traced = [s for s in built["enqueues"] if "carry_leaves" in s]
    assert [(s["chunk"], s["fit_traces"], s["carry_leaves"]) for s in traced] == [
        (0, 4, carry)]
    # in the build's snapshot: the counter in the registry's, the span's
    # counts on the chunk's row of the timeline
    directory = built["out"] / telemetry.SNAPSHOT_DIR
    (snapshot,) = telemetry.load_snapshot_dir(str(directory))
    assert "gordo_fit_layout_total" in json.dumps(snapshot)
    assert "gordo_lstm_backward_total" in json.dumps(snapshot)
    rows = json.loads((directory / "timeline-000-of-001.json").read_text())["chunks"]
    # either layout differentiates through the layers' written backward
    # (ISSUE 30): six layers in each of the four fits, three stacks kept by each
    assert rows[0]["counts"]["enqueue"] == {
        "fit_traces": 4, "carry_leaves": carry,
        "lstm_backward_traces": 24, "lstm_saved_stacks": 72}
    assert "enqueue" not in rows[1]["counts"]
    assert rows[1]["counts"]["stage"]["leaves"] == 3

"""The ``glm_moe_lite`` backbone (``models/factories/backbone.py``) against
its plain reference (``benchmark/reference/glm_moe_lite.py``) at a tiny
preset: hidden 64, 4 heads, low-rank queries of 24, 8 experts of which 2
held, sequences of 32 rows, three layers (dense, experts, experts) and the
multi-token-prediction module.  Float32 on the CPU, so agreement is tight; a
bfloat16 control has to fail the same tolerance."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.kinds import sequence_build as kind  # noqa: E402  (the project and the read-back)
from benchmark.reference import glm_moe_lite as reference  # noqa: E402
from gordo_tpu import compile as compile_plane, telemetry  # noqa: E402
from gordo_tpu.models.estimator import SequenceForecast  # noqa: E402
from gordo_tpu.models.factories import backbone  # noqa: E402
from gordo_tpu.train.fit import make_loss_fn, training_pass  # noqa: E402

TINY = dict(hidden_size=64, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=3)
F = 5
SEED = 11
T = 32
# float32 against float32 on the CPU (measured here: the two fits' changes
# from the common start are 1e-4 of a change apart)
UPDATE_GAP = 3e-3
STEP = 1e-3     # the learning rate: what Adam moves a parameter by in a step


def module_of(**over):
    return backbone.glm_moe_lite(F, F, compute_dtype="float32", **{**TINY, **over})


def shape_of(**over):
    return reference.shape_of({"kind": "glm_moe_lite", **TINY, **over}, F, F)


def start(module, shape):
    """The program's and the reference's initial weights from one seed."""
    init_key, _ = jax.random.split(jax.random.PRNGKey(SEED))
    params = module.init(init_key, jnp.zeros((1, T, F)))["params"]
    ref_params, _ = reference.init_params(SEED, shape)
    return params, ref_params


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (3, T, F))


@pytest.fixture(scope="module")
def batch(x):
    """Inputs, targets and weights with padding at the last sequence's end."""
    y = jax.random.normal(jax.random.PRNGKey(2), (3, T, F))
    w = jnp.ones((3, T)).at[2, 20:].set(0.0)
    return x, y, w


def relative(made, ref):
    return float(jnp.abs(made - ref).max() / jnp.maximum(jnp.abs(ref).max(), 1e-30))


# -- 1. forward, loss and gradients -------------------------------------------

def test_both_forecasts_match_the_reference_and_a_bfloat16_control_does_not(x):
    module, shape = module_of(), shape_of()
    params, ref_params = start(module, shape)
    assert set(params) == set(ref_params)
    for name in params:
        np.testing.assert_allclose(params[name], ref_params[name], atol=1e-6,
                                   err_msg=name)
    made = module.apply({"params": params}, x, mtp=True)
    ref = reference.forward(ref_params, x, shape, mtp=True)
    low = reference.forward(ref_params, x, shape, reference.bfloat16, mtp=True)
    for made_h, ref_h, low_h in zip(made, ref, low):
        tolerance = 1e-4 * float(jnp.abs(ref_h).max())
        assert float(jnp.abs(made_h - ref_h).max()) < tolerance
        assert float(jnp.abs(low_h - ref_h).max()) > tolerance
    # a forecast is the training pass's first output, and runs no module
    np.testing.assert_array_equal(module.apply({"params": params}, x), made[0])
    np.testing.assert_allclose(reference.forward(ref_params, x, shape), ref[0], atol=1e-6)


def test_the_published_widths_count_627_million_parameters():
    module = backbone.glm_moe_lite(50, 50, num_layers=5)
    assert module.param_count() == 627424818
    specs = {n: s for n, s, _ in backbone.param_specs(module.cfg)}
    mla = sum(int(np.prod(s[1:])) for n, s in specs.items() if n.startswith("mla_"))
    assert mla == 21759232 and specs["mla_wq_b"] == (5, 768, 5120)
    assert sum(int(np.prod(s)) for n, s in specs.items() if n.startswith("mtp_")) == 115223808
    assert specs["moe_router"] == (4, 2048, 64) and specs["mtp_weh"] == (1, 4096, 2048)
    assert module.mtp_weight == 0.3 and module.cfg.moe_labels == ("2", "3", "4", "5", "mtp")
    assert [module.cfg.mixer(l) for l in range(1, 6)] == ["mla"] * 5
    with pytest.raises(TypeError, match="unknown arguments"):
        backbone.glm_moe_lite(50, 50, no_such_width=1)
    with pytest.raises(ValueError, match="one multi-token-prediction module"):
        backbone.glm_moe_lite(50, 50, mtp_depth=2)


def test_loss_and_gradients_match_jax_grad_of_the_plain_forward(batch):
    x, y, w = batch
    module, shape = module_of(), shape_of()
    params, ref_params = start(module, shape)
    apply_fn, second = training_pass(module, counts=True)
    assert second == pytest.approx(0.3)
    (value, aux), grads = jax.value_and_grad(
        make_loss_fn(apply_fn, "mse", aux=True, second=second), has_aux=True)(
            params, x, y, w)
    (ref_value, (first, then)), ref_grads = jax.value_and_grad(
        lambda p: (lambda l: (l[0], l[1:]))(reference.loss(p, x, y, w, shape)),
        has_aux=True)(ref_params)
    assert float(value) == pytest.approx(float(ref_value), rel=1e-5)
    terms = np.asarray(aux["loss_terms"])
    assert terms[0] / terms[1] == pytest.approx(float(first), rel=1e-5)
    assert terms[2] / terms[3] == pytest.approx(float(then), rel=1e-5)
    assert float(value) == pytest.approx(float(first) + 0.3 * float(then), rel=1e-6)
    # positions that weigh in each term: every real one, and every real one
    # with a real successor in its own sequence
    assert terms[1] == float(w.sum()) and terms[3] == float(w[:, 1:].sum())
    assert set(grads) == set(ref_grads)
    for name in grads:
        assert relative(grads[name], ref_grads[name]) < 2e-4, name
    low = jax.grad(lambda p: reference.loss(p, x, y, w, shape, reference.bfloat16)[0])(
        ref_params)
    assert max(relative(low[n], ref_grads[n]) for n in low) > 2e-3


def test_the_layer_by_layer_step_is_the_step_of_jax_grad(batch):
    """The reference's fit writes the chain rule over the parts out; one of
    its steps moves every parameter as Adam on ``jax.grad`` of the plain
    forward's loss does."""
    x, y, w = batch
    shape = shape_of()
    a = dict(shape)
    ref_params, _ = reference.init_params(SEED, shape)
    _, ref_first, ref_then = reference.loss(ref_params, x, y, w, shape)
    grads = jax.grad(lambda p: reference.loss(p, x, y, w, shape)[0])(ref_params)
    # the step's Adam donates what it updates: it gets a copy of its own
    model = reference.split(a, jax.tree.map(jnp.array, ref_params))
    zeros = lambda: reference.split(a, jax.tree.map(jnp.zeros_like, ref_params))  # noqa: E731
    first, then = reference._step(reference._pieces(shape, None, True), a, model,
                                  zeros(), zeros(), 1, 1e-3, 0.3, x, y, w)
    assert float(first) == pytest.approx(float(ref_first), rel=1e-5)
    assert float(then) == pytest.approx(float(ref_then), rel=1e-5)
    # Adam's first step is lr * g / (|g| + eps): compare where g is not tiny
    moved = reference.split(a, {n: -1e-3 * g / (jnp.abs(g) + reference.ADAM_EPS)
                                for n, g in grads.items()})
    before = reference.split(a, ref_params)
    groups = list(zip(model.layers, before.layers, moved.layers)) + [
        (model.mtp, before.mtp, moved.mtp), (model.around, before.around, moved.around)]
    for now, was, step in groups:
        for name in now:
            big = jnp.abs(step[name]) > 0.999e-3
            np.testing.assert_allclose(
                jnp.where(big, now[name] - was[name], 0.0),
                jnp.where(big, step[name], 0.0), atol=2e-6, err_msg=name)


# -- 2. rotary positions ---------------------------------------------------------

def test_a_rotated_score_depends_on_the_distance_alone():
    width, theta = 8, 1e6
    q, k = jax.random.normal(jax.random.PRNGKey(4), (2, width))
    cos, sin = backbone.rotary(T, width, theta)
    rows = lambda v: backbone.rotate(jnp.broadcast_to(v, (T, width)), cos, sin)  # noqa: E731
    scores = rows(q) @ rows(k).T                        # (t, s)
    for shift in (1, 7):
        np.testing.assert_allclose(scores[shift:, shift:], scores[:-shift, :-shift],
                                   atol=1e-5)
    assert float(jnp.abs(scores[5, 0] - scores[0, 0])) > 1e-3   # and on nothing less
    # the reference's rotation, written out on its own, is the same rotation
    np.testing.assert_allclose(rows(q), reference.rope(
        jnp.broadcast_to(q, (1, T, width)), theta)[0], atol=1e-6)


def test_the_mixer_rotates_where_the_configuration_says_so():
    module, plain = module_of(), module_of(rope_theta=0.0)
    params, _ = start(module, shape_of())
    p = {n: v[0] for n, v in params.items() if n.startswith("mla_")}
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, 64))
    rotated = backbone.mla_mixer(module.cfg, p, h)
    assert float(jnp.abs(rotated - backbone.mla_mixer(plain.cfg, p, h)).max()) > 1e-4
    # position 0 attends to itself alone, and a rotation by angle 0 is none
    np.testing.assert_allclose(rotated[:, 0], backbone.mla_mixer(plain.cfg, p, h)[:, 0],
                               atol=1e-6)


# -- 2b. the causal core in query blocks ------------------------------------------

BLOCK = 8       # what the tests put in ``backbone.MLA_BLOCK``: sequences of 32 are four blocks


def whole_square_core(cfg, q, k_n, k_r, v):
    """The core as it was before it was computed in blocks, and the
    reference of the blocked one: every pair of the ``t x t`` square
    multiplied, the upper triangle masked, one softmax over whole rows."""
    cd, dn, dr = cfg.compute_dtype, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    t = q.shape[1]
    scores = jnp.einsum("bthc,bshc->bhts", q[..., :dn].astype(cd), k_n.astype(cd),
                        preferred_element_type=jnp.float32)
    q_r = q[..., dn:]
    if cfg.rope_theta:
        cos, sin = backbone.rotary(t, dr, cfg.rope_theta)
        q_r = backbone.rotate(q_r, cos[:, None, :], sin[:, None, :])
        k_r = backbone.rotate(k_r, cos, sin)
    scores += jnp.einsum("bthc,bsc->bhts", q_r.astype(cd), k_r.astype(cd),
                         preferred_element_type=jnp.float32)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal, scores * ((dn + dr) ** -0.5), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhts,bshv->bthv", probs.astype(cd), v.astype(cd),
                      preferred_element_type=jnp.float32)


#: the two kinds' latent attention: rotary with low-rank queries and values
#: wider than the keys' own channels, and NoPE with queries from one matrix
SETTINGS = {
    "rotary": lambda dtype: backbone.glm_moe_lite(F, F, compute_dtype=dtype, **TINY),
    "nope": lambda dtype: backbone.kimi_linear(
        F, F, compute_dtype=dtype, hidden_size=64, num_heads=2, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_layers=4),
}


def mixer_inputs(setting, dtype, t):
    """One latent-attention layer's parameters and a group of two sequences."""
    cfg = SETTINGS[setting](dtype).cfg
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    p = {name: backbone._initializer(init)(next(keys), shape[1:])
         for name, shape, init in backbone.param_specs(cfg)
         if name.startswith("mla_")}
    return cfg, p, jax.random.normal(next(keys), (2, t, 64))


def mixer_and_gradients(cfg, p, h):
    ct = jax.random.normal(jax.random.PRNGKey(8), h.shape)

    @jax.jit        # traced here, with whatever the test has put in the module
    def both(p, h):
        out, vjp = jax.vjp(lambda p, h: backbone.mla_mixer(cfg, p, h), p, h)
        return out, vjp(ct)

    out, (dp, dh) = both(p, h)
    return out, {**dp, "input": dh}


@pytest.mark.parametrize("t", [4 * BLOCK, BLOCK])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("setting", ["rotary", "nope"])
def test_the_blocked_core_is_the_whole_square_one(setting, dtype, t, monkeypatch):
    """Forward: a row's softmax is over the entries it had, so the blocks
    repeat the square's arithmetic (measured here: equal to the bit with
    bfloat16 operands, to float32 rounding with float32 ones, whose matmuls
    sum a prefix in another order).  Gradients: float32 operands to float32
    rounding; with bfloat16 operands a block's share of ``dk`` and ``dv`` is
    rounded to bfloat16 (8 bits: 2^-8 = 0.4 % of an entry) once a block
    before the float32 sum where the square rounds the whole sum once, so
    every gradient is held to 2 % of its largest entry: five such roundings'
    worth, and far under what a misplaced block or mask moves (order 1)."""
    cfg, p, h = mixer_inputs(setting, dtype, t)
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    made, made_grads = mixer_and_gradients(cfg, p, h)
    monkeypatch.setattr(backbone, "_causal_core", whole_square_core)
    ref, ref_grads = mixer_and_gradients(cfg, p, h)
    assert relative(made, ref) < 1e-6
    assert set(made_grads) == set(p) | {"input"}
    for name, g in ref_grads.items():
        assert float(jnp.abs(g).max()) > 0, name
        assert relative(made_grads[name], g) < (1e-5 if dtype == "float32" else 2e-2), name


@pytest.mark.parametrize("t,rule", [(4 * BLOCK, "causal_blocks"), (BLOCK + 4, "whole"),
                                    (BLOCK, "whole"), (BLOCK // 2, "whole")])
def test_a_length_the_block_does_not_divide_is_one_block(t, rule, monkeypatch):
    cfg, p, h = mixer_inputs("rotary", "float32", t)
    lower = lambda: jax.jit(  # noqa: E731
        lambda p, h: backbone.mla_mixer(cfg, p, h)).lower(p, h).as_text()
    counted = telemetry.REGISTRY.get("gordo_mla_attention_total")
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    before = {r: counted.value(r) for r in ("causal_blocks", "whole")}
    with telemetry.span("gordo.test.trace") as attrs:
        text = lower()
    assert {r: counted.value(r) - before[r] for r in before} == {
        rule: 1, "whole" if rule == "causal_blocks" else "causal_blocks": 0}
    n = 4 if rule == "causal_blocks" else 1
    assert attrs["mla_attn_traces"] == 1 and attrs["mla_attn_blocks"] == n
    assert attrs["mla_attn_pairs_computed"] == n * (n + 1) // 2
    assert attrs["mla_attn_pairs_square"] == n * n
    assert text.count("stablehlo.exponential") == n
    # one block is the whole square's program, operation for operation
    monkeypatch.setattr(backbone, "_causal_core", whole_square_core)
    assert (text == lower()) == (n == 1)


@pytest.mark.parametrize("setting", ["rotary", "nope"])
def test_no_row_sees_a_later_one_across_a_block_boundary(setting, monkeypatch):
    cfg, p, h = mixer_inputs(setting, "float32", 4 * BLOCK)
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    mixer = jax.jit(lambda h: backbone.mla_mixer(cfg, p, h))
    out = mixer(h)
    for first in (2 * BLOCK, 2 * BLOCK + 3):   # a block's first row, and one inside it
        later = h.at[:, first:].add(1.0)
        moved = mixer(later)
        np.testing.assert_array_equal(moved[:, :first], out[:, :first])
        assert float(jnp.abs(moved[:, first:] - out[:, first:]).min(axis=-1).max()) > 1e-4


# -- 3. the share --------------------------------------------------------------------

def expert_parameters(cfg, key):
    specs = [(n, s[1:], i) for n, s, i in backbone.param_specs(cfg) if n.startswith("moe_")]
    keys = jax.random.split(key, len(specs))
    return {n: backbone._initializer(i)(k, s) for (n, s, i), k in zip(specs, keys)}


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_of_every_chip_add_up_to_the_uncut_layer(held):
    """Every range of ``held`` experts (8, 4 and 2 chips sharing the layer):
    the routed parts add up, with the shared expert counted once, to what
    the uncut reference gives for the whole layer."""
    whole = module_of(experts_held=8).cfg
    p = expert_parameters(whole, jax.random.PRNGKey(6))
    xs = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    a = dict(shape_of(experts_held=8))
    ref = reference._experts(a, p, xs, None)
    shared = backbone.swiglu(xs, p["moe_shared_wg"], p["moe_shared_wu"],
                             p["moe_shared_wd"], jnp.float32)
    total, pairs = shared, 0
    for first in range(0, 8, held):
        cfg = module_of(experts_held=held, experts_held_from=first).cfg
        mine = {n: (v[first:first + held] if n in ("moe_wg", "moe_wu", "moe_wd") else v)
                for n, v in p.items()}
        y, counted = backbone.expert_layer(cfg, mine, xs)
        total = total + (y - shared)
        pairs += int(counted.sum())
        np.testing.assert_allclose(
            y, reference._experts(a, mine, xs, None, held=(first, held)), atol=2e-5)
    assert pairs == 48 * 2                      # every selected pair fell on one chip
    np.testing.assert_allclose(total, ref, atol=5e-5)


# -- 4. the multi-token-prediction module ----------------------------------------------

def test_the_second_term_weighs_nothing_at_a_sequences_end_and_at_padding(batch):
    x, y, w = batch
    module = module_of()
    params, _ = start(module, shape_of())
    apply_fn, second = training_pass(module)
    loss = jax.jit(make_loss_fn(apply_fn, "mse", second=second))
    base = float(loss(params, x, y, w))
    # what the row after the last position would be never enters: the last
    # position's second forecast has no target; padding's targets weigh 0
    far = y.at[2, 20:].add(100.0)
    assert float(loss(params, x, far, w)) == pytest.approx(base, rel=1e-6)
    y2_only = make_loss_fn(lambda v, z: module.apply(v, z, mtp=True), "mse", second=1.0)
    y1_only = make_loss_fn(module.apply, "mse")
    then = float(y2_only(params, x, y, w)) - float(y1_only(params, x, y, w))
    moved = y.at[:, 0].add(100.0)               # position 0's target: only L1 reads it
    then_moved = float(y2_only(params, x, moved, w)) - float(y1_only(params, x, moved, w))
    assert then_moved == pytest.approx(then, rel=1e-4)
    # the module's forecast at the last position is computed and finite
    ahead = module.apply({"params": params}, x, mtp=True)[1]
    assert np.isfinite(np.asarray(ahead)).all()


def test_with_lambda_zero_the_layers_train_as_a_model_without_the_module(batch):
    x, y, w = batch
    with_module, without = module_of(mtp_weight=0.0), module_of(mtp_depth=0)
    params, _ = start(with_module, shape_of())
    bare = {n: v for n, v in params.items() if not n.startswith("mtp_")}
    assert with_module.mtp_weight == 0.0 and without.mtp_weight == 0.0
    # created last: the layers' draws do not depend on the module
    init_key, _ = jax.random.split(jax.random.PRNGKey(SEED))
    drawn = without.init(init_key, jnp.zeros((1, T, F)))["params"]
    for name in bare:
        np.testing.assert_array_equal(bare[name], drawn[name])

    def grads(module, p):
        apply_fn, second = training_pass(module, counts=True)
        assert second == 0.0
        return jax.grad(lambda q: make_loss_fn(apply_fn, "mse", aux=True)(q, x, y, w)[0])(p)

    g_with, g_without = grads(with_module, params), grads(without, bare)
    for name in bare:
        np.testing.assert_array_equal(g_with[name], g_without[name])
    assert all(float(jnp.abs(g_with[n]).max()) == 0.0 for n in g_with if n.startswith("mtp_"))
    # and with lambda above zero the module's term reaches the layers
    apply_fn, second = training_pass(module_of())
    g_trained = jax.grad(make_loss_fn(apply_fn, "mse", second=second))(params, x, y, w)
    assert relative(g_trained["mla_wo"], g_with["mla_wo"]) > 1e-3
    with pytest.raises(ValueError, match="no multi-token-prediction module"):
        without.apply({"params": bare}, x, mtp=True)


def test_counts_name_the_modules_expert_layer_as_a_layer_of_its_own(x):
    module = module_of()
    params, _ = start(module, shape_of())
    _, plain = module.apply({"params": params}, x, counts=True)
    (_, _), trained = module.apply({"params": params}, x, counts=True, mtp=True)
    assert plain["tokens"].shape == (2, 2) and trained["tokens"].shape == (3, 2)
    np.testing.assert_array_equal(plain["tokens"], trained["tokens"][:2])
    assert int(plain["selected"]) == 3 * T * 2 * 2 and int(trained["selected"]) == 3 * T * 2 * 3
    assert module.cfg.moe_labels == ("2", "3", "mtp")


# -- 5. a project through build_project ---------------------------------------------------

def config_of(**model):
    """One plant machine's two-horizon forecaster as a project describes it,
    in the shape ``benchmark/kinds/sequence_build.py`` ``project_doc`` reads;
    widths that ``model`` leaves out are the published ones."""
    return {
        "detector": "DiffBasedAnomalyDetector", "scalers": ["MinMaxScaler"],
        "estimator": "SequenceForecast",
        "model": {"kind": "glm_moe_lite", "epochs": 1, "learning_rate": 0.001,
                  "compute_dtype": "auto", "experts_held_from": 0,
                  "mtp_depth": 1, "mtp_weight": 0.3, **model},
        "cv": {"splitter": "TimeSeriesSplit", "n_splits": 3},
        "dataset": {"type": "RandomDataset", "resolution": "10min", "n_tags": F,
                    "train_start_date": "2017-01-01T00:00:00+00:00",
                    "train_end_date": "2017-01-02T12:00:00+00:00", "rows": 217},
    }


def tiny_config():
    return config_of(context=T, stride=16, batch_size=4, **TINY)


def reference_of(config, rows, folds):
    """The reference's final fit of one machine and, with ``folds``, the
    thresholds from its cross-validation (the main head alone)."""
    out = reference.fit(np.asarray(rows), config["model"], kind.model_seed(SEED))
    if folds:
        out["thresholds"] = reference.cross_validate(
            np.asarray(rows), config["model"], kind.model_seed(SEED),
            int(config["cv"]["n_splits"]))
    return out


def gaps(made, ref):
    """How far a written machine is from the reference's fit of it: the
    trained loss ``L1 + lambda L2``; the two fits' changes from the common
    start, as the larger of the worst parameter's gap between their norms
    and the median parameter's distance between the changes themselves
    (each relative to the reference's change of that parameter, or the
    median parameter's if larger); the worst threshold."""
    out = {"loss": abs(made["history"][-1] - ref["history"][-1]) / abs(ref["history"][-1])}
    d = reference.distances(ref["model"], made["params"], kind.model_seed(SEED), ref["shape"])
    ours, theirs, apart = (
        np.asarray(d[k], np.float64) for k in ("moved_ours", "moved_theirs", "apart"))
    scale = np.maximum(ours, np.median(ours))
    out["update"] = float(max(np.max(np.abs(theirs - ours) / scale), np.median(apart / scale)))
    if "thresholds" in ref:
        t_ref = np.asarray(ref["thresholds"], np.float64)
        out["threshold"] = float(np.max(
            np.abs(made["thresholds"] - t_ref) / np.maximum(t_ref, np.median(t_ref))))
    return out


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Two machines through ``build_project`` with NO ``max_bucket_size``:
    the planner reads the parameter count and puts both in one chunk."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config = tiny_config()
    out = str(tmp_path_factory.mktemp("glm-project"))
    doc = kind.project_doc(config, SEED, 2)
    machines = NormalizedConfig(doc, "glm-test").machines
    before = telemetry.REGISTRY.snapshot()["metrics"]
    result = build_project(machines, out, artifact_format="v2")
    after = telemetry.REGISTRY.snapshot()["metrics"]
    return config, out, result, before, after


@pytest.fixture(scope="module")
def built_in_blocks(built, tmp_path_factory):
    """The same project with sequences of four blocks (``built``'s are one:
    32 rows are fewer than ``backbone.MLA_BLOCK``)."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    out = str(tmp_path_factory.mktemp("glm-project-blocks"))
    machines = NormalizedConfig(kind.project_doc(built[0], SEED, 2), "glm-test").machines
    patch = pytest.MonkeyPatch()
    patch.setattr(backbone, "MLA_BLOCK", BLOCK)
    # the fleet program is cached by module and config, not by the block
    compile_plane.REGISTRY.clear()
    before = telemetry.REGISTRY.snapshot()["metrics"]
    try:
        result = build_project(machines, out, artifact_format="v2")
    finally:
        patch.undo()
        compile_plane.REGISTRY.clear()
    return built[0], out, result, before, telemetry.REGISTRY.snapshot()["metrics"]


def counter(snapshot, name, *labels):
    series = (snapshot.get(name) or {"series": {}})["series"]
    return sum(v for k, v in series.items() if not labels or json.loads(k) == list(labels))


def test_two_machines_build_in_one_chunk_and_match_the_reference(built):
    config, out, result, _, _ = built
    summary = result.summary()
    assert not summary["failed"] and summary["single_built"] == 0
    assert summary["demoted"]["machines"] == 0
    assert len(result.timeline) == 1          # one chunk of two machines
    for i, name in enumerate(kind.machine_names(SEED, 2)):
        made = kind.produced(out, name)
        assert all(np.all(np.isfinite(v)) for v in made["params"].values())
        far = gaps(made, reference_of(config, kind.reference_rows(config, name), folds=i == 0))
        assert far["loss"] < 1e-5 and far["update"] < UPDATE_GAP
        if i == 0:
            assert far["threshold"] < 1e-4


def test_a_fit_in_four_blocks_writes_the_packs_of_the_whole_square(built, built_in_blocks):
    """Through ``_sequence_fits``: three folds, the final fit, thresholds.
    Float32 on the CPU, where the blocks sum a prefix's products in another
    order than the square.  Adam divides a gradient by its own size, so
    rounding shows where a gradient is next to nothing: measured here, 20 of
    a leaf's 5,760 entries end more than a thousandth of a step apart (the
    farthest 0.07 of one) and every other leaf's all end closer."""
    config, out, result, _, _ = built_in_blocks
    assert not result.summary()["failed"] and len(result.timeline) == 1
    for i, name in enumerate(kind.machine_names(SEED, 2)):
        made, whole = kind.produced(out, name), kind.produced(built[1], name)
        far = gaps(made, reference_of(config, kind.reference_rows(config, name), folds=i == 0))
        assert far["loss"] < 1e-5 and far["update"] < UPDATE_GAP
        np.testing.assert_allclose(made["history"], whole["history"], rtol=1e-6)
        np.testing.assert_allclose(made["thresholds"], whole["thresholds"], rtol=1e-5)
        assert set(made["params"]) == set(whole["params"])
        for leaf, value in made["params"].items():
            apart = np.abs(value - whole["params"][leaf])
            assert apart.max() < 0.1 * STEP and np.mean(apart > 1e-3 * STEP) < 0.01, leaf


@pytest.mark.parametrize("blocks,rule", [(4, "causal_blocks"), (1, "whole")])
def test_the_cores_are_counted_where_the_program_is_traced(
        blocks, rule, built, built_in_blocks):
    """``gordo_mla_attention_total{rule}`` and the span's counts: every core
    of a build is of one rule, 10 of 16 block pairs where a sequence is four
    blocks and 1 of 1 where it is one."""
    _, out, result, before, after = built_in_blocks if blocks == 4 else built
    other = "whole" if rule == "causal_blocks" else "causal_blocks"
    traced = counter(after, "gordo_mla_attention_total", rule) - counter(
        before, "gordo_mla_attention_total", rule)
    assert traced > 0
    assert counter(after, "gordo_mla_attention_total", other) == counter(
        before, "gordo_mla_attention_total", other)
    counts = result.timeline[0]["counts"]["enqueue"]
    assert counts["mla_attn_traces"] == traced
    assert counts["mla_attn_blocks"] == blocks * traced
    assert counts["mla_attn_pairs_computed"] == blocks * (blocks + 1) // 2 * traced
    assert counts["mla_attn_pairs_square"] == blocks * blocks * traced
    assert not [name for name in counts if name.startswith("swa_attn")]   # no windowed core
    (snapshot,) = telemetry.load_snapshot_dir(os.path.join(out, telemetry.SNAPSHOT_DIR))
    assert "gordo_mla_attention_total" in json.dumps(snapshot)


@pytest.fixture(scope="module")
def sound_fit():
    config = tiny_config()
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    return config, rows, reference_of(config, rows, folds=False)


@pytest.mark.parametrize("fault", ["float8", "half_batch", "frozen_leaf", "no_rotation", "no_mtp"])
def test_a_planted_fault_reads_far_above_what_a_sound_build_may(fault, sound_fit):
    """The faults a comparison with this reference has to catch, planted in
    the reference's own fit: float8 operands, half of every minibatch left
    out, a matrix left at its start, the rotation left out of ``q_r`` and
    ``k_r``, lambda 0.  Each reads above what the build above is held to."""
    config, rows, ref = sound_fit
    seed = kind.model_seed(SEED)
    if fault == "frozen_leaf":
        low = {**ref, "model": reference.freeze(ref["model"], seed, ref["shape"], 0, "mla_wo")}
    elif fault == "float8":
        low = reference.fit(rows, config["model"], seed, quantize=reference.float8)
    else:
        low = reference.fit(rows, config["model"], seed, fault=fault)
    far = gaps({"params": low["model"], "history": low["history"]}, ref)
    assert far["update"] > UPDATE_GAP
    if fault in ("frozen_leaf", "no_mtp"):  # a matrix that never moved reads 1
        assert far["update"] == pytest.approx(1.0)
    if fault == "half_batch":
        assert far["update"] > 0.3
    if fault in ("float8", "no_mtp"):  # lambda 0: the trained loss lacks its second term
        assert far["loss"] > 1e-3


def test_a_627_million_parameter_model_is_a_chunk_of_one():
    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import _parameter_count, default_bucket_size
    from gordo_tpu.parallel.anomaly import analyze_definition

    # layers 0-4 of the source (1-5 here), experts 0-7 of 64, the module
    config = config_of(context=2048, stride=1024, batch_size=4, num_layers=5, experts_held=8)
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    assert _parameter_count(spec, (50, 50)) == 627424818
    assert default_bucket_size(spec, (50, 50)) == 1


def test_the_counters_the_span_and_the_artifacts_metadata(built):
    from gordo_tpu import artifacts

    config, out, result, before, after = built
    delta = lambda name: counter(after, name) - counter(before, name)  # noqa: E731
    selected, held = delta("gordo_moe_selected_pairs_total"), delta("gordo_moe_held_pairs_total")
    assert selected > 0 and 0 < held <= selected
    assert delta("gordo_moe_tokens_total") == held
    labels = {tuple(json.loads(k)) for k in after["gordo_moe_tokens_total"]["series"]}
    assert {(layer, e) for layer in ("2", "3", "mtp") for e in ("0", "1")} <= labels
    # 216 inputs a machine: 13 sequences of 32 at stride 16, the last 24 long;
    # every real position but a sequence's last has a successor in it
    real = 12 * T + 24
    assert delta("gordo_mtp_positions_total") == 2 * (real - 13)
    counts = result.timeline[0]["counts"]["enqueue"]
    assert counts["mtp_depth"] == 1 and counts["mtp_weight"] == pytest.approx(0.3)
    assert counts["context"] == T and counts["experts_held"] == 2
    _, refs = artifacts.discover(out)
    meta = refs[0].load_metadata()["model"]
    terms = meta["cross_validation"]["loss_terms"]
    assert terms["row_after_next_positions"] == real - 13
    assert terms["next_row"] > 0 and terms["row_after_next"] > 0
    moe = meta["cross_validation"]["moe"]
    assert np.asarray(moe["tokens_per_held_expert"]).shape == (3, 2)
    ref = reference_of(config, kind.reference_rows(config, refs[0].name), folds=False)
    assert terms["next_row"] == pytest.approx(ref["terms"][-1][0], rel=1e-4)
    assert terms["row_after_next"] == pytest.approx(ref["terms"][-1][1], rel=1e-4)


def test_the_artifact_keeps_the_module_says_so_and_scores_without_it(built):
    from gordo_tpu import artifacts

    config, out, _, _, _ = built
    _, refs = artifacts.discover(out)
    by_name = {ref.name: ref for ref in refs}
    name = kind.machine_names(SEED, 2)[1]
    detector = by_name[name].load_model()
    estimator = detector.base_estimator.steps[-1][1]
    assert isinstance(estimator, SequenceForecast) and estimator.kind == "glm_moe_lite"
    said = estimator.get_metadata()["multi_token_prediction"]
    assert said["modules"] == 1 and said["loss_weight"] == pytest.approx(0.3)
    assert "mtp_weh" in said["parameters"] and "mtp_weh" in estimator.params_
    rows = kind.reference_rows(config, name)
    frame = detector.anomaly(rows, rows)
    assert len(frame) == len(rows) - 1
    assert np.isfinite(frame[("total-anomaly-score", "")].to_numpy()).all()
    # the estimator's own predict is the reference's main-head forecast
    scaled = reference.minmax(rows, rows)
    shape = reference.shape_of(config["model"], F, F)
    ref = reference.predict(
        jax.tree.map(jnp.asarray, estimator.params_), rows, rows, config["model"], shape)
    np.testing.assert_allclose(estimator.predict(scaled), ref, atol=1e-4)
    # scoring never reads the module: with its weights zeroed the scores stand
    before = estimator.predict(scaled)
    estimator.params_ = {n: (np.zeros_like(v) if n.startswith("mtp_") else v)
                         for n, v in estimator.params_.items()}
    estimator._predict_jit = None
    np.testing.assert_array_equal(estimator.predict(scaled), before)
    again = detector.anomaly(rows, rows)
    np.testing.assert_array_equal(again[("total-anomaly-score", "")].to_numpy(),
                                  frame[("total-anomaly-score", "")].to_numpy())


def test_the_serving_planes_go_on_refusing_it_by_name(built):
    from gordo_tpu import artifacts
    from gordo_tpu.serve.fleet_scorer import FleetScorer
    from gordo_tpu.serve.scorer import (
        CompiledScorer, SequenceModelUnsupported, refuse_sequence_model,
    )
    from gordo_tpu.serve.stream import MachineStream

    _, out, _, _, _ = built
    _, refs = artifacts.discover(out)
    models = {ref.name: ref.load_model() for ref in refs}
    name = sorted(models)[0]
    with pytest.raises(SequenceModelUnsupported, match="FleetScorer.*SequenceForecast"):
        FleetScorer.from_models(models)
    scorer = CompiledScorer(models[name], machine=name)
    assert not scorer.fused        # falls back to the detector's own anomaly()
    with pytest.raises(SequenceModelUnsupported, match="MachineStream.*SequenceForecast"):
        MachineStream(name, scorer)
    with pytest.raises(SequenceModelUnsupported, match="backfill.*SequenceForecast"):
        refuse_sequence_model(models[name], name, "the backfill runner")


def test_the_lowered_program_names_the_scopes_the_metrics_read():
    from gordo_tpu import serializer
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition

    config = tiny_config()
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    builder = FleetDiffBuilder(spec)
    rows = int(config["dataset"]["rows"])
    ctx = builder._group_context(rows, F, F)
    program = builder._group_program(ctx, padded=False, warm=False)
    data = jax.ShapeDtypeStruct((1, rows, F), jnp.float32)
    lowered = program._jitted.lower(
        data, data, jax.ShapeDtypeStruct((1,), jnp.uint32))
    # one kind of mixer: the one conditional left is the folds' "forecast or not"
    assert lowered.as_text().count("stablehlo.case") == 1
    named = lowered.as_text(debug_info=True)
    for scope in ("backbone.mla/backbone.mla.attn/", "backbone.mtp/",
                  "backbone.mtp/backbone.moe.experts/", "backbone.moe.route/",
                  "backbone.ffn/"):
        assert scope in named, scope
    assert "backbone.kda" not in named

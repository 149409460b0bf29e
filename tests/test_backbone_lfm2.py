"""The ``lfm2_moe`` backbone (``models/factories/backbone.py``) against its
plain reference (``benchmark/reference/lfm2_moe.py``) at a tiny preset:
hidden 64, 8 query heads over 2 key/value heads of 8, 8 experts of which 2
held and no shared one, sequences of 32 rows, five layers (conv + dense,
attention + experts, three times conv + experts).  Float32 on the CPU, so
agreement is tight; a bfloat16 control has to fail the same tolerance."""

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
from benchmark.reference import lfm2_moe as reference  # noqa: E402
from gordo_tpu import compile as compile_plane, telemetry  # noqa: E402
from gordo_tpu.models.estimator import SequenceForecast  # noqa: E402
from gordo_tpu.models.factories import backbone  # noqa: E402
from gordo_tpu.train.fit import make_loss_fn, training_pass  # noqa: E402

TINY = dict(hidden_size=64, num_heads=8, num_kv_heads=2,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=5)
F = 5
SEED = 13
T = 32
BLOCK = 8       # what the tests put in ``backbone.MLA_BLOCK``: sequences of 32 are four blocks
# float32 against float32 on the CPU (measured here: the two fits' changes
# from the common start are 3e-4 of a change apart)
UPDATE_GAP = 3e-3


def module_of(**over):
    return backbone.lfm2_moe(F, F, compute_dtype="float32", **{**TINY, **over})


def shape_of(**over):
    return reference.shape_of({"kind": "lfm2_moe", **TINY, **over}, F, F)


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

def test_the_forecast_matches_the_reference_and_a_bfloat16_control_does_not(x):
    module, shape = module_of(), shape_of()
    params, ref_params = start(module, shape)
    assert set(params) == set(ref_params)
    for name in params:
        np.testing.assert_allclose(params[name], ref_params[name], atol=1e-6,
                                   err_msg=name)
    made = module.apply({"params": params}, x)
    ref = reference.forward(ref_params, x, shape)
    low = reference.forward(ref_params, x, shape, reference.bfloat16)
    tolerance = 1e-4 * float(jnp.abs(ref).max())
    assert float(jnp.abs(made - ref).max()) < tolerance
    assert float(jnp.abs(low - ref).max()) > tolerance
    # one sequence alone is the batch's row
    np.testing.assert_allclose(module.apply({"params": params}, x[1]), made[1], atol=1e-5)


def test_the_published_widths_count_the_parameters_the_file_states():
    module = backbone.lfm2_moe(50, 50)
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-moe-plant.json")) as fh:
        stated = json.load(fh)
    assert module.param_count() == stated["parameters"] == 452712626
    assert module.param_count() == reference.parameter_count(
        dict(reference.shape_of(stated["model"], 50, 50)))
    specs = {n: s for n, s, _ in backbone.param_specs(module.cfg)}
    of = lambda prefix: sum(  # noqa: E731
        int(np.prod(s[1:])) for n, s in specs.items() if n.startswith(prefix))
    assert of("conv_") == 16783360 and of("gqa_") == 10485888
    assert of("dense_") == 72351744 and of("moe_") == 131072 + 75497472
    assert specs["conv_win"] == (4, 2048, 6144) and specs["conv_taps"] == (4, 3, 2048)
    assert specs["gqa_wk"] == (1, 2048, 512) and specs["gqa_q_norm"] == (1, 64)
    assert specs["moe_router"] == (4, 2048, 64) and specs["moe_wg"] == (4, 8, 2048, 1536)
    # the configuration's file copies what the source publishes
    assert stated["layer_types"] == list(backbone.LFM2_LAYER_TYPES) == list(reference.LAYER_TYPES)
    assert (stated["num_attention_heads"], stated["num_key_value_heads"]) == (
        module.cfg.num_heads, module.cfg.num_kv_heads)
    assert stated["conv_L_cache"] == module.cfg.short_conv_kernel_size == 3
    assert stated["rope_parameters"]["rope_theta"] == module.cfg.rope_theta
    assert stated["norm_eps"] == module.cfg.rms_norm_eps and module.cfg.route_eps == 1e-6


def test_the_pattern_at_the_cut_follows_the_sources_layer_types():
    cfg = backbone.lfm2_moe(50, 50).cfg
    assert cfg.pattern == ("conv", "gqa", "conv", "conv", "conv")
    assert [cfg.ffn(l) for l in range(1, 6)] == ["dense", "moe", "moe", "moe", "moe"]
    assert cfg.layers_of("conv") == (1, 3, 4, 5) and cfg.layers_of("gqa") == (2,)
    assert cfg.moe_labels == ("2", "3", "4", "5") and cfg.mixer_kinds == ("conv", "gqa")
    # a deeper cut goes on through the source's period: attention at 2, 6, 10
    assert backbone.lfm2_moe(50, 50, num_layers=10).cfg.layers_of("gqa") == (2, 6, 10)
    # a pattern that is given is the pattern (a YAML list: hashable all the same)
    given = backbone.lfm2_moe(F, F, **{**TINY, "num_layers": 2, "layer_pattern": ["gqa", "gqa"]})
    assert given.cfg.pattern == ("gqa", "gqa") and hash(given.cfg) is not None
    assert given.cfg.mixer_kinds == ("gqa",)
    with pytest.raises(ValueError, match="layer_pattern names one mixer"):
        backbone.lfm2_moe(F, F, **{**TINY, "layer_pattern": ("conv", "gqa")})
    with pytest.raises(ValueError, match="layer_pattern names one mixer"):
        backbone.lfm2_moe(F, F, **{**TINY, "num_layers": 1, "layer_pattern": ("lstm",)})
    with pytest.raises(ValueError, match="num_kv_heads a divisor"):
        backbone.lfm2_moe(F, F, **{**TINY, "num_kv_heads": 3})
    with pytest.raises(TypeError, match="unknown arguments"):
        backbone.lfm2_moe(50, 50, no_such_width=1)


@pytest.mark.parametrize("preset,pattern,kinds", [
    ("kimi_linear", ("kda", "kda", "kda", "mla", "kda"), ("kda", "mla")),
    ("glm_moe_lite", ("mla",) * 5, ("kda", "mla")),
])
def test_the_older_presets_derive_their_pattern_from_full_attn_every(preset, pattern, kinds):
    """Their marks are the parent's (``is_mla`` and both slots), which is why
    their lowered programs did not move."""
    cfg = getattr(backbone, preset)(50, 50).cfg
    assert cfg.layer_pattern == () and cfg.pattern == pattern and cfg.mixer_kinds == kinds
    which = backbone._which(cfg, [(cfg.mixer(l), 0) for l in (4, 5)])
    assert list(which) == ["is_mla", "kda", "mla"]
    np.testing.assert_array_equal(which["is_mla"], [True, pattern[4] == "mla"])
    assert cfg.route_eps == 1e-20 and cfg.num_shared_experts == 1


def test_with_no_shared_expert_no_zero_width_parameter_exists():
    for module in (module_of(), backbone.lfm2_moe(50, 50)):
        specs = backbone.param_specs(module.cfg)
        assert all(min(shape) > 0 for _, shape, _ in specs)
        assert not [name for name, _, _ in specs if "shared" in name]
    # and a model that has one keeps its three matrices
    kept = [n for n, _, _ in backbone.param_specs(backbone.glm_moe_lite(50, 50).cfg)]
    assert {"moe_shared_wg", "moe_shared_wu", "moe_shared_wd", "mtp_moe_shared_wd"} <= set(kept)


def test_loss_and_gradients_match_jax_grad_of_the_plain_forward(batch):
    x, y, w = batch
    module, shape = module_of(), shape_of()
    params, ref_params = start(module, shape)
    apply_fn, second = training_pass(module, counts=True)
    assert second == 0.0
    (value, aux), grads = jax.value_and_grad(
        make_loss_fn(apply_fn, "mse", aux=True, second=second), has_aux=True)(
            params, x, y, w)
    ref_value, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, x, y, w, shape))(ref_params)
    assert float(value) == pytest.approx(float(ref_value), rel=1e-5)
    assert aux["tokens"].shape == (4, 2)
    assert set(grads) == set(ref_grads)
    for name in grads:
        assert relative(grads[name], ref_grads[name]) < 2e-4, name
    low = jax.grad(lambda p: reference.loss(p, x, y, w, shape, reference.bfloat16))(ref_params)
    assert max(relative(low[n], ref_grads[n]) for n in low) > 2e-3


def test_the_layer_by_layer_step_is_the_step_of_jax_grad(batch):
    """The reference's fit writes the chain rule over the parts out; one of
    its steps moves every parameter as Adam on ``jax.grad`` of the plain
    forward's loss does."""
    x, y, w = batch
    shape = shape_of()
    a = dict(shape)
    ref_params, _ = reference.init_params(SEED, shape)
    ref_value, grads = jax.value_and_grad(
        lambda p: reference.loss(p, x, y, w, shape))(ref_params)
    # the step's Adam donates what it updates: it gets a copy of its own
    model = reference.split(a, jax.tree.map(jnp.array, ref_params))
    zeros = lambda: reference.split(a, jax.tree.map(jnp.zeros_like, ref_params))  # noqa: E731
    value = reference._step(reference._pieces(shape, None, None), a, model,
                            zeros(), zeros(), 1, 1e-3, x, y, w)
    assert float(value) == pytest.approx(float(ref_value), rel=1e-5)
    # Adam's first step is lr * g / (|g| + eps): compare where g is not tiny
    moved = reference.split(a, {n: -1e-3 * g / (jnp.abs(g) + reference.ADAM_EPS)
                                for n, g in grads.items()})
    before = reference.split(a, ref_params)
    groups = list(zip(model.layers, before.layers, moved.layers)) + [
        (model.around, before.around, moved.around)]
    for now, was, step in groups:
        for name in now:
            big = jnp.abs(step[name]) > 0.999e-3
            np.testing.assert_allclose(
                jnp.where(big, now[name] - was[name], 0.0),
                jnp.where(big, step[name], 0.0), atol=2e-6, err_msg=name)


# -- 2. the two new mixers -------------------------------------------------------

def mixer_inputs(kind_, dtype, t):
    """One layer's parameters of ``kind_`` and a group of two sequences."""
    cfg = backbone.lfm2_moe(F, F, compute_dtype=dtype, **TINY).cfg
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    p = {name: backbone._initializer(init)(next(keys), shape[1:])
         for name, shape, init in backbone.param_specs(cfg)
         if name.startswith(kind_ + "_")}
    return cfg, p, jax.random.normal(next(keys), (2, t, 64))


def whole_square_core(cfg, q, k, v, window=0, prefix="gqa"):
    """The grouped core's reference: keys and values repeated for every query
    head of their group, every pair of the ``t x t`` square multiplied, the
    upper triangle masked, one softmax over whole rows (this preset has no
    window: ``tests/test_backbone_afmoe.py`` has the windowed one)."""
    assert not window
    cd = cfg.compute_dtype
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bthc,bshc->bhts", q.astype(cd), k.astype(cd),
                        preferred_element_type=jnp.float32)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores * (q.shape[-1] ** -0.5), -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshv->bthv", probs.astype(cd), v.astype(cd),
                      preferred_element_type=jnp.float32)


def mixer_and_gradients(cfg, p, h):
    ct = jax.random.normal(jax.random.PRNGKey(8), h.shape)

    @jax.jit        # traced here, with whatever the test has put in the module
    def both(p, h):
        out, vjp = jax.vjp(lambda p, h: backbone.gqa_mixer(cfg, p, h), p, h)
        return out, vjp(ct)

    out, (dp, dh) = both(p, h)
    return out, {**dp, "input": dh}


@pytest.mark.parametrize("t", [4 * BLOCK, BLOCK])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_blocked_grouped_core_is_the_whole_squares(dtype, t, monkeypatch):
    """As for the latent core (``tests/test_backbone_glm.py``): the blocks
    repeat the square's arithmetic forward, and with bfloat16 operands a
    block's share of ``dk`` and ``dv`` is rounded once a block before the
    float32 sum, so gradients are held to 2 % of their largest entry.  The
    reference repeats keys and values four times; the core does not."""
    cfg, p, h = mixer_inputs("gqa", dtype, t)
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    made, made_grads = mixer_and_gradients(cfg, p, h)
    monkeypatch.setattr(backbone, "_grouped_core", whole_square_core)
    ref, ref_grads = mixer_and_gradients(cfg, p, h)
    assert relative(made, ref) < (1e-6 if dtype == "float32" else 1e-2)
    assert set(made_grads) == set(p) | {"input"}
    for name, g in ref_grads.items():
        assert float(jnp.abs(g).max()) > 0, name
        assert relative(made_grads[name], g) < (1e-5 if dtype == "float32" else 2e-2), name


@pytest.mark.parametrize("t,rule", [(4 * BLOCK, "causal_blocks"), (BLOCK + 4, "whole"),
                                    (BLOCK, "whole"), (BLOCK // 2, "whole")])
def test_the_grouped_core_is_counted_by_the_one_block_rule(t, rule, monkeypatch):
    """``_query_blocks`` is the latent core's rule too: a length the block
    does not divide is one block; the grouped core counts on a series of its
    own and leaves the latent core's alone; keys are never repeated."""
    cfg, p, h = mixer_inputs("gqa", "float32", t)
    counted = telemetry.REGISTRY.get("gordo_gqa_attention_total")
    latent = telemetry.REGISTRY.get("gordo_mla_attention_total")
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    before = {r: counted.value(r) for r in ("causal_blocks", "whole")}
    latent_before = [latent.value(r) for r in ("causal_blocks", "whole")]
    with telemetry.span("gordo.test.trace") as attrs:
        text = jax.jit(lambda p, h: backbone.gqa_mixer(cfg, p, h)).lower(p, h).as_text()
    assert {r: counted.value(r) - before[r] for r in before} == {
        rule: 1, "whole" if rule == "causal_blocks" else "causal_blocks": 0}
    assert [latent.value(r) for r in ("causal_blocks", "whole")] == latent_before
    n = 4 if rule == "causal_blocks" else 1
    assert attrs["gqa_attn_traces"] == 1 and attrs["gqa_attn_blocks"] == n
    assert attrs["gqa_attn_pairs_computed"] == n * (n + 1) // 2
    assert attrs["gqa_attn_pairs_square"] == n * n
    assert "mla_attn_traces" not in attrs
    assert text.count("stablehlo.exponential") == n
    # two key/value heads stay two in every product: each contracts a block of
    # queries or weights with keys or values that still have 2 heads
    q, k, v = (jnp.zeros((2, t, heads, 8)) for heads in (8, 2, 2))
    products = [eqn for eqn in jax.make_jaxpr(
        lambda q, k, v: backbone._grouped_core(cfg, q, k, v))(q, k, v).eqns
        if eqn.primitive.name == "dot_general"]
    assert len(products) == 2 * n
    for eqn in products:        # einsum may put either operand first
        assert (2, 8) in [v.aval.shape[2:] for v in eqn.invars], eqn
    assert backbone._query_blocks(4 * BLOCK, latent, "mla") == [
        (0, BLOCK), (BLOCK, 2 * BLOCK), (2 * BLOCK, 3 * BLOCK), (3 * BLOCK, 4 * BLOCK)]


@pytest.mark.parametrize("kind_", ["conv", "gqa"])
def test_no_row_sees_a_later_one_also_across_a_block_boundary(kind_, monkeypatch):
    cfg, p, h = mixer_inputs(kind_, "float32", 4 * BLOCK)
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    mixer = jax.jit(lambda h: backbone.MIXERS[kind_](cfg, p, h))
    out = mixer(h)
    for first in (2 * BLOCK, 2 * BLOCK + 3):   # a block's first row, and one inside it
        later = h.at[:, first:].add(1.0)
        moved = mixer(later)
        np.testing.assert_array_equal(moved[:, :first], out[:, :first])
        assert float(jnp.abs(moved[:, first:] - out[:, first:]).min(axis=-1).max()) > 1e-4


def test_the_convolution_reads_its_own_row_and_the_two_before():
    """``y_t`` moves with rows ``t - 2 .. t`` and with no other; the first
    rows read zeros before the sequence.  ``short_conv`` as KDA has it, at
    three taps."""
    cfg, p, h = mixer_inputs("conv", "float32", T)
    out = backbone.conv_mixer(cfg, p, h)
    moved = backbone.conv_mixer(cfg, p, h.at[:, 10].add(1.0))
    changed = np.flatnonzero(np.abs(np.asarray(moved - out)).max(axis=(0, 2)) > 1e-7)
    assert list(changed) == [10, 11, 12]
    a = dict(shape_of())
    np.testing.assert_allclose(out, reference._conv(a, p, h, None), atol=1e-5)
    # the taps alone: a delta at row 3 comes out at rows 3, 4, 5 times w_2, w_1, w_0
    w = jnp.asarray([[2.0], [3.0], [5.0]])
    delta = jnp.zeros((1, 8, 1)).at[0, 3, 0].set(1.0)
    np.testing.assert_array_equal(
        backbone.short_conv(delta, w)[0, :, 0], [0, 0, 0, 5.0, 3.0, 2.0, 0, 0])


def test_a_rotated_score_depends_on_the_distance_alone():
    """All of a head's channels are rotated, queries and keys alike."""
    cfg = module_of().cfg
    width = cfg.gqa_head_dim
    q, k = jax.random.normal(jax.random.PRNGKey(4), (2, width))
    cos, sin = backbone.rotary(T, width, cfg.rope_theta)
    rows = lambda v: backbone.rotate(jnp.broadcast_to(v, (T, width)), cos, sin)  # noqa: E731
    scores = rows(q) @ rows(k).T                        # (t, s)
    for shift in (1, 7):
        np.testing.assert_allclose(scores[shift:, shift:], scores[:-shift, :-shift],
                                   atol=1e-5)
    assert float(jnp.abs(scores[5, 0] - scores[0, 0])) > 1e-3   # and on nothing less
    # the reference's rotation, written out on its own, is the same rotation
    np.testing.assert_allclose(rows(q), reference.rope(
        jnp.broadcast_to(q, (1, T, 1, width)), cfg.rope_theta)[0, :, 0], atol=1e-6)


def test_a_query_head_reads_the_key_value_head_of_its_group():
    """Head ``i`` reads key/value head ``i // 4``: moving key head 1's matrix
    columns moves the outputs of query heads 4-7 and of no other."""
    cfg, p, h = mixer_inputs("gqa", "float32", T)
    eye = {**p, "gqa_wo": jnp.eye(64)}          # the heads' outputs, side by side
    out = backbone.gqa_mixer(cfg, eye, h)
    moved = backbone.gqa_mixer(cfg, {**eye, "gqa_wv": eye["gqa_wv"].at[:, 8:].add(0.5)}, h)
    changed = np.abs(np.asarray(moved - out)).max(axis=(0, 1)).reshape(8, 8).max(axis=1) > 1e-6
    assert list(changed) == [False] * 4 + [True] * 4
    a = dict(shape_of())
    np.testing.assert_allclose(backbone.gqa_mixer(cfg, p, h), reference._gqa(a, p, h, None),
                               atol=1e-5)
    assert relative(reference._gqa(a, p, h, None, "wrong_group"),
                    reference._gqa(a, p, h, None)) > 1e-2


# -- 3. the share --------------------------------------------------------------------

def expert_parameters(cfg, key):
    specs = [(n, s[1:], i) for n, s, i in backbone.param_specs(cfg) if n.startswith("moe_")]
    keys = jax.random.split(key, len(specs))
    return {n: backbone._initializer(i)(k, s) for (n, s, i), k in zip(specs, keys)}


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_of_every_chip_add_up_to_the_uncut_layer(held):
    """Every range of ``held`` experts (8, 4 and 2 chips sharing the layer):
    the routed parts add up to what the uncut reference gives for the whole
    layer.  No shared expert: nothing is counted once."""
    whole = module_of(experts_held=8).cfg
    p = expert_parameters(whole, jax.random.PRNGKey(6))
    assert set(p) == {"moe_router", "moe_wg", "moe_wu", "moe_wd"}
    xs = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    a = dict(shape_of(experts_held=8))
    ref = reference._experts(a, p, xs, None)
    total, pairs = 0.0, 0
    for first in range(0, 8, held):
        cfg = module_of(experts_held=held, experts_held_from=first).cfg
        mine = {n: (v[first:first + held] if n != "moe_router" else v) for n, v in p.items()}
        y, counted = backbone.expert_layer(cfg, mine, xs)
        total = total + y
        pairs += int(counted.sum())
        np.testing.assert_allclose(
            y, reference._experts(a, mine, xs, None, held=(first, held)), atol=2e-5)
    assert pairs == 48 * 2                      # every selected pair fell on one chip
    np.testing.assert_allclose(total, ref, atol=5e-5)
    # the selected weights add up to 1 / (1 + 1e-6 / sum): the source's constant
    _, weights = backbone.route(whole, p["moe_router"], xs)
    experts, ref_weights = reference.routing(a, p["moe_router"], xs)
    np.testing.assert_allclose(weights, ref_weights, atol=1e-6)
    assert float(jnp.abs(weights.sum(-1) - 1.0).max()) < 1e-5


def test_counts_name_the_four_expert_layers_by_their_numbers(x):
    module = module_of()
    params, _ = start(module, shape_of())
    _, counts = module.apply({"params": params}, x, counts=True)
    assert counts["tokens"].shape == (4, 2) and module.cfg.moe_labels == ("2", "3", "4", "5")
    assert int(counts["selected"]) == 3 * T * 2 * 4
    assert int(counts["held"]) == int(counts["tokens"].sum()) <= int(counts["selected"])
    with pytest.raises(ValueError, match="no multi-token-prediction module"):
        module.apply({"params": params}, x, mtp=True)


# -- 4. a project through build_project ---------------------------------------------------

def config_of(**model):
    """One plant machine's forecaster as a project describes it, in the
    shape ``benchmark/kinds/sequence_build.py`` ``project_doc`` reads; widths
    that ``model`` leaves out are the published ones."""
    return {
        "detector": "DiffBasedAnomalyDetector", "scalers": ["MinMaxScaler"],
        "estimator": "SequenceForecast",
        "model": {"kind": "lfm2_moe", "epochs": 1, "learning_rate": 0.001,
                  "compute_dtype": "auto", "experts_held_from": 0, **model},
        "cv": {"splitter": "TimeSeriesSplit", "n_splits": 3},
        "dataset": {"type": "RandomDataset", "resolution": "10min", "n_tags": F,
                    "train_start_date": "2017-01-01T00:00:00+00:00",
                    "train_end_date": "2017-01-02T12:00:00+00:00", "rows": 217},
    }


def tiny_config():
    return config_of(context=T, stride=16, batch_size=4, **TINY)


def reference_of(config, rows, folds):
    """The reference's final fit of one machine and, with ``folds``, the
    thresholds from its cross-validation."""
    out = reference.fit(np.asarray(rows), config["model"], kind.model_seed(SEED))
    if folds:
        out["thresholds"] = reference.cross_validate(
            np.asarray(rows), config["model"], kind.model_seed(SEED),
            int(config["cv"]["n_splits"]))
    return out


def gaps(made, ref):
    """How far a written machine is from the reference's fit of it: the
    loss; the two fits' changes from the common start, as the larger of the
    worst parameter's gap between their norms and the median parameter's
    distance between the changes themselves (each relative to the
    reference's change of that parameter, or the median parameter's if
    larger); the worst threshold."""
    out = {"loss": abs(made["history"][-1] - ref["history"][-1]) / abs(ref["history"][-1])}
    d = reference.distances(ref["model"], made["params"], kind.model_seed(SEED), ref["shape"])
    ours, theirs, apart = (
        np.asarray(d[k], np.float64) for k in ("moved_ours", "moved_theirs", "apart"))
    scale = np.maximum(ours, np.median(ours))
    out["update"] = float(max(np.max(np.abs(theirs - ours) / scale), np.median(apart / scale)))
    if "thresholds" in ref and "thresholds" in made:
        t_ref = np.asarray(ref["thresholds"], np.float64)
        out["threshold"] = float(np.max(
            np.abs(made["thresholds"] - t_ref) / np.maximum(t_ref, np.median(t_ref))))
    return out


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Two machines through ``build_project`` with NO ``max_bucket_size``:
    the planner reads the parameter count and puts both in one chunk.  The
    block is 8 rows, so a sequence is four blocks."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config = tiny_config()
    out = str(tmp_path_factory.mktemp("lfm2-project"))
    machines = NormalizedConfig(kind.project_doc(config, SEED, 2), "lfm2-test").machines
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
    return config, out, result, before, telemetry.REGISTRY.snapshot()["metrics"]


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


def test_the_counters_the_span_and_the_artifacts_metadata(built):
    from gordo_tpu import artifacts

    config, out, result, before, after = built
    delta = lambda name, *labels: (  # noqa: E731
        counter(after, name, *labels) - counter(before, name, *labels))
    selected, held = delta("gordo_moe_selected_pairs_total"), delta("gordo_moe_held_pairs_total")
    assert selected > 0 and 0 < held <= selected
    assert delta("gordo_moe_tokens_total") == held
    labels = {tuple(json.loads(k)) for k in after["gordo_moe_tokens_total"]["series"]}
    assert {(layer, e) for layer in ("2", "3", "4", "5") for e in ("0", "1")} <= labels
    # what the program is made of: four convolutions for each attention
    # layer, wherever a mixer was traced
    convs, cores = delta("gordo_backbone_mixers_total", "conv"), delta(
        "gordo_backbone_mixers_total", "gqa")
    assert 0 < cores < convs <= 2 * cores       # layer 1's and the scan body's
    assert delta("gordo_backbone_mixers_total", "kda") == 0
    assert delta("gordo_gqa_attention_total", "causal_blocks") == cores
    assert delta("gordo_gqa_attention_total", "whole") == 0
    assert delta("gordo_mla_attention_total") == 0
    counts = result.timeline[0]["counts"]["enqueue"]
    assert counts["gqa_attn_traces"] == cores and counts["gqa_attn_blocks"] == 4 * cores
    assert counts["gqa_attn_pairs_computed"] == 10 * cores
    assert counts["gqa_attn_pairs_square"] == 16 * cores
    assert not [name for name in counts if name.startswith("swa_attn")]   # no windowed core
    assert counts["layers_conv"] == 4 and counts["layers_gqa"] == 1
    assert "layers_kda" not in counts and "mtp_depth" not in counts
    assert counts["context"] == T and counts["experts_held"] == 2
    assert counts["params"] == module_of().param_count() == 153685
    (snapshot,) = telemetry.load_snapshot_dir(os.path.join(out, telemetry.SNAPSHOT_DIR))
    assert "gordo_gqa_attention_total" in json.dumps(snapshot)
    assert "gordo_backbone_mixers_total" in json.dumps(snapshot)
    _, refs = artifacts.discover(out)
    meta = refs[0].load_metadata()["model"]
    moe = meta["cross_validation"]["moe"]
    assert np.asarray(moe["tokens_per_held_expert"]).shape == (4, 2)
    assert "loss_terms" not in meta["cross_validation"]
    assert "multi_token_prediction" not in json.dumps(meta)
    assert "shared" not in json.dumps(meta)


def test_the_artifact_scores_and_predicts_as_the_reference_forecasts(built):
    from gordo_tpu import artifacts

    config, out, _, _, _ = built
    _, refs = artifacts.discover(out)
    by_name = {ref.name: ref for ref in refs}
    name = kind.machine_names(SEED, 2)[1]
    detector = by_name[name].load_model()
    estimator = detector.base_estimator.steps[-1][1]
    assert isinstance(estimator, SequenceForecast) and estimator.kind == "lfm2_moe"
    assert "multi_token_prediction" not in estimator.get_metadata()
    assert not [n for n in estimator.params_ if "shared" in n or n.startswith("mtp_")]
    rows = kind.reference_rows(config, name)
    frame = detector.anomaly(rows, rows)
    assert len(frame) == len(rows) - 1
    assert np.isfinite(frame[("total-anomaly-score", "")].to_numpy()).all()
    scaled = reference.minmax(rows, rows)
    shape = reference.shape_of(config["model"], F, F)
    ref = reference.predict(
        jax.tree.map(jnp.asarray, estimator.params_), rows, rows, config["model"], shape)
    np.testing.assert_allclose(estimator.predict(scaled), ref, atol=1e-4)


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


@pytest.fixture(scope="module")
def sound_fit():
    config = tiny_config()
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    return config, rows, reference_of(config, rows, folds=False)


@pytest.mark.parametrize("fault", ["float8", "half_batch", "frozen_leaf", "no_taps",
                                   "no_qk_norm", "no_rotation", "wrong_group"])
def test_a_planted_fault_reads_far_above_what_a_sound_build_may(fault, sound_fit):
    """The faults a comparison with this reference has to catch, planted in
    the reference's own fit: float8 operands, half of every minibatch left
    out, a matrix left at its start, and one per new mechanism (the
    convolution's two earlier taps zero, the heads' norms left out, the
    rotation left out, query head ``i`` reading key/value head ``i % 2``).
    Each reads above what the build above is held to."""
    config, rows, ref = sound_fit
    seed = kind.model_seed(SEED)
    if fault == "frozen_leaf":
        low = {**ref, "model": reference.freeze(ref["model"], seed, ref["shape"], 0, "conv_wout")}
    elif fault == "float8":
        low = reference.fit(rows, config["model"], seed, quantize=reference.float8)
    else:
        low = reference.fit(rows, config["model"], seed, fault=fault)
    far = gaps({"params": low["model"], "history": low["history"]}, ref)
    assert far["update"] > 3 * UPDATE_GAP
    if fault == "frozen_leaf":          # a matrix that never moved reads 1
        assert far["update"] == pytest.approx(1.0)
    if fault == "half_batch":
        assert far["update"] > 0.3
    if fault == "float8":
        assert far["loss"] > 1e-3
    with pytest.raises(ValueError, match="unknown fault"):
        reference.fit(rows, config["model"], seed, fault="no_such_fault")


def test_a_452_million_parameter_model_is_a_chunk_of_one():
    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import _parameter_count, default_bucket_size
    from gordo_tpu.parallel.anomaly import analyze_definition

    # the source's layers 1-5, experts 0-7 of 64: the widths are the preset's
    config = config_of(context=2048, stride=512, batch_size=8, num_layers=5, experts_held=8)
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    assert _parameter_count(spec, (50, 50)) == 452712626
    assert default_bucket_size(spec, (50, 50)) == 1


def test_the_lowered_program_names_the_scopes_the_metrics_read():
    """Forward, recomputation and backward all carry the scopes; the four
    expert layers are one scan whose body chooses between its two kinds."""
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
    # the folds' "forecast or not", and the scan body's choice between the
    # convolution and attention: forward (a fit's and a forecast's), the
    # backward pass's recomputation
    assert lowered.as_text().count("stablehlo.case") == 1 + 3
    named = lowered.as_text(debug_info=True)
    for scope in ("backbone.conv/backbone.conv.gate/", "backbone.gqa/backbone.gqa.attn/",
                  "backbone.moe.experts/", "backbone.moe.route/", "backbone.ffn/",
                  "jvp(backbone.conv)/backbone.conv.gate/", "jvp(backbone.gqa)/backbone.gqa.attn/",
                  "transpose(jvp(backbone.conv))/backbone.conv.gate/",
                  "transpose(jvp(backbone.gqa))/backbone.gqa.attn/"):
        assert scope in named, scope
    for absent in ("backbone.kda", "backbone.mla", "backbone.mtp"):
        assert absent not in named, absent

"""The ``afmoe`` backbone (``models/factories/backbone.py``) against its plain
reference (``benchmark/reference/afmoe.py``) at a tiny preset: hidden 64, 8
query heads over 2 key/value heads of 16 (a head width of its own: 64 / 8 is
8), a window of 12 rows, 8 experts of which 2 held and a shared one,
sequences of 32 rows, five layers (windowed + dense, windowed + experts, full
+ experts, twice windowed + experts).  Float32 on the CPU, so agreement is
tight; a bfloat16 control has to fail the same tolerance."""

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
from benchmark.reference import afmoe as reference  # noqa: E402
from gordo_tpu import compile as compile_plane, telemetry  # noqa: E402
from gordo_tpu.models.estimator import SequenceForecast  # noqa: E402
from gordo_tpu.models.factories import backbone  # noqa: E402
from gordo_tpu.train.fit import make_loss_fn, training_pass  # noqa: E402

WINDOW = 12     # no multiple of the tests' block: a trip reaches two whole blocks back
TINY = dict(hidden_size=64, num_heads=8, num_kv_heads=2, head_dim=16, attn_window=WINDOW,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=5)
F = 5
SEED = 13
T = 32
BLOCK = 8       # what the tests put in ``backbone.MLA_BLOCK``: sequences of 32 are four blocks
# float32 against float32 on the CPU (measured here: the two fits' changes
# from the common start are 4e-4 of a change apart)
UPDATE_GAP = 3e-3


def module_of(**over):
    return backbone.afmoe(F, F, compute_dtype="float32", **{**TINY, **over})


def shape_of(**over):
    return reference.shape_of({"kind": "afmoe", **TINY, **over}, F, F)


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


@pytest.fixture
def blocks(monkeypatch):
    """Blocks of 8 rows in the program and of 16 in the reference."""
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    monkeypatch.setattr(reference, "QUERY_ROWS", 2 * BLOCK)


def relative(made, ref):
    return float(jnp.abs(made - ref).max() / jnp.maximum(jnp.abs(ref).max(), 1e-30))


# -- 1. forward, loss and gradients -------------------------------------------

def test_the_forecast_matches_the_reference_and_a_bfloat16_control_does_not(x, blocks):
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
    # every mechanism the reference can leave out is one the forecast needs
    for fault in reference.FORWARD_FAULTS:
        assert relative(reference.forward(ref_params, x, shape, fault=fault), ref) > 0.05, fault


def test_the_published_widths_count_the_parameters_the_file_states():
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-mini-plant.json")) as fh:
        stated = json.load(fh)
    held = stated["experts"]["held_here"]
    module = backbone.afmoe(50, 50, experts_held=held)
    assert held in (8, 16) and stated["model"]["experts_held"] == held
    assert module.param_count() == stated["parameters"] == {
        16: 603180338, 8: 401853746}[held]
    assert module.param_count() == reference.parameter_count(
        dict(reference.shape_of(stated["model"], 50, 50)))
    specs = {n: s for n, s, _ in backbone.param_specs(module.cfg)}
    of = lambda prefix: sum(  # noqa: E731
        int(np.prod(s[1:])) for n, s in specs.items() if n.startswith(prefix))
    # q, the gate and o 2,048 x 4,096 each, k and v 2,048 x 512, two norms of
    # 128 and the output's of 2,048: 27.3 M a layer
    assert of("gqa_") == of("swa_") == 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2048
    assert of("dense_") == 3 * 2048 * 6144 + 2048
    assert of("moe_") == 2048 * 128 + (held + 1) * 3 * 2048 * 1024 + 2048
    assert specs["swa_wq"] == (4, 2048, 4096) and specs["gqa_wk"] == (1, 2048, 512)
    assert specs["swa_wz"] == (4, 2048, 4096) and specs["swa_q_norm"] == (4, 128)
    assert specs["moe_router"] == (4, 2048, 128) and specs["moe_wg"] == (4, held, 2048, 1024)
    assert specs["moe_shared_wd"] == (4, 1024, 2048) and specs["moe_post_norm"] == (4, 2048)
    # the configuration's file copies what the source publishes
    assert stated["layer_types"] == list(backbone.AFMOE_LAYER_TYPES * 8) == list(
        reference.LAYER_TYPES)
    cfg = module.cfg
    assert (stated["num_attention_heads"], stated["num_key_value_heads"], stated["head_dim"]) == (
        cfg.num_heads, cfg.num_kv_heads, cfg.gqa_head_dim) == (32, 4, 128)
    assert stated["sliding_window"] == cfg.attn_window == 2048
    assert stated["rope_theta"] == cfg.rope_theta and stated["rms_norm_eps"] == cfg.rms_norm_eps
    assert stated["route_scale"] == cfg.routed_scaling_factor == 2.826
    assert (stated["num_experts"], stated["num_experts_per_tok"], stated["num_shared_experts"]) == (
        cfg.num_experts, cfg.num_experts_per_token, cfg.num_shared_experts) == (128, 8, 1)
    assert (stated["intermediate_size"], stated["moe_intermediate_size"]) == (
        cfg.intermediate_size, cfg.moe_intermediate_size)


def test_the_pattern_at_the_cut_follows_the_sources_layer_types():
    cfg = backbone.afmoe(50, 50).cfg
    assert cfg.pattern == ("swa", "swa", "gqa", "swa", "swa")
    assert [cfg.ffn(l) for l in range(1, 6)] == ["dense", "moe", "moe", "moe", "moe"]
    assert cfg.layers_of("swa") == (1, 2, 4, 5) and cfg.layers_of("gqa") == (3,)
    assert cfg.moe_labels == ("2", "3", "4", "5") and cfg.mixer_kinds == ("gqa", "swa")
    assert cfg.attn_gate and cfg.post_norms and not cfg.gqa_rotary and cfg.moe_row_blocks
    # a deeper cut goes on through the source's period: the whole prefix at 3, 7, 11
    assert backbone.afmoe(50, 50, num_layers=12).cfg.layers_of("gqa") == (3, 7, 11)
    which = backbone._which(cfg, [(cfg.mixer(l), 0) for l in (3, 4)])
    assert list(which) == ["is_swa", "gqa", "swa"]
    np.testing.assert_array_equal(which["is_swa"], [False, True])
    with pytest.raises(ValueError, match="needs an attn_window"):
        backbone.afmoe(F, F, **{**TINY, "attn_window": 0})
    with pytest.raises(ValueError, match="an even head width"):
        backbone.afmoe(F, F, **{**TINY, "head_dim": 7})
    with pytest.raises(ValueError, match="where a layer is rotated, a rope_theta"):
        backbone.afmoe(F, F, **{**TINY, "rope_theta": 0.0})
    with pytest.raises(ValueError, match="no output norms"):
        backbone.afmoe(F, F, **{**TINY, "mtp_depth": 1})
    # the older grouped-query preset keeps its head width, its one kind and no gate
    older = backbone.lfm2_moe(50, 50).cfg
    assert older.gqa_head_dim == 64 and older.gqa_rotary and not older.attn_gate
    assert not [n for n, _, _ in backbone.param_specs(older) if "post_norm" in n or "_wz" in n]


def test_loss_and_gradients_match_jax_grad_of_the_plain_forward(batch, blocks):
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


# -- 2. the windowed core, the gate and the two kinds of layer -------------------

def mixer_inputs(kind_, dtype, t, **over):
    """One layer's parameters of ``kind_`` (the mixer's own: its output's
    norm is ``_mixer_of``'s) and a group of two sequences."""
    cfg = backbone.afmoe(F, F, compute_dtype=dtype, **{**TINY, **over}).cfg
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    p = {name: backbone._initializer(init)(next(keys), shape[1:])
         for name, shape, init in backbone.param_specs(cfg)
         if name.startswith(kind_ + "_") and not name.endswith("_post_norm")}
    return cfg, p, jax.random.normal(next(keys), (2, t, 64))


def whole_square_core(cfg, q, k, v, window=0, prefix="gqa"):
    """The grouped core's reference: keys and values repeated for every query
    head of their group, every pair of the ``t x t`` square multiplied, what
    lies above the diagonal or ``window`` rows or more below it masked, one
    softmax over whole rows."""
    cd = cfg.compute_dtype
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bthc,bshc->bhts", q.astype(cd), k.astype(cd),
                        preferred_element_type=jnp.float32)
    apart = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (apart >= 0) & (apart < window) if window else apart >= 0
    probs = jax.nn.softmax(jnp.where(seen, scores * (q.shape[-1] ** -0.5), -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshv->bthv", probs.astype(cd), v.astype(cd),
                      preferred_element_type=jnp.float32)


def mixer_and_gradients(cfg, kind_, p, h):
    ct = jax.random.normal(jax.random.PRNGKey(8), h.shape)

    @jax.jit        # traced here, with whatever the test has put in the module
    def both(p, h):
        out, vjp = jax.vjp(lambda p, h: backbone.MIXERS[kind_](cfg, p, h), p, h)
        return out, vjp(ct)

    out, (dp, dh) = both(p, h)
    return out, {**dp, "input": dh}


ROWS = (2 * BLOCK, BLOCK, BLOCK // 2)    # what the tests put in ``backbone.WINDOW_ROWS``


def trip_bytes(b, window, rows, heads=8):
    """One trip's float32 scores, as ``backbone._window_rows`` counts them."""
    return 4 * b * heads * rows * (window + rows)


@pytest.fixture
def trips(monkeypatch):
    """Trips of 16, 8 or 4 rows (and the tests' block where none of them
    divides window and sequence), under a budget that all of them fit."""
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    monkeypatch.setattr(backbone, "WINDOW_ROWS", ROWS)
    monkeypatch.setattr(backbone, "WINDOW_TRIP_BYTES", 1 << 30)


@pytest.mark.parametrize("kind_,t,window,tight", [
    ("swa", 4 * BLOCK, 12, False),      # trips of 4 rows: 12 is no multiple of 8 or 16
    ("swa", 4 * BLOCK, 2 * BLOCK, False),   # W is a trip's rows: two trips of 16
    ("swa", 4 * BLOCK, 3, False),       # no candidate divides W: the block's rows, four trips
    ("swa", 2 * BLOCK, 2 * BLOCK, False),   # T <= W: the window hides nothing, no loop
    ("swa", 4 * BLOCK, 5 * BLOCK, False),
    ("swa", 4 * BLOCK + 4, 12, False),  # T no multiple of the block: nine trips of 4
    ("swa", 4 * BLOCK + 4, 5, False),   # nor of any candidate: one trip, the whole square
    ("swa", 4 * BLOCK, 2 * BLOCK, True),    # no trip fits the budget: the smallest, 4 rows
    ("gqa", 4 * BLOCK, 12, True),       # the whole-prefix core's blocks recomputed
    ("gqa", 4 * BLOCK, 12, False),
    ("gqa", 16 * BLOCK, 12, False),     # past ATTN_MAX_BLOCKS blocks: eight of two blocks' rows
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_blocked_windowed_core_is_the_whole_masked_squares(
        dtype, kind_, t, window, tight, trips, monkeypatch):
    """The blocks repeat the masked square's arithmetic forward (a row's
    softmax is over exactly the entries it has there); with bfloat16 operands
    a block's share of ``dk`` and ``dv`` is rounded once a block before the
    float32 sum, so gradients are held to 2 % of their largest entry."""
    cfg, p, h = mixer_inputs(kind_, dtype, t, attn_window=window)
    if tight:
        monkeypatch.setattr(backbone, "ATTN_KEEP_BYTES", 0)
        monkeypatch.setattr(backbone, "WINDOW_TRIP_BYTES", 0)
    made, made_grads = mixer_and_gradients(cfg, kind_, p, h)
    monkeypatch.setattr(backbone, "_grouped_core", whole_square_core)
    ref, ref_grads = mixer_and_gradients(cfg, kind_, p, h)
    assert relative(made, ref) < (1e-6 if dtype == "float32" else 1e-2)
    assert set(made_grads) == set(p) | {"input"}
    for name, g in ref_grads.items():
        assert float(jnp.abs(g).max()) > 0, name
        assert relative(made_grads[name], g) < (1e-5 if dtype == "float32" else 2e-2), name


def core_and_gradients(core, cfg, b, t, window):
    """A grouped core's output and its gradients for ``q``, ``k``, ``v``."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v = (jax.random.normal(key, (b, t, heads, 16))
               for key, heads in zip(keys, (8, 2, 2)))
    ct = jax.random.normal(keys[3], q.shape)
    out, vjp = jax.jit(lambda *a: jax.vjp(
        lambda *a: core(cfg, *a, window, "swa"), *a))(q, k, v)
    return out, vjp(ct)


@pytest.mark.parametrize("t,window,b,rows,budget", [
    (16 * BLOCK, 4 * BLOCK, 1, 4, trip_bytes(1, 4 * BLOCK, 4)),   # the cell's ratios: T = 4 W = 32 rows
    (4 * BLOCK, 4 * BLOCK, 1, 0, 1 << 30),      # T = W
    (2 * BLOCK, 5 * BLOCK, 1, 0, 1 << 30),      # T < W
    (4 * BLOCK, 12, 1, 4, 1 << 30),             # W a multiple of the smallest candidate alone
    (6 * BLOCK, 11, 1, BLOCK, 1 << 30),         # W no multiple of a trip's rows (the block's)
    (3 * BLOCK, 2 * BLOCK - 2, 1, BLOCK, 1 << 30),  # a reach past the sequence's start in every trip
    (4 * BLOCK + 2, 12, 1, 4 * BLOCK + 2, 1 << 30),     # T no multiple of them: one trip
    (8 * BLOCK, 2 * BLOCK, 2, BLOCK, trip_bytes(1, 2 * BLOCK, 2 * BLOCK)),   # b = 2 halves the rows
    (8 * BLOCK, 2 * BLOCK, 1, 2 * BLOCK, trip_bytes(1, 2 * BLOCK, 2 * BLOCK)),
])
def test_the_windowed_cores_trips_are_the_whole_masked_square(
        t, window, b, rows, budget, trips, monkeypatch):
    """Forward and the gradients for ``q``, ``k`` and ``v`` of the core alone,
    every block a trip of the one loop (``rows`` 0: the window covers the
    sequence and the core is the one with no window)."""
    monkeypatch.setattr(backbone, "WINDOW_TRIP_BYTES", budget)
    cfg = backbone.afmoe(F, F, compute_dtype="float32", **TINY).cfg
    with telemetry.span("gordo.test.trace") as attrs:
        made, made_grads = core_and_gradients(backbone._grouped_core, cfg, b, t, window)
    if rows:
        assert attrs["swa_attn_blocks"] == t // rows and attrs["swa_attn_unrolled"] == 0
        assert backbone._window_rows(t, window, b * 8) == rows
    else:
        assert "swa_attn_unrolled" not in attrs and "swa_attn_pairs_in_window" not in attrs
    ref, ref_grads = core_and_gradients(whole_square_core, cfg, b, t, window)
    assert relative(made, ref) < 1e-6
    for g, ref_g in zip(made_grads, ref_grads):
        assert float(jnp.abs(ref_g).max()) > 0 and relative(g, ref_g) < 1e-5


def test_a_leading_trips_later_rows_carry_no_weight_and_no_gradient(trips):
    """A leading trip's slice of keys and values is clamped to start at row
    0, so it holds rows after the trip's own: they weigh nothing in its rows'
    softmax and take no gradient from them, whatever they hold."""
    cfg = backbone.afmoe(F, F, compute_dtype="float32", **TINY).cfg
    t, window, rows = 4 * BLOCK, BLOCK, BLOCK       # a trip reads 16 rows: trip 0 holds rows 8..15
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(key, (2, t, heads, 16))
               for key, heads in zip(keys, (8, 2, 2)))
    assert backbone._window_rows(t, window, 16) == rows
    first = jax.jit(lambda q, k, v: backbone._grouped_core(cfg, q, k, v, window, "swa")[:, :rows])
    out, vjp = jax.vjp(first, q, k, v)
    dq, dk, dv = vjp(jnp.ones_like(out))
    assert float(jnp.abs(dk[:, :rows]).max()) > 0 and float(jnp.abs(dv[:, :rows]).max()) > 0
    assert float(jnp.abs(dk[:, rows:]).max()) == 0 and float(jnp.abs(dv[:, rows:]).max()) == 0
    assert float(jnp.abs(dq[:, rows:]).max()) == 0
    loud = first(q, k.at[:, rows:].set(1e4), v.at[:, rows:].set(-1e4))
    assert np.array_equal(np.asarray(loud), np.asarray(out))
    # and the first row's softmax is itself alone
    np.testing.assert_allclose(out[:, 0].reshape(2, 2, 4, 16),
                               jnp.broadcast_to(v[:, 0, :, None], (2, 2, 4, 16)), rtol=1e-6)


@pytest.mark.parametrize("t,window,rule,rows,computed", [
    (4 * BLOCK, 12, "window_blocks", 4, 8 * 4),
    (4 * BLOCK, 2 * BLOCK, "window_blocks", 2 * BLOCK, 2 * 2),
    (4 * BLOCK, 3, "window_blocks", BLOCK, 4 * 2),
    (8 * BLOCK, 12, "window_blocks", 4, 16 * 4),
    (2 * BLOCK, 5 * BLOCK, "causal_blocks", BLOCK, 1 + 2),
    (BLOCK + 4, 3, "whole", BLOCK + 4, 1),
])
def test_the_windowed_core_is_counted_by_the_one_block_rule(
        t, window, rule, rows, computed, trips):
    """Every block of a windowed core is a trip of ONE traced body however
    many there are: none is unrolled, none is a shape of its own.  A window
    that covers the sequence is no window."""
    cfg, p, h = mixer_inputs("swa", "float32", t, attn_window=window)
    counted = telemetry.REGISTRY.get("gordo_gqa_attention_total")
    rules = ("causal_blocks", "window_blocks", "whole")
    before = {r: counted.value(r) for r in rules}
    with telemetry.span("gordo.test.trace") as attrs:
        text = jax.jit(lambda p, h: backbone.MIXERS["swa"](cfg, p, h)).lower(p, h).as_text()
    assert {r: counted.value(r) - before[r] for r in rules} == {
        r: float(r == rule) for r in rules}
    n = t // rows
    assert attrs["swa_attn_traces"] == 1 and attrs["swa_attn_blocks"] == n
    assert attrs["swa_attn_pairs_computed"] == computed
    assert attrs["swa_attn_pairs_square"] == n * n
    assert "gqa_attn_traces" not in attrs
    windowed = window < t
    if windowed:
        assert attrs["swa_attn_unrolled"] == 0
        assert attrs["swa_attn_pairs_in_window"] == pytest.approx(
            (t * window - window * (window - 1) / 2) / rows ** 2)
        assert attrs["swa_attn_pairs_in_window"] <= computed
    else:
        assert "swa_attn_unrolled" not in attrs and "swa_attn_pairs_in_window" not in attrs
    assert text.count("stablehlo.while") == int(windowed)
    # two key/value heads stay two in every product: one trip's two products
    # and one softmax whatever the trips, as many as blocks where they are
    # unrolled
    q, k, v = (jnp.zeros((2, t, heads, 16)) for heads in (8, 2, 2))
    core = lambda q, k, v: backbone._grouped_core(cfg, q, k, v, window, "swa")  # noqa: E731
    assert str(jax.make_jaxpr(core)(q, k, v)).count("dot_general") == (
        2 if windowed else 2 * n)
    assert jax.jit(core).lower(q, k, v).as_text().count("stablehlo.exponential") == (
        1 if windowed else n)


def test_at_the_cells_shape_less_than_half_the_square_is_computed():
    """8,192 rows under a window of 2,048, 32 query heads, one sequence:
    32 trips of 256 rows, each against 2,304 keys: 288 of 1,024 block pairs
    for the 224 that window and mask hold, none outside the loop."""
    latent = telemetry.REGISTRY.get("gordo_mla_attention_total")
    with telemetry.span("gordo.test.trace") as attrs:
        spans = backbone._query_blocks(8192, latent, "swa", 2048, 32)
    rows = backbone._window_rows(8192, 2048, 32)
    assert rows == 256 and spans == [(lo, lo + rows) for lo in range(0, 8192, rows)]
    assert 4 * 32 * rows * (2048 + rows) <= backbone.WINDOW_TRIP_BYTES < 4 * 32 * 512 * 2560
    assert attrs["swa_attn_blocks"] == 32 and attrs["swa_attn_unrolled"] == 0
    assert attrs["swa_attn_pairs_computed"] == 32 * 9 == 288
    assert attrs["swa_attn_pairs_square"] == 1024 and 288 < 1024 / 2
    assert attrs["swa_attn_pairs_in_window"] == pytest.approx(
        (8192 * 2048 - 2048 * 2047 / 2) / rows ** 2) == pytest.approx(224.0, abs=0.02)
    assert attrs["swa_attn_pairs_computed"] / attrs["swa_attn_pairs_in_window"] < 1.29
    # what a build's tracing span says of it, and of the three older presets
    module = backbone.afmoe(50, 50)
    assert module.window_trips(1, 8192) == {"swa_attn_rows": rows}
    assert module.window_trips(1, 2048) == {}       # the window covers the sequence
    for older in (backbone.kimi_linear, backbone.glm_moe_lite, backbone.lfm2_moe):
        assert older(50, 50).window_trips(1, 8192) == {}


@pytest.mark.parametrize("lanes,rows", [
    (32, 256), (64, 128), (128, 64), (1024, 64), (8, 512)])
def test_a_trips_rows_follow_the_batch_and_the_heads(lanes, rows):
    """More sequences or more heads a step: fewer rows a trip, down to the
    smallest candidate; fewer: the largest."""
    assert backbone._window_rows(8192, 2048, lanes) == rows
    assert rows in backbone.WINDOW_ROWS


@pytest.mark.parametrize("t,prefix,spans", [
    (2048, "mla", 4 * [512]),       # the GLM cell's latent cores
    (1024, "mla", 2 * [512]),       # the Kimi cell's
    (2048, "gqa", 4 * [512]),       # the LFM2 cell's grouped ones
    (8192, "gqa", 8 * [1024]),      # the Trinity cell's full layer
])
def test_the_cores_with_no_window_take_the_spans_they_took(t, prefix, spans):
    latent = telemetry.REGISTRY.get("gordo_mla_attention_total")
    with telemetry.span("gordo.test.trace") as attrs:
        made = backbone._query_blocks(t, latent, prefix)
    assert [hi - lo for lo, hi in made] == spans and made[0][0] == 0 and made[-1][1] == t
    n = len(spans)
    assert attrs[f"{prefix}_attn_pairs_computed"] == n * (n + 1) // 2
    assert attrs[f"{prefix}_attn_pairs_square"] == n * n
    assert not {f"{prefix}_attn_unrolled", f"{prefix}_attn_pairs_in_window"} & set(attrs)
    if prefix == "gqa":
        # what the whole-prefix core would keep for its backward pass, and may not
        kept = 4 * 32 * sum((hi - lo) * hi for lo, hi in made)
        assert (kept > backbone.ATTN_KEEP_BYTES) == (t == 8192)


@pytest.mark.parametrize("core", ["latent", "grouped"])
def test_the_cores_with_no_window_do_not_read_the_trips_budget(core, monkeypatch):
    """The jaxpr of ``_causal_core`` and of ``_grouped_core(window=0)`` is
    the same text whatever the windowed rule's constants are."""
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    t = 4 * BLOCK
    if core == "latent":
        cfg = backbone.kimi_linear(
            F, F, compute_dtype="float32", hidden_size=64, num_heads=2, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_layers=4).cfg
        shapes = [(2, t, 2, 24), (2, t, 2, 16), (2, t, 8), (2, t, 2, 16)]
        traced = lambda *a: jax.vjp(lambda *a: backbone._causal_core(cfg, *a), *a)  # noqa: E731
    else:
        cfg = backbone.afmoe(F, F, compute_dtype="float32", **TINY).cfg
        shapes = [(2, t, 8, 16), (2, t, 2, 16), (2, t, 2, 16)]
        traced = lambda *a: jax.vjp(lambda *a: backbone._grouped_core(cfg, *a), *a)  # noqa: E731
    texts = []
    for budget, rows in ((0, (4,)), (1 << 40, (16, 8))):
        monkeypatch.setattr(backbone, "WINDOW_TRIP_BYTES", budget)
        monkeypatch.setattr(backbone, "WINDOW_ROWS", rows)
        texts.append(str(jax.make_jaxpr(traced)(*(jnp.zeros(s) for s in shapes))))
    assert texts[0] == texts[1] and texts[0].count("dot_general") >= 8


def test_a_latent_core_past_the_most_blocks_takes_longer_ones(monkeypatch):
    """``ATTN_MAX_BLOCKS`` holds for every causal core: a latent one of
    sixteen blocks' rows runs in eight blocks of two, counted so, and is the
    one-block core's (the whole masked square's) arithmetic."""
    cfg = backbone.kimi_linear(
        F, F, compute_dtype="float32", hidden_size=64, num_heads=2, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_layers=4).cfg
    t = 16 * BLOCK
    q, k_n, k_r, v = (jax.random.normal(jax.random.PRNGKey(i), shape) for i, shape in enumerate(
        [(2, t, 2, 24), (2, t, 2, 16), (2, t, 8), (2, t, 2, 16)]))
    core = lambda: jax.jit(lambda *a: jax.vjp(  # noqa: E731
        lambda *a: backbone._causal_core(cfg, *a), *a))(q, k_n, k_r, v)
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    with telemetry.span("gordo.test.trace") as attrs:
        made, made_vjp = core()
    assert attrs["mla_attn_blocks"] == backbone.ATTN_MAX_BLOCKS == 8
    assert attrs["mla_attn_pairs_computed"] == 36 and attrs["mla_attn_pairs_square"] == 64
    monkeypatch.setattr(backbone, "MLA_BLOCK", t)
    whole, whole_vjp = core()
    assert relative(made, whole) < 1e-6
    ct = jax.random.normal(jax.random.PRNGKey(9), made.shape)
    for g, ref in zip(made_vjp(ct), whole_vjp(ct)):
        assert float(jnp.abs(ref).max()) > 0 and relative(g, ref) < 1e-5


@pytest.mark.parametrize("kind_", ["swa", "gqa"])
def test_no_row_sees_a_later_one_nor_one_a_window_back(kind_, monkeypatch):
    """Moving row ``r`` moves rows ``r .. r + W - 1`` of a windowed layer and
    every row from ``r`` on of a full one, and no other: also across a block
    boundary and across the loop's first trip."""
    cfg, p, h = mixer_inputs(kind_, "float32", 4 * BLOCK)
    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    mixer = jax.jit(lambda h: backbone.MIXERS[kind_](cfg, p, h))
    out = mixer(h)
    for row in (0, BLOCK - 1, BLOCK, 2 * BLOCK + 3):
        moved = mixer(h.at[:, row].add(1.0))
        changed = np.flatnonzero(np.abs(np.asarray(moved - out)).max(axis=(0, 2)) > 1e-7)
        last = min(row + WINDOW, 4 * BLOCK) if kind_ == "swa" else 4 * BLOCK
        assert list(changed) == list(range(row, last)), (row, changed)


def test_the_full_layer_knows_no_position_and_the_windowed_one_the_distance_alone():
    """Without rotation the last row's output is a function of the SET of
    rows before it; with it, a rotated score depends on the distance alone."""
    cfg, p, h = mixer_inputs("gqa", "float32", T)
    out = backbone.gqa_mixer(cfg, p, h)
    order = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(3), T - 1),
                             jnp.asarray([T - 1])])
    np.testing.assert_allclose(backbone.gqa_mixer(cfg, p, h[:, order])[:, -1], out[:, -1],
                               atol=1e-5)
    # the windowed layer is rotated: the same rows in another order are another output
    cfg_w, p_w, _ = mixer_inputs("swa", "float32", T, attn_window=T)
    swa = backbone.MIXERS["swa"]
    assert relative(swa(cfg_w, p_w, h[:, order])[:, -1], swa(cfg_w, p_w, h)[:, -1]) > 1e-3
    width = cfg.gqa_head_dim
    q, k = jax.random.normal(jax.random.PRNGKey(4), (2, width))
    cos, sin = backbone.rotary(T, width, cfg.rope_theta)
    rows = lambda v: backbone.rotate(jnp.broadcast_to(v, (T, width)), cos, sin)  # noqa: E731
    scores = rows(q) @ rows(k).T                        # (t, s)
    for shift in (1, 7):
        np.testing.assert_allclose(scores[shift:, shift:], scores[:-shift, :-shift],
                                   atol=1e-5)
    assert float(jnp.abs(scores[5, 0] - scores[0, 0])) > 1e-3   # and on nothing less
    np.testing.assert_allclose(rows(q), reference.rope(
        jnp.broadcast_to(q, (1, T, 1, width)), cfg.rope_theta)[0, :, 0], atol=1e-6)


@pytest.mark.parametrize("kind_", ["swa", "gqa"])
def test_the_gate_the_groups_and_the_reference_mixer(kind_, blocks):
    """The heads' outputs times ``sigmoid(x W_z)`` before ``W_o``: a gate
    matrix of zeros halves them.  Head ``i`` reads key/value head ``i // 4``:
    moving key/value head 1's value columns moves query heads 4-7 alone."""
    cfg, p, h = mixer_inputs(kind_, "float32", T)
    a = dict(shape_of())
    mixer = backbone.MIXERS[kind_]
    ref = reference._attention(a, kind_, p, h, None)
    np.testing.assert_allclose(mixer(cfg, p, h), ref, atol=1e-5)
    ungated = reference._attention(a, kind_, p, h, None, "no_gate")
    halved = mixer(cfg, {**p, kind_ + "_wz": jnp.zeros_like(p[kind_ + "_wz"])}, h)
    np.testing.assert_allclose(halved, 0.5 * ungated, atol=1e-5)
    assert relative(ungated, ref) > 1e-2
    wide = 8 * 16
    eye = {**p, kind_ + "_wo": jnp.eye(wide), kind_ + "_wz": jnp.zeros((64, wide))}
    out = mixer(cfg, eye, h)                        # the heads' outputs, side by side
    moved = mixer(cfg, {**eye, kind_ + "_wv": eye[kind_ + "_wv"].at[:, 16:].add(0.5)}, h)
    changed = np.abs(np.asarray(moved - out)).max(axis=(0, 1)).reshape(8, 16).max(axis=1) > 1e-6
    assert list(changed) == [False] * 4 + [True] * 4
    for fault in ("wrong_group", "no_qk_norm"):
        assert relative(reference._attention(a, kind_, p, h, None, fault), ref) > 1e-2, fault
    # each kind's own fault moves that kind and leaves the other alone
    own, other = ("no_window", "rotated_full") if kind_ == "swa" else ("rotated_full", "no_window")
    assert relative(reference._attention(a, kind_, p, h, None, own), ref) > 1e-2
    np.testing.assert_array_equal(reference._attention(a, kind_, p, h, None, other), ref)


def test_every_parts_output_is_normalised_before_it_joins_the_stream(x, blocks):
    """The four norms of a layer: a part's output has a root mean square of
    its own norm's weight whatever the part computed."""
    module, shape = module_of(), shape_of()
    params, ref_params = start(module, shape)
    cfg = module.cfg
    h = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))
    rms = lambda y: jnp.sqrt(jnp.mean(y * y, axis=-1))  # noqa: E731
    layer = lambda kind_, slot: {  # noqa: E731
        n: v[slot] for n, v in params.items() if n.startswith(kind_ + "_")}
    for kind_ in ("swa", "gqa"):
        y = backbone._mixer_of(cfg, kind_, layer(kind_, 0), params["mixer_norm"][0], h)
        np.testing.assert_allclose(rms(y), 1.0, atol=1e-3)
        scaled = {**layer(kind_, 0), kind_ + "_post_norm": jnp.full((64,), 3.0)}
        np.testing.assert_allclose(
            backbone._mixer_of(cfg, kind_, scaled, params["mixer_norm"][0], h), 3.0 * y, rtol=1e-5)
    y = backbone._dense_ffn(cfg, layer("dense", 0), params["ffn_norm"][0], h)
    np.testing.assert_allclose(rms(y), 1.0, atol=1e-3)
    y, _ = backbone._expert_ffn(cfg, layer("moe", 0), params["ffn_norm"][1], h)
    np.testing.assert_allclose(rms(y), 1.0, atol=1e-3)
    assert relative(reference.forward(ref_params, x, shape, fault="no_post_norm"),
                    reference.forward(ref_params, x, shape)) > 0.05


# -- 3. the share --------------------------------------------------------------------

def expert_parameters(cfg, key):
    specs = [(n, s[1:], i) for n, s, i in backbone.param_specs(cfg)
             if n.startswith("moe_") and n != "moe_post_norm"]
    keys = jax.random.split(key, len(specs))
    return {n: backbone._initializer(i)(k, s) for (n, s, i), k in zip(specs, keys)}


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_of_every_chip_add_up_to_the_uncut_layer(held):
    """Every range of ``held`` experts (8, 4 and 2 chips sharing the layer,
    as 16 and 8 share the published one): the routed parts, the shared expert
    counted once, add up to what the uncut reference gives for the layer."""
    whole = module_of(experts_held=8).cfg
    p = expert_parameters(whole, jax.random.PRNGKey(6))
    assert {"moe_router", "moe_shared_wg", "moe_wg", "moe_wu", "moe_wd"} <= set(p)
    xs = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    a = dict(shape_of(experts_held=8))
    ref = reference._experts(a, p, xs, None)
    shared = backbone.swiglu(xs, p["moe_shared_wg"], p["moe_shared_wu"], p["moe_shared_wd"],
                             jnp.float32)
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
    # the selected weights add up to the source's scale
    _, weights = backbone.route(whole, p["moe_router"], xs)
    experts, ref_weights = reference.routing(a, p["moe_router"], xs)
    np.testing.assert_allclose(weights, ref_weights, atol=1e-6)
    assert float(jnp.abs(weights.sum(-1) - 2.826).max()) < 1e-5


def test_counts_name_the_four_expert_layers_by_their_numbers(x):
    module = module_of()
    params, _ = start(module, shape_of())
    _, counts = module.apply({"params": params}, x, counts=True)
    assert counts["tokens"].shape == (4, 2) and module.cfg.moe_labels == ("2", "3", "4", "5")
    assert int(counts["selected"]) == 3 * T * 2 * 4
    assert int(counts["held"]) == int(counts["tokens"].sum()) <= int(counts["selected"])
    assert int(counts["blocks_full"]) == 4 and 0 < int(counts["blocks_run"]) <= 4
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
        "model": {"kind": "afmoe", "epochs": 1, "learning_rate": 0.001,
                  "compute_dtype": "auto", "experts_held_from": 0, **model},
        "cv": {"splitter": "TimeSeriesSplit", "n_splits": 3},
        "dataset": {"type": "RandomDataset", "resolution": "10min", "n_tags": F,
                    "train_start_date": "2017-01-01T00:00:00+00:00",
                    "train_end_date": "2017-01-02T12:00:00+00:00", "rows": 217},
    }


def tiny_config():
    return config_of(context=T, stride=16, batch_size=2, mixer_group=1, **TINY)


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
    block is 8 rows and no candidate of ``WINDOW_ROWS`` divides the window, so
    a sequence is four trips of the windowed layers' loop, 8 rows each."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config = tiny_config()
    out = str(tmp_path_factory.mktemp("afmoe-project"))
    machines = NormalizedConfig(kind.project_doc(config, SEED, 2), "afmoe-test").machines
    patch = pytest.MonkeyPatch()
    patch.setattr(backbone, "MLA_BLOCK", BLOCK)
    patch.setattr(reference, "QUERY_ROWS", 2 * BLOCK)
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
    assert delta("gordo_moe_row_blocks_total", "run") > 0
    labels = {tuple(json.loads(k)) for k in after["gordo_moe_tokens_total"]["series"]}
    assert {(layer, e) for layer in ("2", "3", "4", "5") for e in ("0", "1")} <= labels
    # what the program is made of: wherever a mixer was traced, layer 1's
    # windowed one alone and then the scan body's two kinds
    windowed, full = delta("gordo_backbone_mixers_total", "swa"), delta(
        "gordo_backbone_mixers_total", "gqa")
    assert 0 < full < windowed <= 2 * full
    for absent in ("kda", "mla", "conv"):
        assert delta("gordo_backbone_mixers_total", absent) == 0
    assert delta("gordo_gqa_attention_total", "window_blocks") == windowed
    assert delta("gordo_gqa_attention_total", "causal_blocks") == full
    assert delta("gordo_gqa_attention_total", "whole") == 0
    assert delta("gordo_mla_attention_total") == 0
    counts = result.timeline[0]["counts"]["enqueue"]
    assert counts["swa_attn_traces"] == windowed and counts["swa_attn_blocks"] == 4 * windowed
    # four trips, each against the two blocks before its own and its own
    assert counts["swa_attn_pairs_computed"] == 4 * 3 * windowed
    assert counts["swa_attn_unrolled"] == 0 and counts["swa_attn_rows"] == BLOCK
    assert counts["swa_attn_pairs_square"] == 16 * windowed
    assert counts["swa_attn_pairs_in_window"] == pytest.approx(
        windowed * (T * WINDOW - WINDOW * (WINDOW - 1) / 2) / BLOCK ** 2)
    assert counts["gqa_attn_traces"] == full and counts["gqa_attn_pairs_computed"] == 10 * full
    assert counts["layers_swa"] == 4 and counts["layers_gqa"] == 1
    assert counts["attn_window"] == WINDOW
    assert "layers_conv" not in counts and "mtp_depth" not in counts
    assert counts["context"] == T and counts["experts_held"] == 2
    assert counts["moe_block_rows"] == 2 * T * 2 and counts["moe_blocks_full"] == 4
    assert counts["params"] == module_of().param_count()
    (snapshot,) = telemetry.load_snapshot_dir(os.path.join(out, telemetry.SNAPSHOT_DIR))
    assert "window_blocks" in json.dumps(snapshot)
    _, refs = artifacts.discover(out)
    meta = refs[0].load_metadata()["model"]
    moe = meta["cross_validation"]["moe"]
    assert np.asarray(moe["tokens_per_held_expert"]).shape == (4, 2)
    assert "loss_terms" not in meta["cross_validation"]
    assert "multi_token_prediction" not in json.dumps(meta)


def test_the_artifact_scores_and_predicts_as_the_reference_forecasts(built, blocks):
    from gordo_tpu import artifacts

    config, out, _, _, _ = built
    _, refs = artifacts.discover(out)
    by_name = {ref.name: ref for ref in refs}
    name = kind.machine_names(SEED, 2)[1]
    detector = by_name[name].load_model()
    estimator = detector.base_estimator.steps[-1][1]
    assert isinstance(estimator, SequenceForecast) and estimator.kind == "afmoe"
    assert "multi_token_prediction" not in estimator.get_metadata()
    assert {"swa_wz", "gqa_wz", "swa_post_norm", "dense_post_norm", "moe_post_norm",
            "moe_shared_wg"} <= set(estimator.params_)
    assert not [n for n in estimator.params_ if n.startswith(("mtp_", "conv_", "mla_"))]
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
    with pytest.raises(SequenceModelUnsupported, match="FleetScorer.*SequenceForecast.*afmoe"):
        FleetScorer.from_models(models)
    scorer = CompiledScorer(models[name], machine=name)
    assert not scorer.fused        # falls back to the detector's own anomaly()
    with pytest.raises(SequenceModelUnsupported, match="MachineStream.*SequenceForecast.*afmoe"):
        MachineStream(name, scorer)
    with pytest.raises(SequenceModelUnsupported, match="backfill.*SequenceForecast.*afmoe"):
        refuse_sequence_model(models[name], name, "the backfill runner")


@pytest.fixture(scope="module")
def sound_fit():
    config = tiny_config()
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])
    return config, rows, reference_of(config, rows, folds=False)


@pytest.mark.parametrize("fault", ["float8", "half_batch", "frozen_leaf",
                                   *reference.FORWARD_FAULTS])
def test_a_planted_fault_reads_far_above_what_a_sound_build_may(fault, sound_fit):
    """The faults a comparison with this reference has to catch, planted in
    the reference's own fit: float8 operands, half of every minibatch left
    out, a matrix left at its start, and one per mechanism (the window, the
    full layer's lack of a position, the rotation, the gate, the output
    norms, the heads' norms, the grouping).  Each reads above what the build
    above is held to."""
    config, rows, ref = sound_fit
    seed = kind.model_seed(SEED)
    if fault == "frozen_leaf":
        low = {**ref, "model": reference.freeze(ref["model"], seed, ref["shape"], 0, "swa_wo")}
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


def test_half_of_a_minibatch_of_one_sequence_is_its_later_rows():
    config = tiny_config()
    rows = kind.reference_rows(config, kind.machine_names(SEED, 1)[0])[:60]
    model = {**config["model"], "batch_size": 1}
    sound = reference.fit(rows, model, kind.model_seed(SEED))
    half = reference.fit(rows, model, kind.model_seed(SEED), fault="half_batch")
    assert np.isfinite(half["history"]).all() and half["history"][0] > 0
    assert abs(half["history"][0] - sound["history"][0]) > 1e-6


def test_a_model_of_the_published_widths_is_a_chunk_of_one():
    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import _parameter_count, default_bucket_size
    from gordo_tpu.parallel.anomaly import analyze_definition

    # the source's layers 1-5, experts 0-7 of 128: the widths are the preset's
    config = config_of(context=8192, stride=4096, batch_size=1, num_layers=5)
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    assert _parameter_count(spec, (50, 50)) == 401853746
    assert default_bucket_size(spec, (50, 50)) == 1


def test_the_lowered_program_names_the_scopes_the_metrics_read(monkeypatch):
    """Forward, recomputation and backward all carry the scopes; the four
    expert layers are one scan whose body chooses between its two kinds."""
    from gordo_tpu import serializer
    from gordo_tpu.parallel.anomaly import FleetDiffBuilder, analyze_definition

    monkeypatch.setattr(backbone, "MLA_BLOCK", BLOCK)
    compile_plane.REGISTRY.clear()
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
    compile_plane.REGISTRY.clear()
    # the folds' "forecast or not", and the scan body's choice between the
    # windowed and the full layer: forward (a fit's and a forecast's), the
    # backward pass's recomputation
    assert lowered.as_text().count("stablehlo.case") == 1 + 3
    named = lowered.as_text(debug_info=True)
    for scope in ("backbone.swa/backbone.swa.attn/", "backbone.gqa/backbone.gqa.attn/",
                  "backbone.moe.experts/", "backbone.moe.route/", "backbone.ffn/",
                  "backbone.norm/", "jvp(backbone.swa)/backbone.swa.attn/",
                  "jvp(backbone.gqa)/backbone.gqa.attn/",
                  "transpose(jvp(backbone.swa))/backbone.swa.attn/",
                  "transpose(jvp(backbone.gqa))/backbone.gqa.attn/"):
        assert scope in named, scope
    # the gate lies under its mixer's scope and outside the core's
    assert "backbone.swa/logistic" in named and "backbone.swa.attn/logistic" not in named
    for absent in ("backbone.kda", "backbone.mla", "backbone.mtp", "backbone.conv"):
        assert absent not in named, absent

"""The ``kimi_linear`` backbone (``models/factories/backbone.py``) against
its plain reference (``benchmark/reference/kimi_linear.py``) at a tiny
preset: hidden 64, 2 heads of 16, 8 experts of which 2 held, sequences of
32 rows, KDA chunks of 8.  Float32 on the CPU, so agreement is tight; a
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

from benchmark.kinds import sequence_build as kind  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402
from gordo_tpu import telemetry  # noqa: E402
from gordo_tpu.models.estimator import LSTMForecast, SequenceForecast  # noqa: E402
from gordo_tpu.models.factories import backbone  # noqa: E402
from gordo_tpu.ops.windows import (  # noqa: E402
    make_sequences, make_windows, num_sequences, sequences_to_rows,
)

TINY = dict(hidden_size=64, num_heads=2, kda_head_dim=16, kda_gate_rank=16,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, experts_held=2, experts_held_from=0,
            num_layers=2, full_attn_every=2)
F = 5
SEED = 11
# float32 against float32 on the CPU: the two fits' changes from the common
# start are 1e-4 of a change apart (measured here)
UPDATE_GAP = 3e-3


def module_of(**over):
    return backbone.kimi_linear(F, F, compute_dtype="float32", kda_chunk=8,
                                **{**TINY, **over})


def shape_of(**over):
    return reference.shape_of({"kind": "kimi_linear", **TINY, **over}, F, F)


def start(module, shape):
    """The program's and the reference's initial weights from one seed."""
    init_key, _ = jax.random.split(jax.random.PRNGKey(SEED))
    params = module.init(init_key, jnp.zeros((1, 32, F)))["params"]
    ref_params, _ = reference.init_params(SEED, shape)
    return params, ref_params


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (3, 32, F))


# -- 1. forward --------------------------------------------------------------

def test_forward_matches_the_reference_and_a_bfloat16_control_does_not(x):
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


def test_the_module_says_it_has_no_packed_layout():
    from gordo_tpu.train.fit import TrainConfig, packed_layout

    assert packed_layout(module_of(), TrainConfig()) is False
    assert module_of().fleet_axis == "map"
    assert module_of().param_count() == sum(
        int(np.prod(s)) for _, s, _ in backbone.param_specs(module_of().cfg))


# -- 2. the chunked delta rule -------------------------------------------------

def test_chunked_kda_matches_the_recurrence_across_chunks_and_decays():
    """Four chunks of 8; channels that forget almost everything in a step
    (alpha = e^-12) beside channels that forget almost nothing."""
    b, h, t, dk = 2, 2, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k, v = (jax.random.normal(key, (b, t, h, dk)) for key in keys[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(keys[3], (b, t, h, dk), minval=-9.0, maxval=0.5))
    g = g.at[..., :4].set(-12.0).at[..., 4:8].set(-1e-4)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    ref = reference.kda_recurrence(q, k, v, jnp.exp(g), beta)
    to_heads = lambda a: jnp.moveaxis(a, 2, 1)  # noqa: E731
    made = backbone.kda_chunked(*(to_heads(a) for a in (q, k, v, g, beta)),
                                chunk=8, cd=jnp.float32)
    np.testing.assert_allclose(jnp.moveaxis(made, 1, 2), ref, atol=2e-6)
    assert float(jnp.abs(ref).max()) > 0.05


def substitution(A, rhs):
    """The reference of the within-chunk solve: ``solve_triangular``'s row by
    row substitution, which the package no longer calls."""
    n = A.shape[-1]
    return jax.scipy.linalg.solve_triangular(
        jnp.tril(A, -1) + jnp.eye(n, dtype=A.dtype), rhs, lower=True,
        unit_diagonal=True)


@pytest.mark.parametrize("n,beta", [
    (1, None), (2, None), (8, None), (24, None), (64, None), (64, 0.5), (64, 0.99)])
def test_the_block_inverse_solves_what_substitution_solves(n, beta):
    """Value and both gradients, float32.  ``beta`` times strictly-lower ones
    is what smooth sensor rows drive ``A`` towards: its powers reach 1e17
    while the inverse's entries stay under 1, so a series for the inverse
    cancels to nothing there and only a substitution-like method passes."""
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    if beta is None:
        A = 0.5 * jax.random.normal(keys[0], (3, 2, n, n))    # upper part unread
        width = 5
    else:
        A = beta * jnp.tril(jnp.ones((n, n)), -1)
        width = 256
    rhs = jax.random.normal(keys[1], A.shape[:-1] + (width,))
    ct = jax.random.normal(keys[2], rhs.shape)
    made, made_vjp = jax.vjp(backbone._unit_lower_solve, A, rhs)
    ref, ref_vjp = jax.vjp(substitution, A, rhs)
    assert made.dtype == jnp.float32
    for name, a, b in zip(("U", "dA", "d_rhs"), (made,) + made_vjp(ct),
                          (ref,) + ref_vjp(ct)):
        scale = float(jnp.abs(b).max()) or 1.0     # n = 1: dA is all zeros
        assert float(jnp.abs(a - b).max()) <= 1e-5 * scale, name


def leaf_equations(jaxpr, enclosing=""):
    """``(primitive, name stack)`` of every equation that holds no jaxpr of
    its own, the stacks of the equations around it in front."""
    for eqn in jaxpr.eqns:
        stack = enclosing + "/" + str(eqn.source_info.name_stack)
        inner = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                 if hasattr(getattr(v, "jaxpr", v), "eqns")]
        for sub in inner:
            yield from leaf_equations(sub, stack)
        if not inner:
            yield eqn.primitive.name, stack


def test_every_operation_of_the_solve_stays_in_the_scan_scope():
    """Forward and gradient: ``seq.kda_s_per_step`` and
    ``seq.kda_scan_roofline`` read the operations whose name holds
    ``backbone.kda.scan``, so one that slipped out would flatter them."""
    A, rhs = jnp.zeros((2, 24, 24)), jnp.zeros((2, 24, 3))

    def both(A, rhs, ct):
        out, vjp = jax.vjp(backbone._unit_lower_solve, A, rhs)
        return (out,) + vjp(ct)

    # 24 rows are padded to 32: five levels of two products each, then
    # ``T rhs``; the gradient adds its two matmuls
    for fn, args, matmuls in ((backbone._unit_lower_solve, (A, rhs), 1),
                              (both, (A, rhs, rhs), 3)):
        found = list(leaf_equations(jax.make_jaxpr(fn)(*args).jaxpr))
        names = [name for name, _ in found]
        assert names.count("reduce_sum") == 10 and names.count("dot_general") == matmuls
        assert [(n, s) for n, s in found if "backbone.kda.scan.solve" not in s] == []


def test_gradient_of_every_kda_parameter_matches_the_references(x):
    module, shape = module_of(num_layers=1), shape_of(num_layers=1)
    params, ref_params = start(module, shape)
    made = jax.grad(lambda p: jnp.mean(module.apply({"params": p}, x) ** 2))(params)
    ref = jax.grad(lambda p: jnp.mean(reference.forward(p, x, shape) ** 2))(ref_params)
    kda = [name for name in params if name.startswith("kda_")]
    assert len(kda) == 15
    for name in kda:
        scale = float(jnp.abs(ref[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(made[name], ref[name], atol=2e-4 * scale,
                                   err_msg=name)


LAYERED = dict(num_layers=6, full_attn_every=3, first_k_dense_replace=2)


def test_every_gradient_of_a_layered_model_matches_the_references(x):
    """Six layers, two of them dense and two MLA: the leading dense layers
    traced one by one, the four expert layers as one scan whose body takes
    its layer's mixer out of the stacks and chooses it by ``lax.cond``, the
    mixers' backward written out.  Every parameter's gradient, layer by
    layer of every stack, against autodiff through the plain reference."""
    module, shape = module_of(**LAYERED), shape_of(**LAYERED)
    cfg = module.cfg
    assert [cfg.mixer(l) for l in range(1, 7)] == ["kda", "kda", "mla", "kda", "kda", "mla"]
    assert cfg.layers_of("dense") == (1, 2) and cfg.moe_layers == (3, 4, 5, 6)
    params, ref_params = start(module, shape)
    assert {name: value.shape for name, value in params.items()} == {
        name: value.shape for name, value in ref_params.items()}
    assert params["kda_wq"].shape[0] == 4 and params["mla_wq"].shape[0] == 2
    assert params["dense_wg"].shape[0] == 2 and params["moe_wg"].shape[:2] == (4, 2)
    np.testing.assert_allclose(
        module.apply({"params": params}, x), reference.forward(ref_params, x, shape),
        atol=2e-5)
    made = jax.jit(jax.grad(lambda p: jnp.mean(module.apply({"params": p}, x) ** 2)))(params)
    ref = jax.grad(lambda p: jnp.mean(reference.forward(p, x, shape) ** 2))(ref_params)
    for name in params:
        for slot in range(params[name].shape[0] if params[name].ndim > 1 else 1):
            m, r = (made[name][slot], ref[name][slot]) if params[name].ndim > 1 else (
                made[name], ref[name])
            scale = float(jnp.abs(ref[name]).max())
            assert scale > 0, name
            np.testing.assert_allclose(m, r, atol=5e-4 * scale, err_msg=f"{name}[{slot}]")


# -- 3. the shares add up ------------------------------------------------------

#: the routed part's two rules, by ``BackboneConfig.moe_row_blocks``: the
#: kind's own preset keeps the second (``backbone.KIMI_LINEAR``)
RULES = {"row_blocks": True, "worst_case_buffer": False}


def layer_params(key, cfg):
    """One expert layer's parameters: a slice of the stack's shapes."""
    specs = [(n, s[1:], i) for n, s, i in backbone.param_specs(cfg) if n.startswith("moe_")]
    keys = jax.random.split(key, len(specs))
    return {n: backbone._initializer(i)(k, s) for (n, s, i), k in zip(specs, keys)}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_the_four_shares_of_two_experts_add_up_to_the_uncut_layer(rule):
    whole = module_of(experts_held=8).cfg
    p = layer_params(jax.random.PRNGKey(5), whole)
    xs = jax.random.normal(jax.random.PRNGKey(6), (40, whole.hidden_size))
    shared = backbone.swiglu(xs, p["moe_shared_wg"], p["moe_shared_wu"],
                             p["moe_shared_wd"], jnp.float32)
    total, tokens = shared, []
    for first in (0, 2, 4, 6):
        cfg = module_of(experts_held=2, experts_held_from=first,
                        moe_row_blocks=RULES[rule]).cfg
        mine = {**p, **{n: p[n][first:first + 2] for n in ("moe_wg", "moe_wu", "moe_wd")}}
        y, counted = backbone.expert_layer(cfg, mine, xs)
        total = total + (y - shared)       # the shared expert counted once
        tokens.append(counted)
    ref = reference._experts(dict(shape_of(experts_held=8)), p, xs, None)
    np.testing.assert_allclose(total, ref, atol=1e-5)
    # every selected pair fell on exactly one share
    assert int(jnp.sum(jnp.stack(tokens))) == 40 * whole.num_experts_per_token


# -- 4. nothing is dropped -------------------------------------------------------

@pytest.mark.parametrize("rule", sorted(RULES))
def test_every_position_on_the_same_two_experts_drops_nothing(rule):
    cfg = module_of(moe_row_blocks=RULES[rule]).cfg
    p = layer_params(jax.random.PRNGKey(7), cfg)
    router = jnp.full_like(p["moe_router"], -10.0).at[:, :2].set(10.0)
    p = {**p, "moe_router": router}
    xs = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (64, cfg.hidden_size)))
    y, tokens = backbone.expert_layer(cfg, p, xs)
    assert tokens.tolist() == [64, 64]      # all 128 pairs, no capacity
    ref = reference._experts(dict(shape_of()), p, xs, None)
    np.testing.assert_allclose(y, ref, atol=1e-5)
    # and none on the held experts: the shared expert alone
    y, tokens = backbone.expert_layer(
        module_of(experts_held_from=4, moe_row_blocks=RULES[rule]).cfg, p, xs)
    assert tokens.tolist() == [0, 0]
    np.testing.assert_allclose(y, backbone.swiglu(
        xs, p["moe_shared_wg"], p["moe_shared_wu"], p["moe_shared_wd"], jnp.float32),
        atol=1e-6)


def padded_expert_layer(cfg, p, x):
    """``backbone.expert_layer`` as it was before PR 38, kept as the row
    blocks' second oracle (and as ``scripts/expert_layer_chip.py``'s
    yardstick): every one of the ``N k`` pairs is broadcast, sorted, gathered
    into expert order, masked on both sides of each ``lax.ragged_dot``,
    gathered back and summed with weight 0 on the absent; reverse mode is
    jax's own."""
    cd, k, held = cfg.compute_dtype, cfg.num_experts_per_token, cfg.experts_held
    n, d = x.shape
    experts, weights = backbone.route(cfg, p["moe_router"], x)
    local = experts - cfg.experts_held_from
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(n * k)
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order)
    tokens = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
    valid = (jnp.arange(n * k) < jnp.sum(tokens))[:, None]

    @jax.custom_vjp
    def permute(a, order, inverse):
        return a[order]

    permute.defvjp(lambda a, order, inverse: (a[order], (order, inverse)),
                   lambda res, ct: (ct[res[1]], None, None))

    def rd(a, w):
        a = jnp.where(valid, a.astype(cd), 0)
        out = jax.lax.ragged_dot(a, w.astype(cd), tokens, preferred_element_type=cd)
        return jnp.where(valid, out, 0)

    xs = jnp.broadcast_to(x.astype(cd)[:, None, :], (n, k, d)).reshape(n * k, d)
    xs = permute(xs, order, inverse)
    mid = jax.nn.silu(rd(xs, p["moe_wg"]).astype(jnp.float32)) * rd(xs, p["moe_wu"])
    ys = permute(rd(mid, p["moe_wd"]), inverse, order).reshape(n, k, d)
    y = jnp.sum(ys * jnp.where(mine, weights, 0.0)[..., None], axis=1)
    if cfg.num_shared_experts:
        y += backbone.swiglu(x, p["moe_shared_wg"], p["moe_shared_wu"],
                             p["moe_shared_wd"], cd)
    return y, tokens


def worst_router(router):
    """Every position on experts 0 and 1."""
    return jnp.full_like(router, -10.0).at[:, :2].set(10.0)


#: name -> (the layer's own arguments, the router, ``MOE_BLOCK_ROWS`` (None:
#: as many rows as there are held pairs), the blocks that then run).  64
#: positions, 2 experts a position: 128 pairs, of which the drawn router
#: holds 33
ROW_BLOCK_CASES = {
    "no_held_pair": (dict(experts_held_from=4), worst_router, 16, 0),
    "one_block_exactly_full": ({}, None, None, 1),
    "a_partial_last_block": ({}, None, 24, 2),
    "every_block_of_the_worst_case": ({}, worst_router, 32, 4),
    "no_shared_expert": (dict(num_shared_experts=0), None, 24, 2),
}


@pytest.mark.parametrize("case", sorted(ROW_BLOCK_CASES))
def test_row_blocks_give_what_the_padded_buffer_and_the_dense_loop_give(case, monkeypatch):
    """The expert layer in row blocks of the sorted order, with its written
    backward, against the rule it had before (the worst-case buffer under
    jax's own reverse mode, :func:`padded_expert_layer`) and against the
    reference's dense loop over every held expert: the result, ``dx``, the
    three matrices' gradients, the router's and the shared expert's, with
    blocks small enough that none, one, some or all of them run."""
    over, router_of, rows, blocks = ROW_BLOCK_CASES[case]
    cfg = module_of(moe_row_blocks=True, **over).cfg
    p = layer_params(jax.random.PRNGKey(7), cfg)
    if router_of is not None:
        p = {**p, "moe_router": router_of(p["moe_router"])}
    xs = jax.random.normal(jax.random.PRNGKey(8), (64, cfg.hidden_size))
    xs = jnp.abs(xs) if router_of is worst_router else xs
    probe = jnp.cos(jnp.arange(xs.size, dtype=jnp.float32)).reshape(xs.shape)
    held_pairs = int(backbone.expert_layer(cfg, p, xs)[1].sum())
    monkeypatch.setattr(backbone, "MOE_BLOCK_ROWS", rows or held_pairs)
    assert int(backbone.row_blocks(128, held_pairs)) == blocks
    assert (held_pairs % backbone.MOE_BLOCK_ROWS != 0) == ("partial" in case or "shared" in case)

    # the reference always has a shared expert: one of zeros where the layer has none
    d, w = cfg.hidden_size, cfg.moe_intermediate_size
    none = {"moe_shared_wg": jnp.zeros((d, w)), "moe_shared_wu": jnp.zeros((d, w)),
            "moe_shared_wd": jnp.zeros((w, d))}
    dense = lambda cfg, p, xs: (  # noqa: E731
        reference._experts(dict(shape_of(**over)), {**none, **p}, xs, None), None)

    def of(rule):
        def probed(p, xs):
            y, tokens = rule(cfg, p, xs)
            return jnp.sum(y * probe), (y, tokens)
        (_, (y, tokens)), (dp, dx) = jax.value_and_grad(
            probed, argnums=(0, 1), has_aux=True)(p, xs)
        return {"y": y, "dx": dx, **dp}, tokens

    made, tokens = of(backbone.expert_layer)
    padded, padded_tokens = of(padded_expert_layer)
    assert tokens.tolist() == padded_tokens.tolist()
    assert set(made) == set(padded) == {"y", "dx", *p}
    for other in (padded, of(dense)[0]):
        for name, value in made.items():
            np.testing.assert_allclose(
                value, other[name], atol=2e-5 * float(jnp.abs(other[name]).max()) + 1e-7,
                err_msg=name)
    if router_of is None:   # (a saturated router has no gradient to speak of)
        assert all(float(jnp.abs(made[n]).max()) > 0 for n in made)


def test_the_kinds_own_preset_keeps_the_worst_case_buffer_and_it_is_the_old_rule():
    """``kimi_linear`` alone keeps the rule from before PR 38 (its benchmark
    cell cannot tell what a faster program does: PERF.md section 7); asked
    for, it takes the row blocks as the other presets do.  The buffer's rule
    in the module is the tests' second oracle operation for operation."""
    assert backbone.KIMI_LINEAR == {"moe_row_blocks": False}
    assert module_of().cfg.moe_row_blocks is False
    assert module_of(moe_row_blocks=True).cfg.moe_row_blocks is True
    assert backbone.BackboneConfig(F, F).moe_row_blocks is True
    for preset in (backbone.GLM_MOE_LITE, backbone.LFM2_MOE):
        assert "moe_row_blocks" not in preset
    cfg = module_of().cfg
    p = layer_params(jax.random.PRNGKey(7), cfg)
    xs = jax.random.normal(jax.random.PRNGKey(8), (64, cfg.hidden_size))
    both = [jax.value_and_grad(lambda p, xs: jnp.sum(jnp.sin(rule(cfg, p, xs)[0])),
                               argnums=(0, 1))(p, xs)
            for rule in (backbone.expert_layer, padded_expert_layer)]
    for made, kept in zip(*map(jax.tree.leaves, both)):
        np.testing.assert_array_equal(made, kept)


@pytest.mark.parametrize("rule", sorted(RULES))
def test_the_forward_pass_counts_what_it_routed(x, monkeypatch, rule):
    module = module_of(moe_row_blocks=RULES[rule])
    params, _ = start(module, shape_of())
    monkeypatch.setattr(backbone, "MOE_BLOCK_ROWS", 16)
    y, counts = module.apply({"params": params}, x, counts=True)
    np.testing.assert_allclose(y, module.apply({"params": params}, x))
    assert counts["tokens"].shape == (1, 2)     # one expert layer, two held
    assert int(counts["selected"]) == 3 * 32 * 2
    assert int(counts["held"]) == int(counts["tokens"].sum()) <= int(counts["selected"])
    if rule == "worst_case_buffer":     # no loop, so no trips to count
        assert set(counts) == {"tokens", "selected", "held"}
        assert module.moe_blocks(3 * 32) == {}
        return
    # the loop's trips: the blocks of 16 rows that hold the held pairs, of 12
    assert int(counts["blocks_full"]) == 12
    assert int(counts["blocks_run"]) == -(-int(counts["held"]) // 16) < 12
    assert module.moe_blocks(3 * 32) == {"moe_block_rows": 16, "moe_blocks_full": 12}


# -- 6. sequences ----------------------------------------------------------------

@pytest.mark.parametrize("n_rows", [2, 20, 33, 34, 49, 50, 100])
def test_every_row_from_offset_is_forecast_once_and_padding_weighs_nothing(n_rows):
    context, stride = 32, 16
    rows = jnp.arange(n_rows, dtype=jnp.float32)[:, None] * jnp.ones((1, 2))
    inputs, targets, weights = make_sequences(rows, rows + 0.5, context, stride)
    s = num_sequences(n_rows, context, stride)
    assert inputs.shape == (s, context, 2) and weights.shape == (s, context)
    real = weights == 1
    assert set(np.unique(weights)) <= {0.0, 1.0}
    # a real position reads row r and is asked for row r + 1; padding is zeros
    np.testing.assert_array_equal(targets[real], inputs[real] + 1.5)
    assert float(jnp.abs(inputs[~real]).sum()) == 0 and float(jnp.abs(targets[~real]).sum()) == 0
    # the last row is nobody's input, every other row is somebody's
    assert set(np.asarray(inputs[real][:, 0]).tolist()) == set(range(n_rows - 1))
    # an identity "model": the rows come back as rows 0 .. n_rows - 2, once each
    back = sequences_to_rows(inputs, n_rows, context, stride)
    np.testing.assert_array_equal(back[:, 0], np.arange(n_rows - 1))


def test_lstm_forecast_windows_are_what_they_were():
    X = jnp.arange(40, dtype=jnp.float32).reshape(20, 2)
    est = LSTMForecast(kind="lstm_symmetric", lookback_window=4)
    np.testing.assert_array_equal(est._make_inputs(X), make_windows(X[:-1], 4))
    np.testing.assert_array_equal(est._make_targets(X, None), X[4:])
    assert est.offset == 4 and est._sample_weights(X) is None
    assert est._rows_from_outputs(X, 20) is X


# -- 5. and 7.: a project through build_project -----------------------------------

def tiny_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi-linear-plant.json")) as fh:
        config = json.load(fh)
    config["model"].update(context=32, stride=16, batch_size=4, kda_chunk=8, **TINY)
    config["dataset"].update(
        n_tags=F, train_end_date="2017-01-02T12:00:00+00:00", rows=217)
    return config


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Two machines through ``build_project`` with NO ``max_bucket_size``:
    the planner reads the parameter count and puts both in one chunk."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config = tiny_config()
    out = str(tmp_path_factory.mktemp("backbone-project"))
    doc = kind.project_doc(config, SEED, 2)
    machines = NormalizedConfig(doc, "backbone-test").machines
    before = telemetry.REGISTRY.snapshot()["metrics"]
    result = build_project(machines, out, artifact_format="v2")
    after = telemetry.REGISTRY.snapshot()["metrics"]
    return config, out, result, before, after


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
        ref = kind.reference_of(config, kind.reference_rows(config, name),
                                kind.model_seed(SEED), folds=i == 0)
        numbers = kind.compare(made, ref)
        assert numbers["loss_first_gap"] < 1e-5 and numbers["loss_last_gap"] < 1e-5
        assert numbers["update_norm_gap"] < UPDATE_GAP and numbers["nonfinite"] == 0
        if i == 0:
            assert numbers["threshold_gap"] < 1e-4


def test_a_half_billion_parameter_model_is_a_chunk_of_one():
    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import _parameter_count, default_bucket_size
    from gordo_tpu.parallel.anomaly import analyze_definition

    with open(os.path.join(ROOT, "benchmark", "configs", "kimi-linear-plant.json")) as fh:
        config = json.load(fh)
    doc = kind.project_doc(config, SEED, 1)
    spec = analyze_definition(serializer.from_definition(doc["globals"]["model"]))
    assert _parameter_count(spec, (50, 50)) == config["parameters"] == 508292018
    assert default_bucket_size(spec, (50, 50)) == 1
    tiny = kind.project_doc(tiny_config(), SEED, 1)
    spec = analyze_definition(serializer.from_definition(tiny["globals"]["model"]))
    assert default_bucket_size(spec, (F, F)) == 512


def test_the_routing_counters_and_the_artifacts_metadata(built):
    from gordo_tpu import artifacts

    _, out, _, before, after = built
    selected = counter(after, "gordo_moe_selected_pairs_total") - counter(
        before, "gordo_moe_selected_pairs_total")
    held = counter(after, "gordo_moe_held_pairs_total") - counter(
        before, "gordo_moe_held_pairs_total")
    tokens = counter(after, "gordo_moe_tokens_total") - counter(
        before, "gordo_moe_tokens_total")
    # no pair can be dropped: every held pair was computed by a held expert
    assert selected > 0 and 0 < held <= selected and tokens == held
    labels = [json.loads(k) for k in after["gordo_moe_tokens_total"]["series"]]
    assert sorted(labels) == [["2", "0"], ["2", "1"]]   # layer 2, experts 0 and 1
    _, refs = artifacts.discover(out)
    moe = refs[0].load_metadata()["model"]["cross_validation"]["moe"]
    assert moe["held_pairs"] == int(np.sum(moe["tokens_per_held_expert"]))
    assert moe["selected_pairs"] >= moe["held_pairs"]


def test_the_row_blocks_are_counted_and_the_span_says_their_size(built, tmp_path):
    """``gordo_moe_row_blocks_total{state}`` beside the pair counters, and
    ``moe_block_rows`` / ``moe_blocks_full`` on the tracing chunk's enqueue
    span: a step of the tiny preset is 4 sequences of 32 rows, 256 pairs,
    fewer than ``backbone.MOE_BLOCK_ROWS``, so its one expert layer's loop
    takes them in one block, which runs wherever a pair is held.  A build on
    the worst-case buffer (the kind's own preset, ``built``) says neither."""
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    _, _, kept, before, after = built
    assert counter(after, "gordo_moe_row_blocks_total") == counter(
        before, "gordo_moe_row_blocks_total")
    assert not {"moe_block_rows", "moe_blocks_full"} & set(
        kept.timeline[0]["counts"]["enqueue"])

    config = tiny_config()
    config["model"]["moe_row_blocks"] = True
    machines = NormalizedConfig(kind.project_doc(config, SEED, 1), "row-blocks").machines
    out = str(tmp_path)
    before = telemetry.REGISTRY.snapshot()["metrics"]
    result = build_project(machines, out, artifact_format="v2")
    after = telemetry.REGISTRY.snapshot()["metrics"]
    assert not result.summary()["failed"]
    delta = lambda name, *labels: counter(after, name, *labels) - counter(  # noqa: E731
        before, name, *labels)
    run, full = (delta("gordo_moe_row_blocks_total", state) for state in ("run", "full"))
    assert backbone.MOE_BLOCK_ROWS > 256
    assert 0 < run <= full == delta("gordo_moe_selected_pairs_total") / 256
    counts = result.timeline[0]["counts"]["enqueue"]
    assert counts["moe_block_rows"] == 256 and counts["moe_blocks_full"] == 1
    (snapshot,) = telemetry.load_snapshot_dir(os.path.join(out, telemetry.SNAPSHOT_DIR))
    assert "gordo_moe_row_blocks_total" in json.dumps(snapshot)


def test_the_solve_is_counted_where_the_program_is_traced(built):
    """``gordo_kda_solve_total{rule="block_inverse"}`` and the span's counts:
    one KDA layer (layer 1; layer 2 is MLA), traced in the forward and in the
    written backward's recomputation of four fits and in three forecasts."""
    _, out, result, before, after = built
    labels = [json.loads(k) for k in after["gordo_kda_solve_total"]["series"]]
    assert labels == [["block_inverse"]]
    traced = counter(after, "gordo_kda_solve_total") - counter(
        before, "gordo_kda_solve_total")
    assert traced > 0
    counts = result.timeline[0]["counts"]["enqueue"]
    assert counts["kda_solve_traces"] == traced
    # chunks of 8: three levels a solve
    assert counts["kda_solve_levels"] == 3 * traced
    (snapshot,) = telemetry.load_snapshot_dir(os.path.join(out, telemetry.SNAPSHOT_DIR))
    assert "gordo_kda_solve_total" in json.dumps(snapshot)


def test_the_attention_core_is_counted_as_one_block(built):
    """``gordo_mla_attention_total{rule="whole"}``: the tiny preset's 32
    rows are fewer than ``backbone.MLA_BLOCK``, so its one MLA layer's cores
    (traced where the solves are) are one block each and none is
    ``causal_blocks``; ``tests/test_backbone_glm.py`` has a build of four."""
    _, out, result, before, after = built
    traced = {rule: counter(after, "gordo_mla_attention_total", rule) - counter(
        before, "gordo_mla_attention_total", rule) for rule in ("whole", "causal_blocks")}
    assert traced["whole"] > 0 and traced["causal_blocks"] == 0
    counts = result.timeline[0]["counts"]["enqueue"]
    assert backbone.MLA_BLOCK > 32
    assert counts["mla_attn_traces"] == counts["mla_attn_blocks"] == traced["whole"]
    assert counts["mla_attn_pairs_computed"] == counts["mla_attn_pairs_square"] == traced["whole"]
    assert not [name for name in counts if name.startswith("swa_attn")]   # no windowed core
    (snapshot,) = telemetry.load_snapshot_dir(os.path.join(out, telemetry.SNAPSHOT_DIR))
    assert "gordo_mla_attention_total" in json.dumps(snapshot)


def test_the_lowered_sequence_fit_holds_no_triangular_solve():
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
    text = lowered.as_text()
    assert "stablehlo.dot_general" in text
    assert "triangular_solve" not in text and "triangular-solve" not in text
    # the solve's operations, under the scan's scope in the forward pass and
    # in the backward's (whose stack holds the rule's own scope in any case)
    named = lowered.as_text(debug_info=True)
    for passes in ("backbone.kda", "jvp(backbone.kda)", "transpose(jvp(backbone.kda))"):
        assert passes + "/backbone.kda.scan/backbone.kda.scan.solve/dot_general" in named


def test_the_artifact_round_trips_and_scores(built):
    from gordo_tpu import artifacts

    config, out, _, _, _ = built
    _, refs = artifacts.discover(out)
    by_name = {ref.name: ref for ref in refs}
    name = kind.machine_names(SEED, 2)[1]
    detector = by_name[name].load_model()
    estimator = detector.base_estimator.steps[-1][1]
    assert isinstance(estimator, SequenceForecast) and estimator.offset == 1
    rows = kind.reference_rows(config, name)
    frame = detector.anomaly(rows, rows)
    assert len(frame) == len(rows) - 1
    assert np.isfinite(frame[("total-anomaly-score", "")].to_numpy()).all()
    # the estimator's own predict is the reference's forecast
    scaled = reference.minmax(rows, rows)
    shape = reference.shape_of(config["model"], F, F)
    ref = reference.predict(
        jax.tree.map(jnp.asarray, estimator.params_), rows, rows, config["model"], shape)
    np.testing.assert_allclose(estimator.predict(scaled), ref, atol=1e-4)


def test_the_stacked_the_streaming_and_the_backfill_planes_refuse_it_by_name(built):
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


# -- satellites ---------------------------------------------------------------------

def test_a_list_valued_estimator_argument_buckets():
    from gordo_tpu import serializer
    from gordo_tpu.parallel.anomaly import analyze_definition

    def spec_of(dims):
        return analyze_definition(serializer.from_definition({
            "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector": {"base_estimator": {
                "gordo_tpu.pipeline.Pipeline": {"steps": [
                    "gordo_tpu.ops.scalers.MinMaxScaler",
                    {"gordo_tpu.models.estimator.LSTMAutoEncoder": {
                        "kind": "lstm_symmetric", "lookback_window": 3,
                        "dims": dims, "epochs": 1}}]}}}}))

    a, b = spec_of([256, 128, 64]), spec_of([256, 128, 64])
    assert hash(a.signature) == hash(b.signature) and a.signature == b.signature
    assert a.signature != spec_of([128, 64]).signature
    buckets = {}
    buckets.setdefault((a.signature, (5, 5)), []).append("m0")
    buckets.setdefault((b.signature, (5, 5)), []).append("m1")
    assert list(buckets.values()) == [["m0", "m1"]]

"""The backward of ``_fused_lstm_layer`` is written by hand (ISSUE 30): a
``jax.custom_vjp`` whose forward is the parent's to the bit and whose
gradient is autodiff's to rounding (and nearer the float32 one where a gate
saturates).

Two references live HERE and not in the package: ``parent_layer``, the
layer as it stood before (autodiff through the scan), and flax's
``nn.RNN(OptimizedLSTMCell)`` fed the same weights."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu import telemetry
from gordo_tpu.models.factories import lstm as lstm_mod
from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.models.factories.lstm import GATES, _fused_lstm_layer, lstm_hourglass
from gordo_tpu.train.fit import TrainConfig, make_fit_fn

from tests.lstm_detectors import LOOKBACK, N_TAGS

BATCH, MACHINES, UNITS = 5, 3, 7
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
NAMES = ("x", "kernel_i", "kernel_h", "bias")


def parent_layer(x, kernel_i, kernel_h, bias, features, compute_dtype):
    """``_fused_lstm_layer`` as it was before ISSUE 30, word for word."""
    cd = compute_dtype
    xp = x.astype(cd) @ kernel_i.astype(cd)
    kernel_h = kernel_h.astype(cd)
    bias = bias.astype(cd)
    batch = x.shape[0]
    c0 = jnp.zeros((batch, features), jnp.float32)
    h0 = jnp.zeros((batch, features), jnp.float32)

    def step(carry, xp_t):
        c, h = carry
        z = (h.astype(cd) @ kernel_h + bias) + xp_t
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i, f, o = nn.sigmoid(i), nn.sigmoid(f), nn.sigmoid(o)
        g = nn.tanh(g)
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (c, h), h

    _, hs = jax.lax.scan(step, (c0, h0), jnp.swapaxes(xp, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def flax_layer(x, kernel_i, kernel_h, bias, features, compute_dtype):
    """The oracle: flax's cell over the same weights, gate by gate."""
    cell = {}
    for c, k in zip(GATES, jnp.split(kernel_i, 4, axis=-1)):
        cell[f"i{c}"] = {"kernel": k}
    for c, k, b in zip(GATES, jnp.split(kernel_h, 4, axis=-1),
                       jnp.split(bias, 4, axis=-1)):
        cell[f"h{c}"] = {"kernel": k, "bias": b}
    rnn = nn.RNN(nn.OptimizedLSTMCell(features, dtype=compute_dtype))
    return rnn.apply({"params": {"cell": cell}}, x.astype(compute_dtype))


def layer_args(lookback=LOOKBACK, machines=None, seed=11):
    """One layer's input and weights; with ``machines`` a fleet's, stacked."""
    lead = () if machines is None else (machines,)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(keys[0], lead + (BATCH, lookback, N_TAGS)),
        0.5 * jax.random.normal(keys[1], lead + (N_TAGS, 4 * UNITS)),
        0.5 * jax.random.normal(keys[2], lead + (UNITS, 4 * UNITS)),
        0.1 * jax.random.normal(keys[3], lead + (4 * UNITS,)),
    )


def called(layer, cd, call):
    """``layer`` over one machine's arguments or, under ``vmap``, a fleet's."""
    def one(*args):
        return layer(*args, UNITS, cd)
    return jax.vmap(one) if call == "vmap" else one


def loss_of(layer, cd, call, feeds):
    """Mean squared output: of every time step, or of the last alone (what
    a stack's last layer gets from the head: zero cotangent before it)."""
    fn = called(layer, cd, call)

    def loss(*args):
        out = fn(*args).astype(jnp.float32)
        return jnp.mean((out[..., -1, :] if feeds == "last" else out) ** 2)
    return loss


def gradients(layer, cd, call, feeds, args):
    return jax.jit(jax.grad(loss_of(layer, cd, call, feeds), argnums=(0, 1, 2, 3)))(*args)


def distance(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("call", ["plain", "vmap"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_one_layers_forward_is_the_parents_to_the_bit(dtype, call):
    cd = DTYPES[dtype]
    args = layer_args(machines=MACHINES if call == "vmap" else None)
    parent = jax.jit(called(parent_layer, cd, call))(*args)
    ours = called(_fused_lstm_layer, cd, call)
    primal = jax.jit(ours)(*args)
    assert primal.dtype == parent.dtype and primal.shape == parent.shape
    assert np.array_equal(primal, parent)
    # and the forward rule, which is what a fit's loss is computed by
    under_grad, _ = jax.jit(lambda *a: jax.vjp(ours, *a))(*args)
    assert np.array_equal(under_grad, parent)


@pytest.mark.parametrize("feeds", ["last", "every"])
@pytest.mark.parametrize("call", ["plain", "vmap"])
def test_one_layers_float32_gradient_is_flax_autodiffs(call, feeds):
    args = layer_args(machines=MACHINES if call == "vmap" else None)
    ours = gradients(_fused_lstm_layer, jnp.float32, call, feeds, args)
    oracle = gradients(flax_layer, jnp.float32, call, feeds, args)
    for name, arg, a, b in zip(NAMES, args, ours, oracle):
        assert a.shape == arg.shape and a.dtype == arg.dtype, name
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("feeds", ["last", "every"])
@pytest.mark.parametrize("call", ["plain", "vmap"])
def test_one_layers_bfloat16_gradient_is_no_further_than_autodiffs(call, feeds):
    """At bfloat16 the rule's sums are float32 where autodiff's are
    bfloat16: it lies no further from the float32 gradient.  Both are
    ~1 % from it by the bfloat16 FORWARD they share, which moves either
    by a twentieth of that from draw to draw: so each argument's distance
    is held to autodiff's over three draws, and to 1.1 of it in each."""
    ours, autodiffs = np.zeros(4), np.zeros(4)
    for seed in (11, 12, 13):
        args = layer_args(machines=MACHINES if call == "vmap" else None, seed=seed)
        exact = gradients(flax_layer, jnp.float32, call, feeds, args)
        rule = gradients(_fused_lstm_layer, jnp.bfloat16, call, feeds, args)
        autodiff = gradients(parent_layer, jnp.bfloat16, call, feeds, args)
        for k, (name, arg, a, b, e) in enumerate(
                zip(NAMES, args, rule, autodiff, exact)):
            assert a.shape == arg.shape and a.dtype == arg.dtype, name
            assert distance(a, e) <= 1.1 * distance(b, e), (name, seed)
            ours[k] += distance(a, e)
            autodiffs[k] += distance(b, e)
    assert np.all(ours <= autodiffs), dict(zip(NAMES, zip(ours, autodiffs)))


@pytest.mark.parametrize("call", ["plain", "vmap"])
def test_saturated_gates_keep_their_gradient(call):
    """Why the rule keeps the gates BEFORE their non-linearities: a trained
    last layer's output gate sits near 1, where its bfloat16 value leaves
    ``1 - o`` a quarter off (on the chip the cell's fits then moved those
    kernels a fifth less than the reference's).  Recomputed in float32
    from ``z`` the sigmoid gates' gradients stay as near the float32 one
    as autodiff's at least, the output gate's twice as near or more."""
    x, kernel_i, kernel_h, bias = layer_args(
        machines=MACHINES if call == "vmap" else None)
    bias = bias.at[..., :2 * UNITS].add(4.0).at[..., 3 * UNITS:].add(5.0)
    args = (x, kernel_i, kernel_h, bias)
    exact = gradients(flax_layer, jnp.float32, call, "last", args)
    rule = gradients(_fused_lstm_layer, jnp.bfloat16, call, "last", args)
    autodiff = gradients(parent_layer, jnp.bfloat16, call, "last", args)
    for name, a, b, e in list(zip(NAMES, rule, autodiff, exact))[1:]:
        for k, gate in enumerate(GATES):
            of = (Ellipsis, slice(k * UNITS, (k + 1) * UNITS))
            ours, theirs = distance(a[of], e[of]), distance(b[of], e[of])
            if gate != "g":
                assert ours <= theirs, (name, gate)
            if gate == "o":
                assert ours <= 0.03 and ours <= 0.5 * theirs, (name, ours, theirs)


def test_a_window_of_one_row_has_no_recurrent_gradient():
    """``lookback_window=1``, the estimator's default: ``h_{-1} = 0`` is all
    ``kernel_h`` ever multiplies, so its gradient is zero, as autodiff's."""
    args = layer_args(lookback=1)
    ours = gradients(_fused_lstm_layer, jnp.float32, "plain", "every", args)
    oracle = gradients(flax_layer, jnp.float32, "plain", "every", args)
    assert not np.any(ours[2]) and not np.any(oracle[2])
    for a, b in zip(ours, oracle):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# the six-layer hourglass, through the module
# ---------------------------------------------------------------------------

def hourglass(dtype, call):
    """``(loss, packed parameters, windows)`` of the hourglass stack; the
    loss is the fit's (mean squared error of the head's output)."""
    module = lstm_hourglass(N_TAGS, compute_dtype=dtype)
    X = jax.random.uniform(jax.random.PRNGKey(0), (BATCH, LOOKBACK, N_TAGS))
    init = lambda key: module.pack(module.init(key, X[:1])["params"])  # noqa: E731
    if call == "vmap":
        packed = jax.vmap(init)(jax.random.split(jax.random.PRNGKey(5), MACHINES))
        X = jnp.stack([X + 0.1 * m for m in range(MACHINES)])
        apply = jax.vmap(module.apply_packed)
    else:
        packed, apply = init(jax.random.PRNGKey(5)), module.apply_packed

    def loss(packed, X):
        out = apply(packed, X)
        return jnp.mean((out - X[..., -1, :]) ** 2), out
    return loss, packed, X


def with_layer(monkeypatch, layer, fn, *args):
    """``fn`` traced with the module's recurrence replaced by ``layer``."""
    with monkeypatch.context() as patch:
        patch.setattr(lstm_mod, "_fused_lstm_layer", layer)
        return jax.jit(fn)(*args)


@pytest.mark.parametrize("call", ["plain", "vmap"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_the_hourglass_forward_is_the_parents_to_the_bit(dtype, call, monkeypatch):
    loss, packed, X = hourglass(dtype, call)
    (value, out), _ = jax.jit(jax.value_and_grad(loss, has_aux=True))(packed, X)
    plain_value, plain_out = jax.jit(loss)(packed, X)
    parent_value, parent_out = with_layer(monkeypatch, parent_layer, loss, packed, X)
    assert np.array_equal(out, parent_out) and np.array_equal(plain_out, parent_out)
    assert value == parent_value and plain_value == parent_value


def flax_hourglass(module, call):
    """The stack as flax would run it, over the module's packed tree."""
    def one(packed, x):
        for k, d in enumerate(module.dims):
            layer = packed[f"OptimizedLSTMCell_{k}"]
            x = jnp.tanh(flax_layer(
                x, layer["kernel_i"], layer["kernel_h"], layer["bias"],
                int(d), jnp.float32))
        return x[:, -1, :] @ packed["out"]["kernel"] + packed["out"]["bias"]

    apply = jax.vmap(one) if call == "vmap" else one
    return lambda packed, X: jnp.mean((apply(packed, X) - X[..., -1, :]) ** 2)


@pytest.mark.parametrize("call", ["plain", "vmap"])
def test_the_hourglass_float32_gradient_is_flax_autodiffs(call):
    loss, packed, X = hourglass("float32", call)
    ours = jax.jit(jax.grad(lambda p, x: loss(p, x)[0]))(packed, X)
    module = lstm_hourglass(N_TAGS, compute_dtype="float32")
    oracle = jax.jit(jax.grad(flax_hourglass(module, call)))(packed, X)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree.leaves(oracle)):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5 * float(jnp.abs(b).max()), err_msg=str(path))


@pytest.mark.parametrize("call", ["plain", "vmap"])
def test_the_hourglass_bfloat16_gradient_is_no_further_than_autodiffs(
        call, monkeypatch):
    loss, packed, X = hourglass("bfloat16", call)
    grad = jax.grad(lambda p, x: loss(p, x)[0])
    ours = jax.jit(grad)(packed, X)
    autodiff = with_layer(monkeypatch, parent_layer, grad, packed, X)
    exact = jax.jit(jax.grad(lambda p, x: hourglass("float32", call)[0](p, x)[0]))(
        packed, X)
    # over the whole tree: six layers of bfloat16 forward separate both
    # from the float32 gradient by far more than their sums do
    flat = lambda tree: np.concatenate(  # noqa: E731
        [np.ravel(a) for a in jax.tree.leaves(tree)])
    assert distance(flat(ours), flat(exact)) <= 1.02 * distance(
        flat(autodiff), flat(exact))


# ---------------------------------------------------------------------------
# the counter and the span fields
# ---------------------------------------------------------------------------

def written():
    return telemetry.REGISTRY.get("gordo_lstm_backward_total").value("written")


def traced_fit(module, X, y):
    cfg = TrainConfig(epochs=1, batch_size=16)
    params = module.init(jax.random.PRNGKey(1), X[:1])["params"]
    before = written()
    with telemetry.span("test.lstm_backward") as sp:
        jax.jit(make_fit_fn(module, cfg, 4, 16))(
            params, X, y, jnp.ones(64), jax.random.PRNGKey(2))
    return sp, written() - before


def test_an_lstm_fit_counts_its_backward_where_it_is_traced():
    X = jax.random.uniform(jax.random.PRNGKey(0), (64, LOOKBACK, N_TAGS))
    sp, counted = traced_fit(lstm_hourglass(N_TAGS), X, X[:, -1])
    # one trace of the rule a layer, three stacks kept by each
    assert counted == 6
    assert sp["lstm_backward_traces"] == 6 and sp["lstm_saved_stacks"] == 18
    assert sp["fit_traces"] == 1


def test_a_dense_fit_counts_no_lstm_backward():
    X = jax.random.uniform(jax.random.PRNGKey(0), (64, N_TAGS))
    sp, counted = traced_fit(feedforward_hourglass(N_TAGS), X, X)
    assert counted == 0 and sp["fit_traces"] == 1
    assert "lstm_backward_traces" not in sp and "lstm_saved_stacks" not in sp

"""The exact GELU's derivative rule (``ops/contrib.py`` ``gelu_erf``):
the same function as ``jax.nn.gelu(approximate=False)``, with ``erfc``
evaluated once under differentiation and the derivative saved.  What a
CPU can check: values, first and second derivatives, a toy BERT
pretraining run against plain autodiff, and the structure of the
differentiated step (one ``erfc`` and one barrier an activation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, models, nd, parallel
from mxnet_tpu.ops.contrib import gelu_erf
from mxnet_tpu.ops.registry import OP_REGISTRY

POINTS = np.concatenate([np.linspace(-12.0, 12.0, 4097),
                         [0.0, 88.0, -88.0, 1e4, -1e4]]).astype(np.float32)


def plain(x):
    return jax.nn.gelu(x, approximate=False)


def second_derivative(x):
    x = np.asarray(x, np.float64)
    return (2.0 - x * x) * np.exp(-0.5 * x * x) / np.sqrt(2.0 * np.pi)


@pytest.mark.parametrize("shape", [(POINTS.size,), (3, 5, 7)])
def test_not_differentiated_is_jax_gelu(shape):
    x = jnp.asarray(np.resize(POINTS, shape))
    for f in (gelu_erf, jax.jit(gelu_erf),
              lambda a: nd.LeakyReLU(nd.array(a), act_type="gelu").asnumpy()):
        assert np.array_equal(np.asarray(f(x)), np.asarray(plain(x)))
    text = str(jax.make_jaxpr(gelu_erf)(x))
    assert "erfc" in text and "optimization_barrier" not in text


@pytest.mark.parametrize("what", ["grad", "value"])
def test_first_derivative_and_value_under_differentiation(what):
    x = jnp.asarray(POINTS)
    if what == "grad":
        got = jax.grad(lambda a: gelu_erf(a).sum())(x)
        want = jax.grad(lambda a: plain(a).sum())(x)
        limit = 2e-6
    else:
        got = jax.vjp(gelu_erf, x)[0]
        want = plain(x)
        limit = 2e-7 * np.abs(POINTS) + 1e-9
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert (np.abs(got - want) <= limit).all(), np.abs(got - want).max()


@pytest.mark.parametrize("tape", ["jax", "nd"])
def test_reverse_over_reverse(tape):
    if tape == "jax":
        x = jnp.asarray(POINTS)
        got = jax.grad(lambda a: jax.grad(
            lambda b: gelu_erf(b).sum())(a).sum())(x)
        want = second_derivative(POINTS)
    else:
        pts = np.linspace(-6.0, 6.0, 97).astype(np.float32)
        a = nd.array(pts)
        a.attach_grad()
        with autograd.record():
            y = nd.LeakyReLU(a, act_type="gelu")
            first = autograd.grad(y, [a], create_graph=True)[0]
            total = first.sum()
        total.backward()
        got, want = a.grad.asnumpy(), second_derivative(pts)
    assert np.abs(np.asarray(got) - want).max() <= 2e-6


LAYERS = 2


def toy_trainer():
    """A two-layer BERT with both pretraining heads in a ShardedTrainer,
    and the batches of three steps."""
    rng = np.random.RandomState(7)
    V, B, L, M = 96, 4, 16, 3
    mx.random.seed(11)
    bert = models.get_bert_model("bert_12_768_12", vocab_size=V, dropout=0.0,
                                 max_length=32, units=32, hidden_size=64,
                                 num_layers=LAYERS, num_heads=4)
    bert.initialize(mx.init.Normal(0.5))
    head = models.BERTForPretrain(bert, vocab_size=V)
    head.initialize(mx.init.Normal(0.5))

    def loss(outputs, mlm_y, nsp_y):
        mlm, nsp = (jax.nn.log_softmax(o, -1) for o in outputs)
        return (-jnp.take_along_axis(mlm, mlm_y[..., None], -1).mean()
                - jnp.take_along_axis(nsp, nsp_y[:, None], -1).mean())

    batches = [(rng.randint(0, V, (B, L)).astype(np.int32),
                rng.randint(0, 2, (B, L)).astype(np.int32),
                np.full((B,), L, np.float32),
                rng.randint(0, L, (B, M)).astype(np.int32),
                rng.randint(0, V, (B, M)).astype(np.int32),
                rng.randint(0, 2, (B,)).astype(np.int32)) for _ in range(3)]
    trainer = parallel.ShardedTrainer(
        head, loss, parallel.make_mesh(dp=1, tp=1, sp=1,
                                       devices=jax.devices()[:1]),
        optimizer="adamw", optimizer_params={"learning_rate": 1e-3},
        example_inputs=tuple(nd.array(a) for a in batches[0][:4]),
        n_labels=2)
    return trainer, batches


def three_steps():
    trainer, batches = toy_trainer()
    losses, programs = [], []
    for batch in batches:
        losses.append(float(trainer.step(*batch)))
        programs.append(trainer._step._cache_size())
    # by position: a second build numbers its blocks anew
    params = [(k, np.asarray(v)) for k, v in trainer.params.items()]
    return losses, programs, params


@pytest.fixture(scope="module")
def runs():
    ours = three_steps()
    op = OP_REGISTRY["_contrib_gelu_erf"]
    kept = op.fn
    op.fn = plain
    try:
        theirs = three_steps()
    finally:
        op.fn = kept
    return ours, theirs


@pytest.mark.parametrize("what", ["losses", "params", "programs"])
def test_toy_bert_pretraining_equals_plain_autodiff(runs, what):
    (losses, programs, params), (losses0, _, params0) = runs
    if what == "losses":
        assert np.isfinite(losses).all() and len(set(losses)) == 3
        np.testing.assert_allclose(losses, losses0, rtol=1e-6)
    elif what == "params":
        assert len(params) == len(params0) > 12 * LAYERS
        # by root mean square, 1e-6 of the leaf and a ten-thousandth of
        # what three adamw updates (lr 1e-3) can move an element: adam
        # turns a gradient that is rounding alone into a whole update,
        # on single elements anywhere and on the whole key third of the
        # fused qkv bias (no gradient under softmax)
        def rms(a):
            return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
        for (name, value), (_, value0) in zip(params, params0):
            assert np.isfinite(value).all(), name
            if name.endswith("qkv_bias"):
                continue
            assert rms(value - value0) <= 1e-6 * rms(value0) + 3e-7, name
    else:
        assert programs == [1, 1, 1]


@pytest.mark.parametrize("primitive", ["erfc", "optimization_barrier"])
def test_differentiated_step_has_one_of_each_an_activation(primitive):
    trainer, batches = toy_trainer()
    text = str(jax.make_jaxpr(trainer._step.__wrapped__)(
        trainer.params, trainer.opt_state, *batches[0]))
    # one FFN activation a layer and the masked-LM head's: autodiff
    # itself never recomputed it, XLA's fusion did
    assert text.count(f" {primitive} ") + text.count(f" {primitive}[") \
        == LAYERS + 1, text.count(primitive)

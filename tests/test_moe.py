"""ops/moe.py (top-k dropless routing, the held experts' share) +
gluon.contrib.MoEFFN + expert-parallel sharding."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon, parallel
from mxnet_tpu.gluon.contrib import MoEFFN
from mxnet_tpu.ops.moe import moe_ffn, moe_topk_route

ACT = {"relu": lambda x: np.maximum(x, 0),
       "silu": lambda x: x / (1 + np.exp(-x))}


def dense_moe(x, wg, w1, w2, k, first=0, activation="silu", gated=True):
    """Every held expert on every token, weighted, in float64 numpy."""
    x, wg, w1, w2 = (np.asarray(a, np.float64) for a in (x, wg, w1, w2))
    logits = x @ wg
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ids = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(p, ids, -1)
    w = w / w.sum(-1, keepdims=True)
    full = np.zeros_like(p)
    np.put_along_axis(full, ids, w, -1)
    out = np.zeros_like(x)
    for e in range(w1.shape[0]):
        h = x @ w1[e]
        if gated:
            gate, up = np.split(h, 2, -1)
            h = ACT[activation](gate) * up
        else:
            h = ACT[activation](h)
        out += full[:, first + e:first + e + 1] * (h @ w2[e])
    return out, ids, w


def _weights(seed, S=48, C=8, H=16, E=8, gated=True):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))  # noqa: E731
    return (f(S, C), f(C, E), 0.3 * f(E, C, (2 if gated else 1) * H),
            0.3 * f(E, H, C))


@pytest.mark.parametrize("k", [1, 2, 8])
def test_topk_route(k):
    x, wg, _w1, _w2 = _weights(0)
    with jax.default_matmul_precision("highest"):
        w, ids = moe_topk_route(x, wg, experts_per_token=k)
    _out, want_ids, want_w = dense_moe(x, wg, np.zeros((0, 8, 2)),
                                       np.zeros((0, 1, 8)), k)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    assert ids.dtype == jnp.int32 and w.dtype == jnp.float32


def test_route_ties_go_to_the_lower_id():
    x = jnp.ones((3, 4), jnp.float32)
    w, ids = moe_topk_route(x, jnp.zeros((4, 6), jnp.float32),
                            experts_per_token=2)
    np.testing.assert_array_equal(np.asarray(ids), [[0, 1]] * 3)
    np.testing.assert_allclose(np.asarray(w), 0.5, rtol=1e-6)


@pytest.mark.parametrize("k,first,held,activation,gated", [
    (1, 0, 8, "relu", False), (2, 0, 8, "silu", True),
    (3, 2, 3, "silu", True), (8, 4, 4, "silu", True),
    (2, 6, 2, "relu", False)])
def test_moe_ffn_is_the_held_experts_weighted_sum(k, first, held,
                                                  activation, gated):
    x, wg, w1, w2 = _weights(1, gated=gated)
    w1, w2 = w1[first:first + held], w2[first:first + held]
    with jax.default_matmul_precision("highest"):
        out, rows = jax.jit(lambda *a: moe_ffn(
            *a, experts_per_token=k, first_expert=first,
            activation=activation, gated=gated))(x, wg, w1, w2)
    want, ids, _w = dense_moe(x, wg, w1, w2, k, first, activation, gated)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(
        np.asarray(rows), [(ids == first + e).sum() for e in range(held)])


def test_no_token_is_dropped_when_all_choose_the_same_experts():
    # every token's 8 choices are experts 0..7 of 16: each of them gets
    # all S rows (8 times the mean load), experts 8..15 none
    S, C, H, E, k = 64, 8, 4, 16, 8
    rng = np.random.RandomState(2)
    x = jnp.asarray(np.abs(rng.randn(S, C)).astype(np.float32) + 0.1)
    wg = np.zeros((C, E), np.float32)
    wg[:, :8] = 1.0 + 0.01 * np.arange(8)
    w1 = jnp.asarray(0.3 * rng.randn(E, C, 2 * H).astype(np.float32))
    w2 = jnp.asarray(0.3 * rng.randn(E, H, C).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        out, rows = moe_ffn(x, jnp.asarray(wg), w1, w2, experts_per_token=k,
                            activation="silu", gated=True)
    np.testing.assert_array_equal(np.asarray(rows), [S] * 8 + [0] * 8)
    want, _ids, _w = dense_moe(x, wg, w1, w2, k)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-6)


def test_a_token_none_of_whose_experts_is_held_gets_zero():
    x, wg, w1, w2 = _weights(3)
    _o, ids, _w = dense_moe(x, wg, w1, w2, 2)
    out, rows = moe_ffn(x, wg, w1[6:], w2[6:], experts_per_token=2,
                        first_expert=6, activation="silu", gated=True)
    unheld = ~np.isin(ids, (6, 7)).any(-1)
    assert unheld.sum() > 5 and (~unheld).sum() > 5
    assert not np.asarray(out)[unheld].any()
    assert np.abs(np.asarray(out)[~unheld]).min(0).max() > 0
    assert float(rows.sum()) == np.isin(ids, (6, 7)).sum()


def test_moe_ffn_single_expert_equals_mlp():
    rng = np.random.RandomState(0)
    S, C, H = 8, 4, 16
    x = jnp.asarray(rng.randn(S, C).astype(np.float32))
    w1 = jnp.asarray(rng.randn(1, C, H).astype(np.float32))
    w2 = jnp.asarray(rng.randn(1, H, C).astype(np.float32))
    out, rows = moe_ffn(x, jnp.zeros((C, 1), jnp.float32), w1, w2,
                        activation="relu")
    # E=1: the one weight is 1, so this IS the plain MLP
    ref = np.maximum(np.asarray(x) @ np.asarray(w1[0]), 0) @ \
        np.asarray(w2[0])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    assert float(rows[0]) == S


def dense_layer(x, wg, w1, w2, k, first):
    """Every held expert on every token, weighted: what ``moe_ffn``
    (silu, gated) computes, differentiable."""
    held = w1.shape[0]
    p = jax.nn.softmax(x @ wg, -1)
    ids = jnp.argsort(-p, -1)[:, :k]
    w = jnp.take_along_axis(p, ids, -1)
    w = w / w.sum(-1, keepdims=True)
    full = jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None],
                                ids].set(w)
    gate, up = jnp.split(jnp.einsum("sc,ech->seh", x, w1), 2, -1)
    a = jax.nn.silu(gate) * up * full[:, first:first + held, None]
    return jnp.einsum("seh,ehc->sc", a, w2)


# (sizes, k, the router weight's scale, rtol, atol over the largest
# element): the toy shape takes ``lax.ragged_dot``; S*k = 2048 rows of
# C = 128 with H = 128 take the Pallas kernels, whose operands are
# bfloat16
SHAPES = {"ragged_dot": (dict(), 3, 1.0, 1e-4, 0.0),
          "kernel": (dict(S=512, C=128, H=128), 4, 0.1, 2e-2, 2e-2)}


def _takes_the_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _assert_close(got, want, rtol, atol_of_max):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(
        got, want, rtol=rtol, atol=1e-5 + atol_of_max * np.abs(want).max())


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("recompute", [False, True])
def test_moe_ffn_gradients_match_the_dense_layer(recompute, shape):
    sizes, k, scale, rtol, atol = SHAPES[shape]
    x, wg, w1, w2 = _weights(4, **sizes)
    first, held = 2, 4
    args = (x, scale * wg, w1[first:first + held], w2[first:first + held])
    proj = jnp.asarray(np.random.RandomState(5).randn(*x.shape), jnp.float32)

    def layer(*a):
        return (moe_ffn(*a, experts_per_token=k, first_expert=first,
                        activation="silu", gated=True,
                        recompute=recompute)[0] * proj).sum()
    assert _takes_the_kernel(layer, *args) == (shape == "kernel")
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(layer, argnums=(0, 1, 2, 3)))(*args)
        want = jax.grad(lambda *a: (dense_layer(*a, k, first) * proj).sum(),
                        argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        _assert_close(g, w, rtol, atol)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_layers_predicate_is_the_products(shape):
    """Where the grouped products take the Pallas kernels so does every
    pass around them, forward and backward: nothing but a kernel writes
    a buffer of S*k rows of features, and ``ops/moe.py`` gathers only
    numbers (a pair's weight).  Where they take ``lax.ragged_dot`` the
    layer runs no kernel at all."""
    sizes, k, scale, _rtol, _atol = SHAPES[shape]
    x, wg, w1, w2 = _weights(4, **sizes)
    args = (x, scale * wg, w1[2:6], w2[2:6])

    def layer(*a):
        return moe_ffn(*a, experts_per_token=k, first_expert=2,
                       activation="silu", gated=True)[0].sum()
    eqns = list(_equations(jax.make_jaxpr(
        jax.grad(layer, argnums=(0, 1, 2, 3)))(*args).jaxpr))
    names = [e.primitive.name for e in eqns]
    pair_rows = x.shape[0] * k
    wide = [e.primitive.name for e in eqns for v in e.outvars
            if getattr(v.aval, "ndim", 0) == 2
            and v.aval.shape[0] == pair_rows and v.aval.shape[1] > 1
            and e.primitive.name not in ("pallas_call", "pjit", "jit",
                                         "custom_vjp_call",
                                         "custom_vjp_call_jaxpr")]
    if shape == "kernel":
        # dispatch, product, activation, product, combine; combine's
        # transpose, two gradients a product, the activation's, dispatch's
        assert names.count("pallas_call") == 5 + 7
        assert "ragged_dot_general" not in names
        assert wide == []
    else:
        assert "pallas_call" not in names
        assert names.count("ragged_dot_general") >= 2


@pytest.mark.parametrize("recompute", [False, True])
def test_the_rows_past_the_last_held_pair_may_hold_anything(
        recompute, monkeypatch):
    """No pass visits the rows past the last held pair: fill them with
    NaN in every pair buffer, forward and backward (the gathered rows,
    both products, the activation, the gradients of all of them and the
    weights' gradient taken on the pair side), and the layer's output
    and gradients are what they were."""
    from mxnet_tpu.ops import moe
    calls = []

    def poison(a, live):
        row = jnp.arange(a.shape[0]).reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(row < live, a, jnp.nan)

    def poisoned(name, live_of, outputs=None):
        """``moe.<name>`` with the pair buffers among its results
        poisoned; ``live_of`` reads the number of live rows off its
        arguments."""
        real = getattr(moe, name)

        def fn(*args, **kw):
            out, live = real(*args, **kw), live_of(*args, **kw)
            calls.append(name)
            if not isinstance(out, tuple):
                return poison(out, live)
            return tuple(poison(o, live) if outputs is None or i in outputs
                         else o for i, o in enumerate(out))
        monkeypatch.setattr(moe, name, fn)
    poisoned("rows_of_tokens", lambda x, tok, live, **kw: live)
    poisoned("grouped_matmul", lambda lhs, rhs, sizes: sizes.sum())
    poisoned("expert_activation", lambda h, live, *a, **kw: live)
    poisoned("grouped_matmul_grads",
             lambda lhs, rhs, sizes, g: sizes.sum(), outputs=(0,))

    x, wg, w1, w2 = _weights(7, S=512, C=128, H=128)
    first, held, k = 5, 2, 4            # a quarter of the pairs held here
    args = (x, 0.1 * wg, w1[first:first + held], w2[first:first + held])
    proj = jnp.asarray(np.random.RandomState(8).randn(*x.shape), jnp.float32)

    def layer(*a):
        out, rows = moe_ffn(*a, experts_per_token=k, first_expert=first,
                            activation="silu", gated=True,
                            recompute=recompute)
        return (out * proj).sum(), (out, rows)
    assert _takes_the_kernel(lambda *a: layer(*a)[0], *args)
    with jax.default_matmul_precision("highest"):
        got, (out, rows) = jax.jit(jax.grad(
            layer, argnums=(0, 1, 2, 3), has_aux=True))(*args)
        want = jax.grad(lambda *a: (dense_layer(*a, k, first) * proj).sum(),
                        argnums=(0, 1, 2, 3))(*args)
        want_out = dense_layer(*args, k, first)
    assert set(calls) == {"rows_of_tokens", "grouped_matmul",
                          "expert_activation", "grouped_matmul_grads"}
    assert 0 < float(rows.sum()) < 0.5 * x.shape[0] * k
    _assert_close(out, want_out, 2e-2, 2e-2)
    for g, w in zip(got, want):
        _assert_close(g, w, 2e-2, 2e-2)


def test_moe_ffn_refuses_a_share_outside_the_router():
    x, wg, w1, w2 = _weights(6)
    with pytest.raises(mx.base.MXNetError):
        moe_ffn(x, wg, w1[:4], w2[:4], first_expert=6)
    with pytest.raises(mx.base.MXNetError):
        moe_ffn(x, wg, w1, w2, activation="tanh")
    with pytest.raises(mx.base.MXNetError):
        MoEFFN(8, 16, 8, experts_held=4, first_expert=6)


def test_gluon_moe_block_eager_hybrid_parity():
    mx.random.seed(0)
    layer = MoEFFN(units=8, hidden_size=16, num_experts=4,
                   experts_per_token=2, gated=True, activation="silu")
    layer.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(2).randn(2, 6, 8)
                 .astype(np.float32))
    out_e = layer(x)
    rows_e = layer.rows_routed.data().asnumpy().copy()
    layer.hybridize()
    out_h = layer(x)
    np.testing.assert_allclose(out_e.asnumpy(), out_h.asnumpy(),
                               rtol=1e-5, atol=1e-6)
    # the cumulative count: 12 tokens x 2 experts a call, eager or traced
    assert rows_e.sum() == 24
    np.testing.assert_array_equal(layer.rows_routed.data().asnumpy(),
                                  2 * rows_e)


@pytest.mark.parametrize("k", [1, 2])
def test_moe_trains_with_gradient(k):
    # tiny regression: MoE layer + residual learns a mapping
    mx.random.seed(1)
    layer = MoEFFN(units=4, hidden_size=8, num_experts=2,
                   experts_per_token=k, activation="relu")
    layer.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(layer.collect_params(), "adam",
                            {"learning_rate": 5e-3})
    rng = np.random.RandomState(3)
    X = rng.randn(64, 4).astype(np.float32)
    Y = np.tanh(X[:, ::-1].copy()).astype(np.float32)
    first = None
    for i in range(120):
        x, y = nd.array(X), nd.array(Y)
        with autograd.record():
            loss = ((layer(x) + x - y) ** 2).mean()
        loss.backward()
        trainer.step(64)
        if i == 0:
            first = float(loss.asscalar())
    last = float(loss.asscalar())
    assert last < 0.5 * first, (first, last)


def test_expert_parallel_sharded_step():
    # dp=2 x ep=2 mesh on the virtual 8-device CPU backend: the expert
    # dim must actually shard over ep, and training steps must run and
    # carry the count of routed rows in their state
    devices = jax.devices()[:4]
    mesh = parallel.make_mesh(dp=2, tp=1, sp=1, ep=2, devices=devices)
    assert mesh.shape["ep"] == 2

    mx.random.seed(2)

    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.moe = MoEFFN(units=8, hidden_size=16, num_experts=4,
                                  experts_per_token=2, gated=True,
                                  activation="silu")

        def hybrid_forward(self, F, x):
            return self.moe(x) + x

    net = Net()
    net.initialize(mx.init.Xavier())

    x = nd.array(np.random.RandomState(4).randn(8, 6, 8)
                 .astype(np.float32))
    y = nd.array(np.random.RandomState(5).randn(8, 6, 8)
                 .astype(np.float32))
    trainer = parallel.ShardedTrainer(
        net, lambda out, y: ((out - y) ** 2).mean(), mesh,
        optimizer="adamw", optimizer_params={"learning_rate": 1e-3},
        example_inputs=(x,), n_labels=1)
    for _ in range(2):
        loss = trainer.step(x, y)
    assert np.isfinite(float(jax.device_get(loss)))
    # the expert weights and their counts really live sharded over ep
    for leaf in ("expert_w1", "expert_w2", "rows_routed"):
        name = [n for n in trainer.params if n.endswith(leaf)]
        assert name, list(trainer.params)[:8]
        assert trainer.params[name[0]].sharding.spec[0] == "ep", leaf
    rows = np.asarray(trainer.params[name[0]])
    assert rows.sum() == 2 * 48 * 2, rows     # steps x tokens x k


def test_expert_rules_on_mesh_without_ep_axis():
    # a hand-built 3-axis mesh: 'ep' rules degrade to replication, not
    # KeyError
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.sharding import MEGATRON_RULES
    devs = np.array(jax.devices()[:4]).reshape(2, 2, 1)
    mesh = Mesh(devs, axis_names=("dp", "tp", "sp"))
    shardings = MEGATRON_RULES.shardings(
        mesh, {"net_moe_expert_w1": jnp.zeros((4, 8, 16))})
    spec = shardings["net_moe_expert_w1"].spec
    assert spec[0] is None         # ep dropped


def test_make_mesh_ep_backcompat():
    # existing 3-axis call sites keep working; default ep axis size 1
    mesh = parallel.make_mesh(dp=2, tp=2, sp=2,
                              devices=jax.devices()[:8])
    assert mesh.shape["ep"] == 1
    assert mesh.shape["dp"] == 2


# ------------------------------------------- the router told to score by sigmoid
def sigmoid_layer(x, wg, bias, w1, w2, s1, s2, k, first, scale):
    """Sigmoid scores, the k largest of score + bias, the scores at
    those ids renormalised and scaled; relu^2 experts, not gated; plus
    the shared expert: what ``moe_ffn`` computes when told so,
    differentiable."""
    held = w1.shape[0]
    s = jax.nn.sigmoid(x @ wg)
    ids = jnp.argsort(-(s + bias), -1)[:, :k]
    w = jnp.take_along_axis(s, ids, -1)
    w = scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    full = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], ids].set(w)
    relu2 = lambda a: jnp.square(jax.nn.relu(a))            # noqa: E731
    a = relu2(jnp.einsum("sc,ech->seh", x, w1)) \
        * full[:, first:first + held, None]
    return jnp.einsum("seh,ehc->sc", a, w2) + relu2(x @ s1) @ s2


def test_sigmoid_route_chooses_by_the_bias_and_weighs_without_it():
    """Expert 3 scores lowest and is chosen first because the bias says
    so; its weight is still its own (small) score's share, so the order
    of the choice is not the order of the weights."""
    x = jnp.eye(4, dtype=jnp.float32)[:1]                   # one token
    wg = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -3.0]] + [[0.0] * 5] * 3)
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0, 0.0])
    scores = np.asarray(jax.nn.sigmoid(wg[0]))
    w, ids = moe_topk_route(x, wg, bias, experts_per_token=3,
                            scoring="sigmoid", scale=2.5)
    assert np.asarray(ids).tolist() == [[3, 0, 1]]
    want = scores[[3, 0, 1]] / scores[[3, 0, 1]].sum() * 2.5
    np.testing.assert_allclose(np.asarray(w)[0], want, rtol=1e-6)
    assert np.argsort(-np.asarray(w)[0]).tolist() == [1, 2, 0]
    np.testing.assert_allclose(np.asarray(w).sum(), 2.5, rtol=1e-6)
    # without the bias the choice follows the scores
    _w, plain = moe_topk_route(x, wg, None, experts_per_token=3,
                               scoring="sigmoid")
    assert np.asarray(plain).tolist() == [[0, 1, 2]]
    # ties still go to the lower id, and softmax is what it was
    _w, tied = moe_topk_route(x, jnp.zeros((4, 5)), jnp.zeros((5,)),
                              experts_per_token=2, scoring="sigmoid")
    assert np.asarray(tied).tolist() == [[0, 1]]
    with pytest.raises(mx.base.MXNetError, match="scoring"):
        moe_topk_route(x, wg, experts_per_token=1, scoring="tanh")


@pytest.mark.parametrize("shape", ["ragged_dot", "kernel", "kernel_64"])
@pytest.mark.parametrize("recompute", [False, True])
def test_sigmoid_relu2_shared_layer_matches_the_dense_layer(recompute, shape):
    """The layer Nemotron-3 tells it to be, output and gradients; at a
    width that is a multiple of 64 and not of 128 ("kernel_64": H = 192,
    as 1856 = 29 x 64) the products and the movers still take the
    Pallas kernels."""
    sizes, k, scale, rtol, atol = {
        **SHAPES, "kernel_64": (dict(S=512, C=128, H=192), 4, 0.1, 2e-2,
                                2e-2)}[shape]
    x, wg, w1, w2 = _weights(11, gated=False, **sizes)
    rng = np.random.RandomState(12)
    C, H = w1.shape[1], w1.shape[2]
    bias = jnp.asarray(rng.randn(wg.shape[1]).astype(np.float32))
    s1 = jnp.asarray(0.3 * rng.randn(C, 2 * H).astype(np.float32))
    s2 = jnp.asarray(0.3 * rng.randn(2 * H, C).astype(np.float32))
    first, held = 2, 4
    args = (x, scale * wg, w1[first:first + held], w2[first:first + held],
            bias, s1, s2)
    proj = jnp.asarray(rng.randn(*x.shape), jnp.float32)

    def layer(*a):
        out, rows = moe_ffn(*a, experts_per_token=k, first_expert=first,
                            activation="relu2", gated=False,
                            recompute=recompute, scoring="sigmoid",
                            route_scale=2.5, shared_expert=True)
        return (out * proj).sum(), (out, rows)

    def dense(x, wg, w1, w2, bias, s1, s2):
        return sigmoid_layer(x, wg, bias, w1, w2, s1, s2, k, first, 2.5)
    assert _takes_the_kernel(lambda *a: layer(*a)[0], *args) \
        == (shape != "ragged_dot")
    wrt = (0, 1, 2, 3, 5, 6)
    with jax.default_matmul_precision("highest"):
        got, (out, rows) = jax.jit(jax.grad(layer, argnums=wrt,
                                            has_aux=True))(*args)
        want = jax.grad(lambda *a: (dense(*a) * proj).sum(),
                        argnums=wrt)(*args)
        want_out = dense(*args)
        d_bias = jax.grad(lambda *a: layer(*a)[0], argnums=4)(*args)
    _assert_close(out, want_out, rtol, atol)
    for g, w in zip(got, want):
        _assert_close(g, w, rtol, atol)
    assert float(jnp.abs(d_bias).max()) == 0.0     # it chooses, no more
    assert 0 < float(rows.sum()) < x.shape[0] * k


def test_relu2_joins_the_kernel_activations():
    from mxnet_tpu.ops.pallas_kernels import expert_activation, relu2
    h = jnp.asarray(np.random.RandomState(0).randn(1024, 256), jnp.float32)
    g = jnp.asarray(np.random.RandomState(1).randn(1024, 256), jnp.float32)
    live = jnp.int32(700)
    assert _takes_the_kernel(lambda h: expert_activation(
        h, live, relu2, False), h)
    want = np.maximum(np.asarray(h), 0) ** 2
    want_grad = 2 * np.maximum(np.asarray(h), 0) * np.asarray(g)
    a = expert_activation(h, live, relu2, False)
    da = expert_activation(h, live, relu2, False, g=g)
    np.testing.assert_allclose(np.asarray(a)[:700], want[:700], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(da)[:700], want_grad[:700],
                               rtol=1e-6)
    # a width of 1.5 x 128 (as 1856 = 14.5 x 128): the products take the
    # kernels, the activation stays in jnp over all rows (the chip's
    # kernel is slower there than XLA: PERF.md, PR 37)
    assert not _takes_the_kernel(lambda h: expert_activation(
        h, live, relu2, False), h[:, :192])
    np.testing.assert_allclose(
        np.asarray(expert_activation(h[:, :192], live, relu2, False,
                                     g=g[:, :192])), want_grad[:, :192],
        rtol=1e-6)


def test_gluon_moe_block_with_sigmoid_router_and_shared_expert():
    mx.random.seed(5)
    layer = MoEFFN(units=8, hidden_size=16, num_experts=8,
                   experts_per_token=3, experts_held=4, first_expert=2,
                   activation="relu2", scoring="sigmoid", route_scale=2.5,
                   shared_hidden_size=12)
    layer.initialize(mx.init.Normal(0.5))
    params = {n.split("_", 1)[1]: p for n, p in
              layer.collect_params().items()}
    assert params["route_bias"].shape == (8,)
    assert params["route_bias"].grad_req == "null"      # no weight
    assert params["shared_w1"].shape == (8, 12)         # not gated
    assert params["shared_w2"].shape == (12, 8)
    bias = np.random.RandomState(3).randn(8).astype(np.float32)
    params["route_bias"].set_data(nd.array(bias))
    x = np.random.RandomState(2).randn(2, 6, 8).astype(np.float32)
    leaves = {k: p.data()._data for k, p in params.items()}
    with jax.default_matmul_precision("highest"):
        out = layer(nd.array(x)).asnumpy()
        want = sigmoid_layer(
            jnp.asarray(x.reshape(12, 8)), leaves["gate_weight"],
            leaves["route_bias"], leaves["expert_w1"], leaves["expert_w2"],
            leaves["shared_w1"], leaves["shared_w2"], 3, 2, 2.5)
    np.testing.assert_allclose(out.reshape(12, 8), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    layer.hybridize()
    np.testing.assert_allclose(layer(nd.array(x)).asnumpy(), out, rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(mx.base.MXNetError, match="scoring"):
        MoEFFN(8, 16, 8, scoring="tanh")

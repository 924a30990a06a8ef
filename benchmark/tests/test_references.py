"""Both plain references against the package's symbols at toy size on
the CPU: same seeded weights in, same numbers out (float32 round-off)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, weights


def _forward(net, args, aux=None, **inputs):
    """Outputs of the package's executor for one forward pass."""
    import mxnet_tpu as mx
    arrays = {k: mx.nd.array(np.asarray(v)) for k, v in args.items()}
    arrays.update({k: mx.nd.array(v) for k, v in inputs.items()})
    exe = net.bind(mx.cpu(), arrays, aux_states={
        k: mx.nd.array(np.asarray(v)) for k, v in (aux or {}).items()},
        grad_req="null")
    return [o.asnumpy() for o in exe.forward(is_train=bool(aux))]


def test_resnet_reference_equals_the_symbol():
    import mxnet_tpu as mx
    cell = harness.Cell("resnet50.fit-b128", rehearse=True)
    cfg, ref = cell.config, cell.module("reference")
    from mxnet_tpu.models.resnet import get_symbol
    net = get_symbol(**cfg["builder_args"])
    shape = (4, 3, cfg["image"], cfg["image"])
    arg_shapes, _, aux_shapes = net.infer_shape(data=shape)
    have = {n: tuple(s) for n, s in zip(net.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    assert have == {k: tuple(v) for k, v in
                    ref.param_shapes(cfg).items()}
    assert dict(zip(net.list_auxiliary_states(),
                    map(tuple, aux_shapes))) == ref.aux_shapes(cfg)
    params = weights.draw(have, 5, gain=cfg["init_gain"])
    aux = weights.draw(ref.aux_shapes(cfg), 5)
    rng = np.random.default_rng(5)
    x = rng.random(shape, dtype=np.float32)
    y = rng.integers(0, cfg["num_classes"], 4).astype(np.float32)
    (prob,) = _forward(net, params, aux, data=x, softmax_label=y)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.forward(params, jnp.asarray(x), cfg))
        loss = float(ref.loss(params, jnp.asarray(x), jnp.asarray(y),
                              cfg))
    np.testing.assert_allclose(
        prob, np.asarray(jax.nn.softmax(logits, -1)), rtol=2e-4,
        atol=1e-6)
    mine = -np.log(prob[np.arange(4), y.astype(int)]).mean()
    assert abs(mine - loss) <= 1e-5 * abs(loss)


def test_resnet_bottleneck_layout_matches_the_symbol():
    import mxnet_tpu as mx
    cell = harness.Cell("resnet50.fit-b128")
    cfg, ref = cell.config, cell.module("reference")
    from mxnet_tpu.models.resnet import get_symbol
    net = get_symbol(**cfg["builder_args"])
    arg_shapes, _, _ = net.infer_shape(data=(2, 3, 224, 224))
    have = {n: tuple(s) for n, s in zip(net.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    assert have == {k: tuple(v) for k, v in
                    ref.param_shapes(cfg).items()}
    assert ref.layout(cfg) == (cfg["stage_units"], cfg["stage_filters"],
                               True)


def test_lm_reference_equals_the_symbol():
    from mxnet_tpu.models.transformer_lm import get_symbol
    cell = harness.Cell("lm2048.serve-chat-backlog", rehearse=True)
    cfg, ref = cell.config, cell.module("reference")
    t = 24
    net = get_symbol(seq_len=t, **{k: cfg[k] for k in (
        "num_layers", "num_hidden", "num_heads", "vocab_size")})
    arg_shapes, _, _ = net.infer_shape(data=(1, t),
                                       softmax_label=(1, t))
    have = {n: tuple(s) for n, s in zip(net.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    assert have == {k: tuple(v) for k, v in
                    ref.param_shapes(cfg).items()}
    params = weights.draw(have, 9, gain=cfg["init_gain"])
    tokens = np.random.default_rng(9).integers(
        0, cfg["vocab_size"], (1, t))
    (prob,) = _forward(net, params, data=tokens.astype(np.float32),
                       softmax_label=np.zeros((1, t), np.float32))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.logits(params, jnp.asarray(tokens[0]),
                                       cfg))
    np.testing.assert_allclose(
        prob, np.asarray(jax.nn.softmax(logits, -1)), rtol=2e-4,
        atol=1e-7)


def test_served_gaps_reads_the_right_rows():
    cell = harness.Cell("lm2048.serve-chat-backlog", rehearse=True)
    cfg, ref = cell.config, cell.module("reference")
    params = weights.draw(ref.param_shapes(cfg), 3,
                          gain=cfg["init_gain"])
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg["vocab_size"], 10)
    # greedy continuation by the reference itself: every gap is 0
    seq = list(prompt)
    for _ in range(5):
        z = ref.logits(params, jnp.asarray(seq), cfg)
        seq.append(int(jnp.argmax(z[-1])))
    served = np.asarray(seq[10:], np.int32)
    padded = np.zeros(32, np.int32)
    padded[:14] = seq[:14]
    pad_served = np.zeros(8, np.int32)
    pad_served[:5] = served
    gap, best = ref.served_gaps(params, jnp.asarray(padded),
                                np.int32(9), jnp.asarray(pad_served),
                                cfg)
    assert np.asarray(gap)[:5].max() <= 1e-5
    assert list(np.asarray(best)[:5]) == list(served)
    # a wrong token lies below the best by what the logits say
    wrong = pad_served.copy()
    wrong[2] = (served[2] + 1) % cfg["vocab_size"]
    gap2, _ = ref.served_gaps(params, jnp.asarray(padded), np.int32(9),
                              jnp.asarray(wrong), cfg)
    assert np.asarray(gap2)[2] > 0


def test_weights_are_a_function_of_the_seed():
    shapes = {"a_weight": (8, 4), "a_gamma": (8,), "a_bias": (8,)}
    a, b = weights.draw(shapes, 2**33 + 1), weights.draw(shapes,
                                                         2**33 + 1)
    c = weights.draw(shapes, 1)        # the low word alone would collide
    for k in shapes:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["a_weight"]),
                              np.asarray(c["a_weight"]))
    assert abs(float(np.asarray(a["a_gamma"]).mean()) - 1) < 0.2
    with pytest.raises(ValueError):
        weights.draw({"mystery": (2,)}, 0)
    with pytest.raises(ValueError):
        weights.seed_words(-1)

"""The real-size programs of the four cells, compiled for a described
``v5e:2x2`` — no chip, nothing runs, a compile that passes is not a chip
run.  It shows what the chip's compiler would refuse (a kernel, a
program that does not fit 16 GB) before any chip time is spent.  The
topology is described inside a module fixture, in this one file."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import harness

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip("cannot describe a v5e topology: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernel eligibility as it answers on a TPU (the probe sees this
    CPU): steered here, in the test, not by an option of the program."""
    from mxnet_tpu.pallas_ops import dispatch
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)


def _memory(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("lq", [1, 32])
def test_lm2048_paged_programs_fit_one_chip(topo, on_tpu, lq):
    """Decode (lq=1) and prefill-chunk (lq=32) programs of the
    ``lm2048`` deployment: 16 layers, 32 slots, the full pool."""
    from mxnet_tpu.models.transformer_lm import lm_spec, paged_step_apply
    from mxnet_tpu.serving.program_store import sample_tokens
    cell = harness.Cell("lm2048.serve-chat-backlog")
    cfg, dep = cell.config, cell.config["deploy"]
    spec = lm_spec(**{k: cfg[k] for k in ("num_layers", "num_hidden",
                                          "num_heads", "vocab_size")})
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    bb, bs = dep["batch_buckets"][-1], dep["kv_block"]
    width = -(-dep["kv_max"] // bs)
    blocks = bb * width + 1
    dh = spec["num_hidden"] // spec["num_heads"]
    pool = sds((spec["num_layers"], spec["num_heads"], blocks * bs, dh))
    params = {k: sds(v) for k, v in
              cell.module("reference").param_shapes(cfg).items()}

    def fn(params, pk, pv, tables, tokens, positions, valid, keys,
           temps, top_ks, do):
        logits, pk, pv = paged_step_apply(params, pk, pv, tables, tokens,
                                          positions, valid, spec, bs)
        toks, carry = sample_tokens(logits, keys, temps, top_ks)
        return toks, pk, pv, jnp.where(do[:, None], carry, keys)

    compiled = jax.jit(fn, donate_argnums=(1, 2, 7)).lower(
        params, pool, pool, sds((bb, width), jnp.int32),
        sds((bb, lq), jnp.int32), sds((bb,), jnp.int32),
        sds((bb,), jnp.int32), sds((bb, 2), jnp.uint32), sds((bb,)),
        sds((bb,), jnp.int32), sds((bb,), jnp.bool_)).compile()
    total = _memory(compiled)
    print("lm2048 lq=%d: %.2f GB, kernels %d" % (
        lq, total / 1e9, compiled.as_text().count("tpu_custom_call")))
    assert total < HBM
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chips,batch", [(1, 128), (4, 512)])
def test_resnet50_step_fits(topo, on_tpu, chips, batch):
    """The fused ``Module.fit`` step of ResNet-50 at the cells' batches,
    on one chip and data-parallel over the four of the host."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import spmd
    cell = harness.Cell("resnet50.fit-b128")
    cfg = cell.config
    net = mx.models.resnet(**{k: v for k, v in
                              cfg["builder_args"].items()
                              if k != "image_shape"},
                           image_shape=(3, cfg["image"], cfg["image"]))
    mesh = Mesh(np.array(topo.devices[:chips]), ("dp",))
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    data = {"data": (batch, 3, cfg["image"], cfg["image"])}
    label = {"softmax_label": (batch,)}
    names = [n for n in net.list_arguments()
             if n not in ("data", "softmax_label")]
    opt = mx.optimizer.create(
        "sgd", sym=net, param_idx2name=dict(enumerate(names)),
        learning_rate=0.01, momentum=0.9, wd=1e-4,
        rescale_grad=1.0 / batch)
    prog = spmd.get_step_program(
        net, mesh, data, label, optimizer=opt,
        param_shardings={n: whole for n in names})
    arg_shapes, _, aux_shapes = net.infer_shape(**data, **label)

    def sds(shape, sharding=whole, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                    sharding=sharding)

    shapes = dict(zip(net.list_arguments(), arg_shapes))
    params = {n: sds(shapes[n]) for n in names}
    state = {n: (sds(shapes[n]),) for n in names}
    aux = {n: sds(s) for n, s in zip(net.list_auxiliary_states(),
                                     aux_shapes)}
    batch_in = {"data": sds(data["data"], rows),
                "softmax_label": sds(label["softmax_label"], rows)}
    hyper = sds((len(names),))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
    compiled = prog.train_step.lower(params, state, aux, batch_in,
                                     hyper, hyper, key).compile()
    total = _memory(compiled)
    text = compiled.as_text()
    print("resnet50 x%d b%d: %.2f GB a chip, all-reduce: %s" % (
        chips, batch, total / 1e9, "all-reduce" in text))
    assert total < HBM
    assert ("all-reduce" in text) == (chips > 1)

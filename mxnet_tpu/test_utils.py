"""Test harness utilities.

Reference: ``python/mxnet/test_utils.py`` — ``check_numeric_gradient``
(finite differences vs executor.backward with random projections, :360),
``check_symbolic_forward/backward`` (:473/:526 vs numpy references),
``check_consistency`` (:676 — same symbol under N (ctx, dtype) combos),
``check_speed`` (:602), ``default_context``, ``assert_almost_equal``.
"""
from __future__ import annotations

import time

import numpy as np

from . import ndarray as nd
from .context import Context, cpu, current_context
from .ndarray import NDArray

_default_ctx = [None]


def default_context():
    return _default_ctx[0] or current_context()


def set_default_context(ctx):
    _default_ctx[0] = ctx


def default_dtype():
    return np.float32


def rand_shape_2d(dim0=10, dim1=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_ndarray(shape, ctx=None, dtype="float32"):
    return nd.array(np.random.uniform(-1, 1, shape), ctx=ctx, dtype=dtype)


def random_arrays(*shapes):
    arrays = [np.random.randn(*s).astype(default_dtype()) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def same(a, b):
    return np.array_equal(a, b)


def reldiff(a, b):
    diff = np.sum(np.abs(a - b))
    norm = np.sum(np.abs(a)) + np.sum(np.abs(b))
    if diff == 0:
        return 0
    return diff / norm


def fetch_sync(outs):
    """Force TRUE device completion by fetching dependent bytes to host.

    Dispatch is asynchronous: a clock read after the last enqueue
    measures the host's dispatch rate, which once read as resnet-50
    "MFU 2.2" — 220% of chip peak.  A host fetch of bytes that
    data-depend on the computation cannot return before the device is
    done, on any backend; every timed window starts and stops on one
    (benchmark_score.py, docs/perf.md "measuring honestly")."""
    import jax
    leaves = jax.tree_util.tree_leaves(outs)
    for leaf in leaves[:1]:
        data = getattr(leaf, "_data", leaf)  # NDArray or jax array
        np.asarray(data)


def smoke_mlp(num_hidden=64, num_classes=10):
    """Tiny 2-layer softmax MLP shared by the smoke harnesses
    (tools/step_profile.py, serving/loadgen.py,
    tests/test_input_staging.py) so the smoke protocol can't drift
    between the CI and test call sites."""
    from . import symbol as sym
    data = sym.Variable("data")
    h = sym.Activation(
        sym.FullyConnected(data, num_hidden=num_hidden, name="fc1"),
        act_type="relu")
    return sym.SoftmaxOutput(
        sym.FullyConnected(h, num_hidden=num_classes, name="fc2"),
        name="softmax")


class DelayedIter:
    """DataIter wrapper injecting a fixed per-batch host latency into
    ``next()`` — the faultinject-delay pattern applied to the input
    pipeline, standing in for slow decode/augmentation so input-staging
    overlap is measurable on one CPU host (tests/test_input_staging.py,
    tools/step_profile.py)."""

    def __init__(self, source, delay=0.02):
        self._source = source
        self.delay = float(delay)

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self._source)   # raises StopIteration at epoch end
        time.sleep(self.delay)
        return batch

    next = __next__

    def reset(self):
        self._source.reset()

    def __getattr__(self, name):
        return getattr(self._source, name)


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-8, names=("a", "b")):
    a = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, NDArray) else np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                               err_msg="%s vs %s" % names)


def _parse_location(sym, location, ctx):
    if isinstance(location, dict):
        return {k: (v if isinstance(v, NDArray) else nd.array(v, ctx=ctx))
                for k, v in location.items()}
    return {k: (v if isinstance(v, NDArray) else nd.array(v, ctx=ctx))
            for k, v in zip(sym.list_arguments(), location)}


def _parse_aux_states(sym, aux_states, ctx):
    if aux_states is None:
        return None
    if isinstance(aux_states, dict):
        return {k: (v if isinstance(v, NDArray) else nd.array(v, ctx=ctx))
                for k, v in aux_states.items()}
    return {k: (v if isinstance(v, NDArray) else nd.array(v, ctx=ctx))
            for k, v in zip(sym.list_auxiliary_states(), aux_states)}


def numeric_grad(executor, location, aux_states=None, eps=1e-4,
                 use_forward_train=True):
    """Finite-difference gradients of executor's scalar-summed output."""
    approx_grads = {}
    for k, v in location.items():
        old_value = v.asnumpy()
        flat = old_value.reshape(-1)
        grad = np.zeros_like(flat)
        for i in range(flat.size):
            fv = flat[i]
            flat[i] = fv + eps / 2
            executor.forward(is_train=use_forward_train,
                             **{k: nd.array(old_value.reshape(v.shape))})
            f_peps = sum(out.asnumpy().sum() for out in executor.outputs)
            flat[i] = fv - eps / 2
            executor.forward(is_train=use_forward_train,
                             **{k: nd.array(old_value.reshape(v.shape))})
            f_neps = sum(out.asnumpy().sum() for out in executor.outputs)
            flat[i] = fv
            grad[i] = (f_peps - f_neps) / eps
        approx_grads[k] = grad.reshape(v.shape)
    return approx_grads


def check_numeric_gradient(sym, location, aux_states=None,
                           numeric_eps=1e-3, rtol=1e-2, atol=None,
                           grad_nodes=None, use_forward_train=True,
                           ctx=None):
    """Verify executor.backward against finite differences with a random
    projection head (reference test_utils.py:360)."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux = _parse_aux_states(sym, aux_states, ctx)
    if grad_nodes is None:
        grad_nodes = [k for k in location]

    input_shape = {k: v.shape for k, v in location.items()}
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(**input_shape)

    # random-projection head makes the output scalar-summable with a
    # well-spread gradient
    from . import symbol as S
    proj = S.Variable("__random_proj")
    out = S.make_loss(S.sum(sym * proj), name="__loss")

    arg_names = out.list_arguments()
    loc = dict(location)
    proj_arr = nd.array(np.random.uniform(-1, 1, out_shapes[0]), ctx=ctx)
    loc["__random_proj"] = proj_arr

    grads = {k: nd.zeros(v.shape, ctx=ctx) for k, v in loc.items()}
    reqs = {k: ("write" if k in grad_nodes or k == "__random_proj"
                else "null") for k in arg_names}
    executor = out.bind(ctx, loc, args_grad=grads, grad_req=reqs,
                        aux_states=aux)

    executor.forward(is_train=True)
    executor.backward()
    symbolic_grads = {k: executor.grad_dict[k].asnumpy()
                      for k in grad_nodes}

    # numeric: vary each grad_node entry, objective = sum(out * proj)
    numeric = {}
    for name in grad_nodes:
        v = loc[name]
        old = v.asnumpy()
        flat = old.reshape(-1).copy()
        grad = np.zeros_like(flat)
        for i in range(flat.size):
            orig = flat[i]
            for sign, store in ((+1, "p"), (-1, "m")):
                flat[i] = orig + sign * numeric_eps / 2
                v._data = nd.array(flat.reshape(old.shape), ctx=ctx)._data
                executor.forward(is_train=use_forward_train)
                s = executor.outputs[0].asnumpy().sum()
                if sign > 0:
                    f_p = s
                else:
                    f_m = s
            flat[i] = orig
            grad[i] = (f_p - f_m) / numeric_eps
        v._data = nd.array(old, ctx=ctx)._data
        numeric[name] = grad.reshape(old.shape)

    for name in grad_nodes:
        atol_ = atol if atol is not None else rtol
        np.testing.assert_allclose(
            symbolic_grads[name], numeric[name], rtol=rtol, atol=atol_,
            err_msg="NUMERICAL_%s vs BACKWARD_%s" % (name, name))


def check_symbolic_forward(sym, location, expected, rtol=1e-5, atol=None,
                           aux_states=None, ctx=None):
    """Compare executor forward against numpy expected outputs
    (reference test_utils.py:473)."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux = _parse_aux_states(sym, aux_states, ctx)
    executor = sym.bind(ctx, location, aux_states=aux, grad_req="null")
    executor.forward(is_train=False)
    outputs = [x.asnumpy() for x in executor.outputs]
    for output, expect in zip(outputs, expected):
        np.testing.assert_allclose(output, expect, rtol=rtol,
                                   atol=atol if atol is not None else rtol)
    return outputs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None):
    """Compare executor backward against numpy expected gradients
    (reference test_utils.py:526)."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux = _parse_aux_states(sym, aux_states, ctx)
    if isinstance(expected, (list, tuple)):
        expected = {k: v for k, v in zip(sym.list_arguments(), expected)}
    args_grad = {k: nd.zeros(v.shape, ctx=ctx)
                 for k, v in location.items() if k in expected}
    executor = sym.bind(ctx, location, args_grad=args_grad,
                        grad_req=grad_req, aux_states=aux)
    executor.forward(is_train=True)
    ograds = [g if isinstance(g, NDArray) else nd.array(g, ctx=ctx)
              for g in out_grads] if out_grads is not None else None
    executor.backward(ograds)
    grads = {k: v.asnumpy() for k, v in args_grad.items()}
    for name in expected:
        np.testing.assert_allclose(
            grads[name], expected[name], rtol=rtol,
            atol=atol if atol is not None else rtol,
            err_msg="EXPECTED_%s vs BACKWARD_%s" % (name, name))
    return grads


def check_speed(sym, location=None, ctx=None, N=20, grad_req=None,
                typ="whole"):
    """Time executor fwd/fwd+bwd (reference test_utils.py:602)."""
    ctx = ctx or default_context()
    if grad_req is None:
        grad_req = "write"
    if location is None:
        arg_shapes, _, _ = sym.infer_shape()
        location = {name: nd.array(np.random.normal(size=s), ctx=ctx)
                    for name, s in zip(sym.list_arguments(), arg_shapes)}
    else:
        location = {k: v if isinstance(v, NDArray) else
                    nd.array(v, ctx=ctx) for k, v in location.items()}
    grads = {k: nd.zeros(v.shape, ctx=ctx) for k, v in location.items()}
    exe = sym.bind(ctx, args=location, args_grad=grads, grad_req=grad_req)

    if typ == "whole":
        exe.forward(is_train=True)
        exe.backward()
        nd.waitall()
        tic = time.time()
        for _ in range(N):
            exe.forward_backward()
        nd.waitall()
        return (time.time() - tic) / N
    elif typ == "forward":
        exe.forward(is_train=False)
        nd.waitall()
        tic = time.time()
        for _ in range(N):
            exe.forward(is_train=False)
        nd.waitall()
        return (time.time() - tic) / N
    else:
        raise ValueError("typ can only be 'whole' or 'forward'")


def check_consistency(sym, ctx_list, scale=1.0, grad_req="write",
                      arg_params=None, aux_params=None, tol=None,
                      raise_on_err=True):
    """Run the same symbol under multiple (ctx, shapes, dtype) setups and
    compare forward/backward within dtype-scaled tolerances
    (reference test_utils.py:676)."""
    if tol is None:
        tol = {np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
               np.dtype(np.float64): 1e-5, np.dtype(np.uint8): 0,
               np.dtype(np.int32): 0}
    assert len(ctx_list) > 1

    output_points = []
    for ctx_spec in ctx_list:
        ctx_spec = dict(ctx_spec)
        ctx = ctx_spec.pop("ctx", default_context())
        type_dict = ctx_spec.pop("type_dict", {})
        exe = sym.simple_bind(ctx, grad_req=grad_req, type_dict=type_dict,
                              **ctx_spec)
        if arg_params is None:
            np.random.seed(0)
            arg_params = {}
            for name, arr in exe.arg_dict.items():
                if name.endswith("label"):
                    arg_params[name] = np.zeros(arr.shape)
                else:
                    arg_params[name] = np.random.normal(
                        size=arr.shape, scale=scale)
        for name, arr in exe.arg_dict.items():
            arr[:] = arg_params[name].astype(np.asarray(
                arr.asnumpy()).dtype)
        if aux_params is not None:
            for name, arr in exe.aux_dict.items():
                arr[:] = aux_params[name]
        exe.forward(is_train=(grad_req != "null"))
        if grad_req != "null":
            # head grads must match the executor's output dtype (a bf16
            # run needs bf16 cotangents)
            exe.backward([nd.ones(o.shape, ctx=ctx, dtype=str(o.dtype))
                          for o in exe.outputs])
        output_points.append(exe)

    base = output_points[0]
    for other in output_points[1:]:
        dtype = np.asarray(other.outputs[0].asnumpy()).dtype
        t = tol.get(np.dtype(dtype), 1e-3)
        for o1, o2 in zip(base.outputs, other.outputs):
            np.testing.assert_allclose(
                o1.asnumpy().astype(np.float64),
                o2.asnumpy().astype(np.float64), rtol=t, atol=t)
        if grad_req != "null":
            for name in base.grad_dict:
                if name in other.grad_dict:
                    np.testing.assert_allclose(
                        base.grad_dict[name].asnumpy().astype(np.float64),
                        other.grad_dict[name].asnumpy().astype(np.float64),
                        rtol=t, atol=t)
    return output_points


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    """Bind, forward, return numpy outputs."""
    ctx = ctx or default_context()
    inputs = {k: nd.array(v, ctx=ctx) for k, v in inputs.items()}
    exe = sym.bind(ctx, args=inputs, grad_req="null")
    exe.forward(is_train=is_train)
    outputs = [x.asnumpy() for x in exe.outputs]
    if len(outputs) == 1:
        outputs = outputs[0]
    return outputs

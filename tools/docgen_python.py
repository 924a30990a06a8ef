"""Generate the Python-API reference (docs/api/python/*.md) from live
docstrings.

Reference role: ``docs/api/python/{ndarray,symbol,module,io,kvstore,
optimization,model}.md`` are sphinx-autosummary pages whose body text
comes from the python docstrings at build time.  Here the pages are
emitted directly from introspection: each page has a hand-written intro
(with a runnable ```python snippet, executed by
``tests/test_doc_snippets.py``) followed by generated sections for the
listed classes and module functions.  ``--check`` exits nonzero when
the files on disk are stale (CI hook, same contract as docgen.py).

Op-backed functions (every name in the op registry) are documented in
``docs/api/ops.md`` and intentionally excluded here.

Usage::

    python tools/docgen_python.py [--check]
"""
from __future__ import annotations

import argparse
import inspect
import io
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# doc generation is platform-independent and must never touch an
# accelerator backend: pin the CPU platform before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

OUT_DIR = os.path.join(REPO, "docs", "api", "python")


_ENV_REPR = re.compile(r"<module '([^']+)' from '[^']*'>")


def _sig(obj):
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # default-arg reprs must not embed this machine's interpreter paths
    # (e.g. logger=<module 'logging' from '/usr/.../python3.X/...'>), or
    # --check fails on any host with a different python
    return _ENV_REPR.sub(r"<module '\1'>", sig)


def _doc(obj):
    d = inspect.getdoc(obj)
    return d.strip() if d else ""


def _emit_callable(out, qualname, obj, undocumented):
    out.write("#### `%s%s`\n\n" % (qualname, _sig(obj)))
    doc = _doc(obj)
    if doc:
        out.write(doc + "\n\n")
    else:
        undocumented.append(qualname)
        out.write("*(undocumented)*\n\n")


def _inherited_doc(cls, name):
    """Docstring from the nearest ancestor defining ``name`` (an
    override without its own docstring keeps the contract's doc)."""
    for base in cls.__mro__[1:]:
        if name in vars(base):
            v = vars(base)[name]
            if isinstance(v, property):
                v = v.fget
            elif isinstance(v, (classmethod, staticmethod)):
                v = v.__func__
            d = _doc(v)
            if d:
                return d
    return ""


def _emit_class(out, cls, undocumented, skip=()):
    out.write("\n### class `%s`\n\n" % cls.__name__)
    doc = _doc(cls)
    if doc:
        out.write(doc + "\n\n")
    else:
        undocumented.append(cls.__name__)
        out.write("*(undocumented)*\n\n")
    init = cls.__dict__.get("__init__")
    if init is not None and callable(init):
        out.write("Constructor: `%s%s`\n\n" % (cls.__name__, _sig(init)))
    props = [(n, v) for n, v in sorted(vars(cls).items())
             if isinstance(v, property) and not n.startswith("_")]
    if props:
        out.write("**Properties**\n\n")
        for n, v in props:
            d = (_doc(v.fget) if v.fget else "") \
                or _inherited_doc(cls, n)
            if not d:
                undocumented.append("%s.%s" % (cls.__name__, n))
                d = "*(undocumented)*"
            out.write("- `%s` — %s\n" % (n, d.splitlines()[0]))
        out.write("\n")
    meths = [(n, v) for n, v in sorted(vars(cls).items())
             if callable(v) and not n.startswith("_") and n not in skip]
    for n, v in meths:
        fn = v.__func__ if isinstance(v, (classmethod, staticmethod)) \
            else v
        qual = "%s.%s" % (cls.__name__, n)
        out.write("#### `%s%s`\n\n" % (qual, _sig(fn)))
        doc = _doc(fn) or _inherited_doc(cls, n)
        if doc:
            out.write(doc + "\n\n")
        else:
            undocumented.append(qual)
            out.write("*(undocumented)*\n\n")


def _emit_functions(out, module, names, undocumented):
    for n in names:
        _emit_callable(out, n, getattr(module, n), undocumented)


def _module_functions(module, exclude=()):
    """Public functions belonging to this module, minus op-registry
    names (documented in ops.md) and explicit excludes."""
    from mxnet_tpu.ops import registry
    ops = set(registry.list_ops())
    names = []
    for n, o in sorted(vars(module).items()):
        if n.startswith("_") or n in ops or n in exclude:
            continue
        # re-exports (e.g. registry helpers) are documented at home
        if inspect.isfunction(o) and o.__module__ == module.__name__:
            names.append(n)
    return names


# ---------------------------------------------------------------------------
# Page definitions.  intro text is part of the generated artifact; each
# ```python block below runs in CI (tests/test_doc_snippets.py).
# ---------------------------------------------------------------------------

def page_ndarray():
    import mxnet_tpu.ndarray as nd
    intro = """\
# NDArray API

Imperative n-dimensional arrays on TPU (role of the reference's
`mxnet.ndarray`; here each NDArray wraps a jax array and dispatches
through the async engine, so arithmetic enqueues device work and
`asnumpy()`/`wait_to_read()` are the synchronization points).

```python
import mxnet_tpu as mx
x = mx.nd.array([[1, 2, 3], [4, 5, 6]])
y = x + mx.nd.ones(x.shape) * 3
assert y.shape == (2, 3)
assert y.asnumpy()[0, 0] == 4.0
g = mx.nd.arange(0, 6).reshape((2, 3))
assert float((g * y).sum().asscalar()) > 0
```

Every operator in the registry is also exposed as a free function here
(`mx.nd.FullyConnected(...)`, `mx.nd.sum(...)`); see
[the operator reference](../ops.md) for those.  This page documents the
NDArray class and the non-operator module functions.
"""
    return intro, [("class", nd.NDArray)], \
        ("functions", nd, _module_functions(nd))


def page_symbol():
    import mxnet_tpu.symbol as sym
    intro = """\
# Symbol API

Declarative graph construction (role of the reference's
`mxnet.symbol`).  A Symbol records the op DAG; binding it to shapes and
devices produces an executor whose whole fused forward/backward is one
XLA program — the TPU-native replacement for the reference's per-op
graph executor.

```python
import mxnet_tpu as mx
data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
net = mx.sym.SoftmaxOutput(net, name="softmax")
assert "fc_weight" in net.list_arguments()
arg_shapes, out_shapes, _ = net.infer_shape(data=(2, 8))
assert out_shapes[0] == (2, 4)
```

Operator symbols (`mx.sym.Convolution`, ...) are documented in
[the operator reference](../ops.md).
"""
    return intro, [("class", sym.Symbol)], \
        ("functions", sym, _module_functions(sym))


def page_module():
    import mxnet_tpu.module as module
    intro = """\
# Module API

The intermediate/high-level training interface (role of the reference's
`mxnet.module`): a Module owns a bound executor group, parameters,
and optimizer state, and drives
forward/backward/update/metric across devices.  On TPU the hot path is
the fused step: bind compiles one XLA program per (shapes, devices)
signature and `fit` reuses it every batch.

```python
import numpy as np
import mxnet_tpu as mx
X = np.random.randn(64, 10).astype("float32")
y = (X.sum(axis=1) > 0).astype("float32")
it = mx.io.NDArrayIter(X, y, batch_size=16)
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
    mx.sym.Variable("data"), num_hidden=2), name="softmax")
mod = mx.Module(net, context=mx.cpu())
mod.fit(it, num_epoch=2, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1})
assert mod.score(it, "acc")[0][1] > 0.3
```
"""
    entries = [("class", module.BaseModule), ("class", module.Module),
               ("class", module.BucketingModule),
               ("class", module.SequentialModule),
               ("class", module.PythonModule),
               ("class", module.PythonLossModule)]
    return intro, entries, None


def page_io():
    import mxnet_tpu.io as mio
    intro = """\
# Data Loading API

Data iterators and batch containers (role of the reference's
`mxnet.io`).  Record-file iterators pipeline read, decode, augment and
batch assembly in background threads so the accelerator never waits on
the host.

```python
import numpy as np
import mxnet_tpu as mx
X = np.arange(40, dtype="float32").reshape(10, 4)
y = np.arange(10, dtype="float32")
it = mx.io.NDArrayIter(X, y, batch_size=4, shuffle=True)
n = sum(b.data[0].shape[0] for b in it)
assert n == 12  # last batch padded
it.reset()
batch = next(iter(it))
assert batch.data[0].shape == (4, 4)
```
"""
    names = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter",
             "ResizeIter", "PrefetchingIter", "CSVIter", "MNISTIter",
             "ImageRecordIter", "ImageDetRecordIter"]
    entries = [("class", getattr(mio, n)) for n in names
               if inspect.isclass(getattr(mio, n, None))]
    return intro, entries, None


def page_kvstore():
    import mxnet_tpu.kvstore as kv
    intro = """\
# KVStore API

Synchronized key-value parameter storage (role of the reference's
`mxnet.kvstore`): `local`/`device` aggregate gradients across the
in-process device mesh; `dist_*` run the parameter-server protocol
across processes (see `docs/how_to/multi_devices.md`).

```python
import mxnet_tpu as mx
kv = mx.kvstore.create("local")
kv.init(3, mx.nd.ones((2, 2)))
out = mx.nd.zeros((2, 2))
kv.push(3, mx.nd.ones((2, 2)) * 4)
kv.pull(3, out=out)
# default updater accumulates: 1 (init) + 4 (push)
assert out.asnumpy().max() == 5.0
```
"""
    entries = [("class", kv.KVStore)]
    return intro, entries, ("functions", kv, ["create"])


def page_optimization():
    import mxnet_tpu.optimizer as opt
    import mxnet_tpu.lr_scheduler as lrs
    import mxnet_tpu.initializer as init
    intro = """\
# Optimization API

Optimizers, learning-rate schedules and initializers (role of the
reference's `mxnet.optimizer` / `mxnet.lr_scheduler` /
`mxnet.initializer`).  Under the fused Module path the optimizer update
runs in-graph on device (`parallel/ingraph_opt.py`), so these classes
define the math while XLA fuses it into the training step.

```python
import mxnet_tpu as mx
opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
w, g = mx.nd.ones((2, 2)), mx.nd.ones((2, 2))
state = opt.create_state(0, w)
opt.update(0, w, g, state)
assert float(w.asnumpy().mean()) < 1.0
sched = mx.lr_scheduler.FactorScheduler(step=10, factor=0.5)
assert sched(20) < 0.02
```
"""
    entries = [("class", c) for c in
               [opt.Optimizer] + sorted(
                   {o for o in vars(opt).values()
                    if inspect.isclass(o) and issubclass(o, opt.Optimizer)
                    and o is not opt.Optimizer},
                   key=lambda c: c.__name__)]
    entries += [("class", lrs.LRScheduler),
                ("class", lrs.FactorScheduler),
                ("class", lrs.MultiFactorScheduler)]
    entries += [("class", c) for c in sorted(
        {o for o in vars(init).values()
         if inspect.isclass(o) and issubclass(o, init.Initializer)},
        key=lambda c: c.__name__)]
    return intro, entries, ("functions", opt, ["create"])


def page_model():
    import mxnet_tpu.model as model
    intro = """\
# Model API (FeedForward)

The legacy convenience estimator (role of the reference's
`mxnet.model.FeedForward`) plus checkpoint helpers shared with Module.

```python
import numpy as np
import mxnet_tpu as mx
X = np.random.randn(64, 8).astype("float32")
y = (X.sum(axis=1) > 0).astype("float32")
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
    mx.sym.Variable("data"), num_hidden=2), name="softmax")
m = mx.model.FeedForward(net, ctx=mx.cpu(), num_epoch=2,
                         numpy_batch_size=16, learning_rate=0.3)
m.fit(X, y)
assert m.predict(X).shape == (64, 2)
```
"""
    entries = [("class", model.FeedForward)]
    return intro, entries, \
        ("functions", model, ["save_checkpoint", "load_checkpoint"])


PAGES = {
    "ndarray.md": page_ndarray,
    "symbol.md": page_symbol,
    "module.md": page_module,
    "io.md": page_io,
    "kvstore.md": page_kvstore,
    "optimization.md": page_optimization,
    "model.md": page_model,
}


def generate(name):
    intro, entries, functions = PAGES[name]()
    undocumented = []
    out = io.StringIO()
    out.write(intro)
    out.write("\n<!-- GENERATED by tools/docgen_python.py from live "
              "docstrings; do not edit by hand. -->\n")
    for kind, obj in entries:
        assert kind == "class"
        _emit_class(out, obj, undocumented)
    if functions:
        _, module, names = functions
        out.write("\n### Module functions\n\n")
        _emit_functions(out, module, names, undocumented)
    return out.getvalue(), undocumented


def generate_all():
    import mxnet_tpu  # noqa: F401
    result = {}
    undocumented = {}
    for name in sorted(PAGES):
        text, undoc = generate(name)
        result[name] = text
        if undoc:
            undocumented[name] = undoc
    return result, undocumented


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from genutil import sync_file
    result, undocumented = generate_all()
    stale = [name for name, text in result.items()
             if sync_file(os.path.join(OUT_DIR, name), text, args.check)]
    n_undoc = sum(len(v) for v in undocumented.values())
    if n_undoc:
        print("undocumented entries: %d %s" % (n_undoc, undocumented))
    if args.check:
        if stale:
            print("STALE: %s out of date; rerun tools/docgen_python.py"
                  % ", ".join(stale))
            return 1
        print("ok: docs/api/python/*.md current")
        return 0
    print("wrote %d pages (%s)" % (len(result), ", ".join(sorted(result))))
    return 0


if __name__ == "__main__":
    sys.exit(main())

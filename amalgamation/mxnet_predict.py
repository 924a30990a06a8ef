#!/usr/bin/env python
"""Standalone predictor for ``.mxtpkg`` deploy artifacts.

THIS FILE IS SELF-CONTAINED: it depends on numpy + jax only — no
mxnet_tpu import, no symbol code, no op registry.  It is the TPU-native
analog of the reference's amalgamation output (``amalgamation/
mxnet_predict0.cc`` built by ``amalgamation/amalgamation.py``): where the
reference concatenates the C++ predict path into one BLAS-only
translation unit, here the whole model (graph + weights) was
ahead-of-time compiled to StableHLO by ``mxnet_tpu.deploy.export_model``
and this loader merely deserializes and calls it — on CPU or TPU,
whichever the artifact was lowered for.

Library use:

    from mxnet_predict import Predictor
    p = Predictor("model.mxtpkg")
    [out] = p.forward(data=np.zeros((1, 3, 28, 28), "float32"))

CLI smoke run (random inputs, prints output shapes):

    python mxnet_predict.py model.mxtpkg
"""
import io
import json
import sys
import zipfile

import numpy as np


class Predictor:
    """MXPredCreate/SetInput/Forward/GetOutput verbs over one artifact
    (reference include/mxnet/c_predict_api.h:59-160)."""

    def __init__(self, path_or_bytes):
        from jax import export as jexport
        if isinstance(path_or_bytes, (bytes, bytearray)):
            path_or_bytes = io.BytesIO(path_or_bytes)
        with zipfile.ZipFile(path_or_bytes) as z:
            self.meta = json.loads(z.read("meta.json"))
            self._exported = jexport.deserialize(
                bytearray(z.read("exported.bin")))
        self._inputs = {}
        self._outputs = None

    @property
    def input_names(self):
        return list(self.meta["input_names"])

    def set_input(self, name, data):
        if name not in self.meta["input_names"]:
            raise KeyError("unknown input %r (have %s)"
                           % (name, self.meta["input_names"]))
        self._inputs[name] = np.ascontiguousarray(
            data, dtype=self.meta["input_dtypes"][name])

    def forward(self, **inputs):
        import jax.numpy as jnp
        for k, v in inputs.items():
            self.set_input(k, v)
        feed = {n: jnp.asarray(self._inputs[n])
                for n in self.meta["input_names"]}
        self._outputs = [np.asarray(o) for o in self._exported.call(feed)]
        return self._outputs

    def get_output(self, index):
        if self._outputs is None:
            self.forward()
        return self._outputs[index]


def main(argv):
    if len(argv) != 2:
        print(__doc__)
        return 2
    p = Predictor(argv[1])
    rng = np.random.RandomState(0)
    feed = {n: rng.uniform(-1, 1, p.meta["input_shapes"][n]).astype(
        p.meta["input_dtypes"][n]) for n in p.input_names}
    outs = p.forward(**feed)
    for name, o in zip(p.meta["output_names"], outs):
        print(name, o.shape, o.dtype, "first:", o.ravel()[:4])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

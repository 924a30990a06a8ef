"""Device mesh helpers.

The reference scales by enumerating devices into a context list
(``ctx=[mx.gpu(i) for i in range(N)]``); the TPU-native unit of scale is a
``jax.sharding.Mesh`` over the ICI fabric.  These helpers build the standard
meshes (dp / dp×mp / dp×mp×sp) and the NamedShardings the trainer uses.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "local_mesh", "data_parallel_sharding", "P",
           "NamedSharding", "mesh_for_contexts",
           "global_device_order", "distributed_init_from_env"]


def make_mesh(axes, devices=None):
    """Build a Mesh from {axis_name: size}; size -1 means 'the rest'.

    make_mesh({'dp': 8})                       # pure data parallel
    make_mesh({'dp': 2, 'mp': 4})              # dp × tensor parallel
    make_mesh({'dp': -1, 'sp': 2})             # sequence parallel inner axis
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    names = list(axes.keys())
    sizes = list(axes.values())
    unknown = [i for i, s in enumerate(sizes) if s == -1]
    known = int(np.prod([s for s in sizes if s != -1]))
    if unknown:
        assert len(unknown) == 1, "only one axis may be -1"
        sizes[unknown[0]] = n // known
    assert int(np.prod(sizes)) == n, \
        "mesh axes %s don't cover %d devices" % (dict(zip(names, sizes)), n)
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def local_mesh(axis_name="dp", devices=None):
    """One-axis mesh over all local devices."""
    if devices is None:
        devices = jax.devices()
    return make_mesh({axis_name: len(devices)}, devices)


def global_device_order(devices):
    """Canonical multi-host device order: (process_index, id) ascending.

    Every process must enumerate the global mesh in the SAME order or
    collectives deadlock/misroute; ``jax.devices()`` already interleaves
    by process but this makes the contract explicit and testable with
    stub devices (anything carrying ``process_index`` and ``id``)."""
    return sorted(devices,
                  key=lambda d: (int(getattr(d, "process_index", 0)),
                                 int(d.id)))


def distributed_init_from_env():
    """Boot this process into the one global mesh tools/launch.py --mesh
    described via MXNET_MESH_{COORDINATOR,NUM_PROCESSES,PROCESS_ID}.

    Returns True when jax.distributed was (already) initialized for this
    launch, False when the env names no mesh (single-process run).  Must
    run before the first device lookup; a late call on an
    already-initialized backend raises RuntimeError from jax itself."""
    from ..base import get_env
    coordinator = get_env("MXNET_MESH_COORDINATOR")
    if not coordinator:
        return False
    try:
        from jax._src.distributed import global_state as _gs
        already = _gs.client is not None
    except Exception:                                  # pragma: no cover
        already = jax.process_count() > 1
    if already:
        return True        # a prior call (ours or the script's) won
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(get_env("MXNET_MESH_NUM_PROCESSES")),
        process_id=int(get_env("MXNET_MESH_PROCESS_ID")))
    return True


def mesh_for_contexts(contexts, axes=None, batch_axis="dp",
                      multihost=False):
    """THE mesh factory for module-level training: a Mesh over the jax
    devices of a Context list.

    ``axes`` is a ``make_mesh``-style {axis_name: size} dict (sizes may
    use -1; they must cover the mesh's devices); the default is a
    one-axis data-parallel mesh.  Every mesh a Module builds goes
    through here, so this is the multi-host seam: with
    ``multihost=True`` under a multi-process ``jax.distributed`` launch
    the mesh spans EVERY process's devices in :func:`global_device_order`
    (the contexts name this process's local slice; the axes dict then
    covers the global census), which is what folds the cross-host psum
    into the one SPMD step program.

    Raises MXNetError when contexts resolve to duplicate devices — a
    mesh must enumerate distinct chips.
    """
    from ..base import MXNetError
    devices = [ctx.jax_device() for ctx in contexts]
    if len(set(devices)) != len(devices):
        raise MXNetError("contexts %s resolve to duplicate jax devices; "
                         "a mesh needs one distinct device per context"
                         % (list(map(str, contexts)),))
    if multihost and jax.process_count() > 1:
        if set(devices) != set(jax.local_devices()):
            raise MXNetError(
                "multihost mesh requires contexts covering every local "
                "device (got %d of %d): each process contributes its "
                "whole slice of the global mesh"
                % (len(devices), len(jax.local_devices())))
        devices = global_device_order(jax.devices())
    if axes is None:
        axes = {batch_axis: len(devices)}
    return make_mesh(dict(axes), devices)


def data_parallel_sharding(mesh, batch_axis="dp"):
    """(replicated_params, batch_sharded) NamedShardings for pure DP."""
    replicated = NamedSharding(mesh, P())
    batched = NamedSharding(mesh, P(batch_axis))
    return replicated, batched

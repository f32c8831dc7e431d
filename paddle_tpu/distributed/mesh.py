"""Device mesh management — the Place/ring-id world replaced by jax.sharding.Mesh.

Reference parity: NCCLCommContext's ring-id -> communicator map
(platform/collective_helper.h:67) becomes named mesh axes; process groups become
sub-meshes. Axis naming convention across the framework:
  'dp' data parallel | 'sharding' ZeRO | 'mp' tensor/model parallel |
  'pp' pipeline | 'sp' sequence/context parallel | 'ep' expert parallel |
  'clients' federated MapReduce (paddle_tpu.federated, docs/FEDERATED.md).
"""
import contextlib

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

_CURRENT_MESH = [None]


def build_mesh(mesh_shape=None, axis_names=None, devices=None):
    """Build a Mesh over the available devices (default: 1-axis 'dp' over all)."""
    devs = devices if devices is not None else jax.devices()
    if mesh_shape is None:
        mesh_shape = (len(devs),)
        axis_names = axis_names or ("dp",)
    axis_names = tuple(axis_names)
    arr = np.array(devs).reshape(tuple(mesh_shape))
    return Mesh(arr, axis_names)


def set_mesh(mesh):
    _CURRENT_MESH[0] = mesh
    return mesh


def get_mesh():
    if _CURRENT_MESH[0] is None:
        _CURRENT_MESH[0] = build_mesh()
    return _CURRENT_MESH[0]


def current_mesh():
    """The mesh set by set_mesh()/mesh_scope(), or None. Unlike get_mesh()
    it never builds a default one: for code that has to know whether it is
    running under a mesh at all (SpmdTrainer scopes its mesh around the
    traced forward so the Pallas flash kernel can shard_map itself)."""
    return _CURRENT_MESH[0]


@contextlib.contextmanager
def mesh_scope(mesh):
    old = _CURRENT_MESH[0]
    _CURRENT_MESH[0] = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _CURRENT_MESH[0] = old


def client_mesh(n_clients, inner_shape=(), inner_names=(), devices=None):
    """A Mesh with a leading federated ``clients`` axis composing with the
    SPMD axes: ``client_mesh(4)`` shards 4 clients over 4 devices;
    ``client_mesh(2, (2,), ("dp",))`` gives each of 2 clients a 2-device dp
    sub-mesh. Arrays whose leading axis is the clients dimension shard over
    the ``clients`` axis (paddle_tpu.federated.client_map does this when
    handed this mesh); everything inside one client's shard uses the inner
    axes exactly as plain SPMD code does."""
    inner_shape = tuple(int(s) for s in inner_shape)
    need = int(n_clients) * int(np.prod(inner_shape, dtype=np.int64)
                                if inner_shape else 1)
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < need:
        raise ValueError(
            f"client_mesh needs {need} devices for {n_clients} clients x "
            f"{inner_shape or (1,)} inner mesh, have {len(devs)}")
    return build_mesh((int(n_clients),) + inner_shape,
                      ("clients",) + tuple(inner_names),
                      devices=devs[:need])


def sharding(*spec, mesh=None):
    return NamedSharding(mesh or get_mesh(), P(*spec))


def replicated(mesh=None):
    return NamedSharding(mesh or get_mesh(), P())

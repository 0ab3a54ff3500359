"""Collective communication API (reference: python/paddle/distributed/
collective.py: all_reduce:405, broadcast:338, all_gather:580, scatter:658,
barrier:166, send:1253/recv:1302; C++ operators/collective/c_*).

TPU-native semantics: the 'ring_id'/'group' of the reference is a mesh
axis name. Two execution contexts:

- **Inside a traced SPMD region** (shard_map/pjit) the functions lower to
  jax.lax collectives (psum/all_gather/ppermute) — compiled onto ICI.
- **Eagerly on sharded global arrays** the same ops run through a cached
  shard_map over the global mesh — XLA executes the collective across
  the participating devices, the eager analog of issuing a c_allreduce.

On replicated (unsharded) eager tensors in a single process the ops are
mathematically the identity (every "rank" holds the same value), matching
the reference's 1-proc behavior.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from ..core.tensor import Tensor
from ..core.dispatch import in_trace
from . import topology

_CUSTOM_GROUPS = {}


class Group:
    def __init__(self, ranks=None, axis="dp", id=0):
        self.ranks = ranks
        self.axis = axis
        self.id = id

    @property
    def nranks(self):
        if self.ranks is not None:
            return len(self.ranks)
        mesh = topology.get_global_mesh()
        return mesh.shape.get(self.axis, 1)


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _axis_of(group):
    if group is None:
        return "dp"
    if isinstance(group, str):
        return group
    if isinstance(group, Group):
        return group.axis
    return "dp"


def new_group(ranks=None, backend=None, timeout=None):
    """reference: collective.py:206. Mesh axes replace comm rings; a custom
    rank list maps onto the axis containing those ranks."""
    g = Group(ranks=ranks, axis="dp", id=len(_CUSTOM_GROUPS) + 1)
    _CUSTOM_GROUPS[g.id] = g
    return g


def is_initialized():
    return True


# --------------------------------------------------------------- in-SPMD ops
# Usable inside shard_map'd / pjit'd functions (axis must be live).


def psum(x, axis):
    return jax.lax.psum(x, axis)


def pmean(x, axis):
    return jax.lax.pmean(x, axis)


def pmax(x, axis):
    return jax.lax.pmax(x, axis)


def all_gather_spmd(x, axis, gather_axis=0):
    return jax.lax.all_gather(x, axis, axis=gather_axis, tiled=True)


def ppermute(x, axis, perm):
    return jax.lax.ppermute(x, axis, perm)


def all_to_all_spmd(x, axis, split_axis, concat_axis):
    return jax.lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)


# --------------------------------------------------------------- eager ops


@functools.lru_cache(maxsize=256)
def _eager_collective(op, axis, mesh_id, ndim, reduce_op="sum"):
    mesh = topology.get_global_mesh()
    spec = _first_dim_spec(axis, ndim)

    if op == "all_reduce":
        red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
               "avg": jax.lax.pmean}[reduce_op]

        def fn(x):
            return red(x, axis)

        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec))
    if op == "all_gather":
        def fn(x):
            return jax.lax.all_gather(x, axis, axis=0, tiled=True)

        out_spec = _none_spec(ndim)
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=out_spec))
    raise ValueError(op)


def _first_dim_spec(axis, ndim):
    return P(axis, *([None] * (ndim - 1)))


def _none_spec(ndim):
    return P(*([None] * ndim))


def _is_sharded_over(arr, axis):
    sh = getattr(arr, "sharding", None)
    if sh is None or not isinstance(sh, NamedSharding):
        return False
    return any(axis in (p if isinstance(p, tuple) else (p,))
               for p in sh.spec if p is not None)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference: collective.py:405 / c_allreduce_sum op."""
    axis = _axis_of(group)
    if in_trace():
        red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
               "avg": jax.lax.pmean}[op]
        out = red(tensor._value, axis)
        result = Tensor(out, stop_gradient=tensor.stop_gradient)
        tensor._assign_result(result)
        return tensor
    if not _is_sharded_over(tensor._value, axis):
        # replicated single-process view: allreduce(sum) over identical copies
        mesh = topology.get_global_mesh()
        n = mesh.shape.get(axis, 1)
        if op == ReduceOp.SUM:
            tensor._value = tensor._value * n
        elif op == ReduceOp.PROD:
            tensor._value = tensor._value ** n
        return tensor
    fn = _eager_collective("all_reduce", axis, id(topology.get_global_mesh()),
                          tensor._value.ndim, op)
    tensor._value = fn(tensor._value)
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """reference: collective.py:580."""
    axis = _axis_of(group)
    mesh = topology.get_global_mesh()
    n = mesh.shape.get(axis, 1)
    if in_trace():
        out = jax.lax.all_gather(tensor._value, axis)
        for i in range(n):
            tensor_list.append(Tensor(out[i]))
        return tensor_list
    if not _is_sharded_over(tensor._value, axis):
        for _ in range(n):
            tensor_list.append(Tensor(tensor._value))
        return tensor_list
    fn = _eager_collective("all_gather", axis, id(mesh), tensor._value.ndim)
    gathered = fn(tensor._value)
    chunks = jnp.split(gathered, n, axis=0)
    tensor_list.extend(Tensor(c) for c in chunks)
    return tensor_list


@functools.lru_cache(maxsize=256)
def _eager_broadcast(axis, mesh_id, ndim, src):
    mesh = topology.get_global_mesh()
    spec = _first_dim_spec(axis, ndim)

    def fn(x):
        # every shard replaces its block with src's block
        return jax.lax.all_gather(x, axis)[src]

    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec))


def broadcast(tensor, src=0, group=None, sync_op=True):
    """reference: collective.py:338 / c_broadcast op.

    Sharded-over-axis arrays ("rank rows" along dim 0): every shard's
    block becomes src's block. Replicated arrays are already identical on
    every device — the broadcast result by definition."""
    axis = _axis_of(group)
    if in_trace():
        out = jax.lax.all_gather(tensor._value, axis)[src]
        tensor._assign_result(Tensor(out, stop_gradient=tensor.stop_gradient))
        return tensor
    if not _is_sharded_over(tensor._value, axis):
        return tensor
    fn = _eager_broadcast(axis, id(topology.get_global_mesh()),
                          tensor._value.ndim, int(src))
    tensor._value = fn(tensor._value)
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference: collective.py (c_reduce). In the global-array model the
    reduced value lands on every shard (dst included); semantically a
    superset of rank-dst-only placement."""
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """reference: collective.py:658 / c_scatter op.

    Sharded convention (dim 0 = rank index over the group axis): the
    stacked tensor_list becomes the new sharded value, so shard r holds
    tensor_list[r]. Replicated convention: this process's view becomes its
    own rank's element."""
    axis = _axis_of(group)
    mesh = topology.get_global_mesh()
    n = mesh.shape.get(axis, 1)
    if not tensor_list:
        return tensor
    if len(tensor_list) != n:
        raise ValueError(f"scatter needs {n} tensors for axis {axis!r}, "
                         f"got {len(tensor_list)}")
    if _is_sharded_over(tensor._value, axis):
        stacked = jnp.stack([t._value if isinstance(t, Tensor) else jnp.asarray(t)
                             for t in tensor_list])
        if stacked.size != tensor._value.size:
            raise ValueError(
                f"scatter shape mismatch: {n} x {stacked.shape[1:]} elements "
                f"!= target {tuple(tensor._value.shape)}")
        val = stacked.reshape(tensor._value.shape)
        tensor._value = jax.device_put(
            val, NamedSharding(mesh, _first_dim_spec(axis, val.ndim)))
        return tensor
    tensor._assign_result(tensor_list[get_rank_in(group)])
    return tensor


def get_rank_in(group=None):
    """This process's rank along the group axis. Single-process mesh SPMD
    has one controller (rank 0); under jax.distributed the process index
    maps onto the axis via the hybrid topology when one is configured."""
    axis = _axis_of(group)
    if jax.process_count() == 1:
        return 0
    try:
        from .fleet import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
    except Exception:
        hcg = None
    if hcg is not None:
        getter = {"dp": "get_data_parallel_rank", "mp": "get_model_parallel_rank",
                  "pp": "get_stage_id"}.get(axis)
        if getter and hasattr(hcg, getter):
            return getattr(hcg, getter)()
    # derived from mesh device ownership — stride arithmetic on the
    # process index is wrong whenever a process hosts >1 device
    return _group_pos_of(axis)


def barrier(group=None):
    """reference: collective.py:166 / barrier_op. XLA programs are bulk-
    synchronous; an explicit barrier only needs to drain local dispatch."""
    (jnp.zeros(()) + 0).block_until_ready()


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """reference: collective.py (alltoall). Rank j's out[i] = rank i's
    in[j]. With replicated single-process ranks every peer holds the same
    list, so out[i] = in[my_rank] for all i."""
    if jax.process_count() > 1:
        raise NotImplementedError(
            "eager list-form all_to_all is single-process only (each "
            "process would need its peers' lists); use alltoall_single "
            "on a sharded array, or jax.lax.all_to_all inside a "
            "compiled step")
    rank = get_rank_in(group)
    axis = _axis_of(group)
    mesh = topology.get_global_mesh()
    n = mesh.shape.get(axis, 1)
    if len(in_tensor_list) != n:
        raise ValueError(f"all_to_all needs {n} tensors for axis {axis!r}, "
                         f"got {len(in_tensor_list)}")
    out_tensor_list.extend(Tensor(in_tensor_list[rank]._value)
                           for _ in range(n))
    return out_tensor_list


def alltoall_single(out_tensor, in_tensor, group=None, sync_op=True):
    """All-to-all on a dim-0 sharded array (reference alltoall over a
    ring): shard r's k-th block goes to shard k's r-th block."""
    axis = _axis_of(group)
    mesh = topology.get_global_mesh()
    n = mesh.shape.get(axis, 1)
    if n == 1 or not _is_sharded_over(in_tensor._value, axis):
        out_tensor._value = in_tensor._value
        return out_tensor
    f = _eager_alltoall_single(axis, id(mesh), in_tensor._value.ndim)
    out_tensor._value = f(in_tensor._value)
    return out_tensor


@functools.lru_cache(maxsize=256)
def _eager_alltoall_single(axis, mesh_id, ndim):
    mesh = topology.get_global_mesh()
    spec = _first_dim_spec(axis, ndim)

    def fn(x):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec))


# P2P: XLA has no eager point-to-point primitive — in-graph P2P is
# ppermute (see distributed/pipeline.py for the compiled use). The eager
# API ships tensors host-to-host over the TCP transport in p2p.py (the
# TPU analog of send_v2/recv_v2 over NCCL P2P bootstrapped by the
# gen_comm_id_helper.cc TCP side channel). ``src``/``dst`` are
# group-relative like the reference: the wire address is the peer's
# GLOBAL trainer rank (same mesh coordinates, axis index swapped) and
# frames are matched by (axis, group-relative src).


def _global_rank_of(axis, peer):
    """Trainer rank (process index) of the peer at group-relative
    position ``peer`` on ``axis``.

    Derived from mesh DEVICE OWNERSHIP, not stride arithmetic on the
    process index: with multiple local devices per process (any real
    TPU host) the process index does not walk the mesh axes, so strides
    would compute a wrong or nonexistent rank. For every mesh
    coordinate this process owns, swap the ``axis`` index to ``peer``
    and collect the owning process of the device there; eager P2P is
    well-defined only when that resolves to ONE process."""
    mesh = topology.get_global_mesh()
    if axis not in mesh.axis_names:
        if int(peer) != 0:
            raise ValueError(
                f"axis {axis!r} is not on the global mesh (group size "
                f"1): the only valid peer is 0, got {peer}")
        return jax.process_index()  # size-1 group: self
    return _rank_of_cached(mesh, axis, int(peer), jax.process_index())


@functools.lru_cache(maxsize=1024)
def _rank_of_cached(mesh, axis, peer, me):
    axis_idx = list(mesh.axis_names).index(axis)
    dev = np.asarray(mesh.devices)
    size = dev.shape[axis_idx]
    if not 0 <= peer < size:
        raise ValueError(
            f"peer rank {peer} out of range for group axis {axis!r} "
            f"of size {size}")
    procs = set()
    for coord in np.ndindex(dev.shape):
        if dev[coord].process_index != me:
            continue
        pc = list(coord)
        pc[axis_idx] = peer
        procs.add(dev[tuple(pc)].process_index)
    if len(procs) == 1:
        return procs.pop()
    if not procs:
        raise RuntimeError(
            f"process {me} owns no device of the global mesh; eager "
            "send/recv needs every participant on the mesh")
    raise RuntimeError(
        f"eager send/recv over axis {axis!r} is ambiguous: this "
        f"process's local devices map peer {peer} to processes "
        f"{sorted(procs)}. Host-side P2P addresses a single peer "
        "process; use in-graph ppermute (distributed/pipeline.py) for "
        "per-device point-to-point")


def _group_pos_of(axis):
    """This process's group-relative position on ``axis``, derived from
    device ownership (the src the receiver matches on — must agree with
    _global_rank_of's geometry, not process-index stride arithmetic)."""
    mesh = topology.get_global_mesh()
    if axis not in mesh.axis_names:
        return 0
    return _pos_of_cached(mesh, axis, jax.process_index())


@functools.lru_cache(maxsize=1024)
def _pos_of_cached(mesh, axis, me):
    axis_idx = list(mesh.axis_names).index(axis)
    dev = np.asarray(mesh.devices)
    pos = {coord[axis_idx] for coord in np.ndindex(dev.shape)
           if dev[coord].process_index == me}
    if len(pos) == 1:
        return pos.pop()
    if pos and all(
            _rank_of_cached(mesh, axis, p, me) == me
            for p in range(dev.shape[axis_idx])):
        # EVERY position on the axis is this same process (single-
        # controller virtual mesh / in-process group): self-group
        # convention rank 0. Testing only our own positions would wrongly
        # pass when a spanning axis is split in contiguous blocks.
        return 0
    raise RuntimeError(
        f"this process's devices span positions {sorted(pos)} of axis "
        f"{axis!r}; host-side P2P needs a unique per-process position "
        "on the group axis")


def send(tensor, dst=0, group=None, sync_op=True):
    """reference: collective.py:1253 / send_v2 op (see P2P note above)."""
    from . import p2p

    axis = _axis_of(group)
    p2p.get_transport().send(axis, _global_rank_of(axis, dst),
                             np.asarray(tensor._value),
                             src_tag=_group_pos_of(axis))
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    """reference: collective.py:1302 / recv_v2 op (see P2P note above).

    Blocks until the matching send arrives (PADDLE_P2P_TIMEOUT caps the
    wait), like the reference's synchronous recv_v2."""
    from . import p2p

    val = p2p.get_transport().recv(_axis_of(group), int(src))
    arr = jnp.asarray(val)
    tensor._value = arr.astype(tensor._value.dtype) \
        if arr.dtype != tensor._value.dtype else arr
    return tensor


_SPLIT_LAYERS = {}


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """reference: collective.py:1021 paddle.distributed.split — build and
    apply a tensor-parallel fc/embedding sharded over the 'mp' mesh axis.

    operation='linear': axis=0 shards the input dim (RowParallelLinear),
    axis=1 shards the output dim (ColumnParallelLinear).
    operation='embedding': vocab-sharded VocabParallelEmbedding.
    Layers are cached by `name` so repeated dygraph calls reuse weights.
    """
    from .meta_parallel import (ColumnParallelLinear, RowParallelLinear,
                                VocabParallelEmbedding)

    layer = _SPLIT_LAYERS.get(name) if name else None
    if layer is None:
        if operation == "linear":
            in_f, out_f = size
            if axis == 1:
                layer = ColumnParallelLinear(
                    in_f, out_f, has_bias=bias_attr is not False,
                    gather_output=gather_out)
            elif axis == 0:
                layer = RowParallelLinear(
                    in_f, out_f, has_bias=bias_attr is not False,
                    input_is_parallel=False)
            else:
                raise ValueError(f"linear split axis must be 0 or 1, got {axis}")
        elif operation == "embedding":
            vocab, dim = size
            layer = VocabParallelEmbedding(vocab, dim)
        else:
            raise ValueError(f"unknown split operation {operation!r}")
        if name:  # anonymous layers are not cached (fresh weights per call)
            _SPLIT_LAYERS[name] = layer
    # eager inputs may be committed to one device; the sharded layer
    # computes over the whole mesh
    mesh = topology.get_global_mesh()
    if isinstance(x, Tensor) and not isinstance(x._value, jax.core.Tracer):
        x = Tensor(jax.device_put(x._value, NamedSharding(mesh, P())),
                   stop_gradient=x.stop_gradient)
    return layer(x)

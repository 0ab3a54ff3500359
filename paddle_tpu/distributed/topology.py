"""Hybrid parallel topology over a jax device Mesh.

Reference: python/paddle/distributed/fleet/base/topology.py:35
CommunicateTopology, :111 HybridCommunicateGroup. The reference builds
cartesian rank coordinates and creates one NCCL ring per axis slice; here
an axis IS a mesh dimension and "rings" are XLA collectives over that
axis — no comm-group materialisation is needed. Axis order is chosen so
the innermost (fastest-varying) axis 'mp' maps to physically-adjacent
chips on the ICI torus (tensor parallel needs the highest bandwidth),
then 'sharding', then 'pp', then 'dp' (scaling-book §sharding recipe).
"""
import contextlib
import contextvars

import numpy as np
import jax
from jax.sharding import Mesh

_HYBRID_GROUP = None
_GLOBAL_MESH = None
_TRACED_MESH = contextvars.ContextVar("traced_mesh", default=None)

AXIS_ORDER = ("dp", "pp", "sharding", "sp", "ep", "mp")


def build_mesh(dp=1, mp=1, pp=1, sharding=1, sp=1, ep=1, devices=None):
    devices = devices if devices is not None else jax.devices()
    n = dp * mp * pp * sharding * sp * ep
    if n == 1 and len(devices) > 1:
        dp = len(devices)
        n = dp
    if n > len(devices):
        raise ValueError(f"topology dp{dp}xpp{pp}xsharding{sharding}xsp{sp}"
                         f"xep{ep}xmp{mp}={n} needs {n} devices, have "
                         f"{len(devices)}")
    arr = np.asarray(devices[:n]).reshape(dp, pp, sharding, sp, ep, mp)
    return Mesh(arr, AXIS_ORDER)


def data_axes(mesh):
    """The mesh axes that shard the batch dimension (shard_batch and
    every consumer of its layout must agree on this set)."""
    return tuple(ax for ax in ("dp", "sharding")
                 if mesh.shape.get(ax, 1) > 1)


@contextlib.contextmanager
def tracing_for(mesh):
    """Mark the code inside as being traced into a program partitioned
    over ``mesh`` (the train-step builders enter it around the forward).
    An op GSPMD cannot partition — a Mosaic kernel — reads it through
    ``traced_mesh()`` and shards itself with a shard_map."""
    token = _TRACED_MESH.set(mesh)
    try:
        yield
    finally:
        _TRACED_MESH.reset(token)


def traced_mesh():
    """The mesh of the partitioned program being traced, else None
    (eager ops, to_static, a plain jax.jit)."""
    return _TRACED_MESH.get()


def set_global_mesh(mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh():
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        devs = jax.devices()
        _GLOBAL_MESH = Mesh(np.asarray(devs).reshape(
            (len(devs),) + (1,) * (len(AXIS_ORDER) - 1)), AXIS_ORDER)
    return _GLOBAL_MESH


class CommunicateTopology:
    """reference: topology.py:35."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"),
                 dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = None
        self._world = int(np.prod(dims))

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return self._world

    def get_rank(self, **kwargs):
        coords = [kwargs[n] for n in self._parallel_names]
        return int(np.ravel_multi_index(coords, self._dims))

    def get_coord(self, rank):
        return tuple(int(c) for c in np.unravel_index(rank, self._dims))

    def get_axis_list(self, axis_name, index):
        axis = self._parallel_names.index(axis_name)
        ranks = []
        for r in range(self._world):
            if self.get_coord(r)[axis] == index:
                ranks.append(r)
        return ranks

    def get_comm_list(self, axis_name):
        """All rank-groups along `axis_name` (reference topology.py:85)."""
        axis = self._parallel_names.index(axis_name)
        other_dims = [d for i, d in enumerate(self._dims) if i != axis]
        groups = []
        for flat in range(int(np.prod(other_dims)) if other_dims else 1):
            coords_other = np.unravel_index(flat, other_dims) if other_dims else ()
            group = []
            for k in range(self._dims[axis]):
                coord = list(coords_other[:axis]) + [k] + list(coords_other[axis:])
                group.append(self.get_rank(**dict(zip(self._parallel_names, coord))))
            groups.append(group)
        return groups


class HybridCommunicateGroup:
    """reference: topology.py:111. Mesh-backed: per-axis 'groups' are mesh
    axis names usable directly in psum/ppermute/shard_map."""

    def __init__(self, topology=None, dp=1, mp=1, pp=1, sharding=1, sp=1):
        if topology is not None:
            dims = [topology.get_dim(n) for n in topology.get_hybrid_group_names()]
            if len(dims) == 4:
                dp, pp, sharding, mp = dims
            else:
                dp, pp, sharding, sp, mp = dims
        self._dp_degree = dp
        self._mp_degree = mp
        self._pp_degree = pp
        self._sharding_degree = sharding
        self._sp_degree = sp
        self._topo = CommunicateTopology(
            ("data", "pipe", "sharding", "sep", "model"),
            (dp, pp, sharding, sp, mp))
        self.mesh = build_mesh(dp=dp, mp=mp, pp=pp, sharding=sharding, sp=sp)
        set_global_mesh(self.mesh)
        self.global_rank = jax.process_index()
        self._coord = self._topo.get_coord(min(self.global_rank,
                                               self._topo.world_size() - 1))

    # --- degree getters (reference :209-254) ---
    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sep_parallel_world_size(self):
        """Sequence (context) parallel degree — green-field: the reference
        has no sequence parallelism (SURVEY §5 long-context: absent)."""
        return self._sp_degree

    def get_sep_parallel_group(self):
        return "sp"

    def get_data_parallel_rank(self):
        return self._coord[0]

    def get_pipe_parallel_rank(self):
        return self._coord[1]

    def get_sharding_parallel_rank(self):
        return self._coord[2]

    def get_model_parallel_rank(self):
        return self._coord[3]

    # mesh axis names usable in collectives
    def get_data_parallel_group(self):
        return "dp"

    def get_model_parallel_group(self):
        return "mp"

    def get_pipe_parallel_group(self):
        return "pp"

    def get_sharding_parallel_group(self):
        return "sharding"

    def get_check_parallel_group(self):
        return None

    def get_data_parallel_group_src_rank(self):
        return 0

    def get_model_parallel_group_src_rank(self):
        return 0

    def topology(self):
        return self._topo

    def get_parallel_mode(self):
        if self._mp_degree == 1 and self._pp_degree == 1 and self._dp_degree > 1:
            return "data"
        if self._mp_degree > 1 and self._pp_degree == 1:
            return "model" if self._dp_degree == 1 else "hybrid"
        if self._pp_degree > 1:
            return "pipe" if self._dp_degree == 1 and self._mp_degree == 1 else "hybrid"
        return "single"


def set_hybrid_communicate_group(hcg):
    global _HYBRID_GROUP
    _HYBRID_GROUP = hcg


def get_hybrid_communicate_group():
    return _HYBRID_GROUP

"""Kernel placement: which implementation of a Pallas kernel a traced
program may hold, decided once — the only reader of the two flags, the
platform and the announced mesh under ``ops/``, ``incubate/``, ``text/``.

GSPMD cannot partition a Mosaic call, so a call site with a kernel and an
XLA implementation has to know, while it is traced, whether kernels are
selected, whether the platform compiles them (a TPU) or interprets them
(``pallas_interpret``: plain HLO, fine anywhere), and whether the program's
devices are known: a one-device process, or a mesh a step builder announced
(``topology.tracing_for``), over which ``on_mesh`` shards the call. A plain
``jax.jit`` on several devices may be partitioned, which only its lowering
sees: not known here, so no kernel. ``kernel`` answers ``"mosaic"`` |
``"interpret"`` | ``None`` (XLA) — ``tests/test_kernel_placement.py``:

========  =========  ===  ========  =======  =======  =========  ===========
selected  interpret  TPU  mesh      devices  sharded  unsharded  no_fallback
========  =========  ===  ========  =======  =======  =========  ===========
no        any        any  any       any      None     None       None
yes       yes        any  any       any      interpret (all three)
yes       no         no   any       any      None     None       None
yes       no         yes  none      1        mosaic   mosaic     mosaic
yes       no         yes  none      > 1      None     None       mosaic
yes       no         yes  size 1    any      mosaic   mosaic     mosaic
yes       no         yes  size > 1  any      mosaic   None       mosaic
========  =========  ===  ========  =======  =======  =========  ===========

The answer is taken where an op is dispatched, outside it, and rides the
op's static arguments: an eager trace is cached by them, so a flag flipped
or a mesh announced retraces.

Who asks (each from what else it can observe — shapes, dtype, the 'mp'
axis — and each counted under a label of its own): the attention gate
(``ops.attention.attention_route``: ``short`` sharded, ``stream`` with no
fallback), the stage before that core (``ops.attention.qk_path``: a head's
RMSNorm, RoPE and the head split, decided by ``qk_kernel`` in the layers of
``text/models.py`` that call ``qk_heads``: Trinity's, Qwen3-Next's gated
attention, LFM2's — the last always ``xla``, heads of 64), the delta rule's
scan and the convolution stage before it (``ops.linear_attention.core_path``
/ ``conv_path``), the selective state-space scan (``ssd_path``, asked in
``ssd_scan`` outside its dispatched op: rows over the data axes, the heads
whole on an 'mp' axis — B and C are one group's for all its heads —, the
per-head A and D a copy a row because ``on_mesh`` shards dim 0 of every
array) and LFM2's gated short convolution (``shortconv_path``,
asked inside ``gated_short_conv``: the layer's call carries two arrays and
nothing else; rows over the data axes, whole on an 'mp' axis), all sharded
through ``on_mesh``; and, unsharded, the expert layer (``incubate.moe``: it
takes no ``shard_map`` of its own) for its grouped matmul and for its
router's choice (``route_path``, decided by ``route_kernel`` in
``_forward_sorted`` and riding ``_route``'s static arguments: top-k ids,
the scores at them and the loads as one Mosaic call a pass from 64 experts
on, XLA's sort and one-hot sums elsewhere).
"""
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import flags
from ..core.place import is_tpu_available
from ..distributed import topology


def kernel(*, sharded, no_fallback=False):
    """The module's table for the call site being traced. ``sharded``: it
    runs its kernel through ``on_mesh`` (a fact about the site, not an
    option). ``no_fallback``: it has no XLA implementation at its sizes
    (the ``stream`` attention route: XLA's S^2 logits), so on a TPU Mosaic
    is chosen whatever is known of the devices, and the lowering raises if
    GSPMD has to partition it. A platform query that fails is an error."""
    if not flags.flag_value("use_pallas_kernels"):
        return None
    if flags.flag_value("pallas_interpret"):
        return "interpret"
    if not is_tpu_available():
        return None
    mesh = topology.traced_mesh()
    known = (jax.device_count() == 1 if mesh is None
             else sharded or mesh.size == 1)
    return "mosaic" if known or no_fallback else None


def axis_size(axis, *, or_global=False):
    """The announced mesh's size on ``axis``, else 1 — for 'mp', over how
    many shards ``on_mesh`` cuts ``head_axis``; ``or_global``: else the
    global mesh's (it lays out a layer's weights where none is announced)."""
    mesh = topology.traced_mesh() or (
        topology.get_global_mesh() if or_global else None)
    return 1 if mesh is None else mesh.shape.get(axis, 1)


def on_mesh(call, arrays, *, head_axis, out_head_axis=None, seed=None,
            seed_per_shard=False):
    """Run ``call(*arrays)`` (``call(*arrays, seed=seed)`` where a seed
    is given) — directly, or inside a step being traced for a
    multi-device mesh (topology.traced_mesh) under a shard_map: GSPMD
    cannot partition a Mosaic kernel, every mesh axis has to be manual
    around it (a builder whose step already runs inside a shard_map over
    the whole mesh has done that: direct again). Programs are independent
    per batch row and head, so dim 0 of every array shards over the data
    axes and dim ``head_axis`` (None: heads are not a dim of their own)
    over 'mp' — each only where it divides (the head dim in every array:
    it may hold a head's features too, [.., heads x d]); otherwise that dim
    is computed whole on every device of the axis. ``out_head_axis``: the
    dim the results carry their heads on, where not ``head_axis`` (a stage
    that takes streams [.., T, heads x d] and leaves [.., heads, T, d]).
    ``seed_per_shard``: the kernel's mask hash counts (batch, head) from 0
    on every shard, so each shard gets a seed of its own or they all drop
    the same entries."""
    def run(*args):
        *shards, seed = args
        return call(*shards) if seed is None else call(*shards, seed=seed)

    mesh = topology.traced_mesh()
    if (mesh is None or mesh.size == 1 or set(mesh.axis_names) <= set(
            jax.sharding.get_abstract_mesh().manual_axes)):
        return run(*arrays, seed)

    shape = arrays[0].shape
    data = topology.data_axes(mesh)
    n_data = math.prod(mesh.shape[ax] for ax in data)
    n_mp = mesh.shape.get("mp", 1)
    b_axes = data if n_data > 1 and shape[0] % n_data == 0 else ()
    h_axes = (("mp",) if head_axis is not None and n_mp > 1 and all(
        a.shape[head_axis] % n_mp == 0 for a in arrays) else ())

    def spec(a, head_axis=head_axis):
        dims = [None] * a.ndim
        dims[0] = b_axes or None
        if h_axes:
            dims[head_axis] = h_axes
        return P(*dims)

    def on_shard(*args):
        *shards, seed = args
        if seed_per_shard:
            for ax in b_axes + h_axes:
                seed = (seed * jnp.int32(mesh.shape[ax])
                        + jax.lax.axis_index(ax))
        return run(*shards, seed)

    # no seed is an empty operand: the map then takes the arrays alone
    return jax.shard_map(
        on_shard, mesh=mesh, in_specs=tuple(map(spec, arrays)) + (P(),),
        out_specs=spec(arrays[0], head_axis if out_head_axis is None
                       else out_head_axis),
        check_vma=False)(*arrays, seed)

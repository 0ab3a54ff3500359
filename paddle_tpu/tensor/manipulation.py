"""Shape/layout manipulation ops (reference: python/paddle/tensor/manipulation.py;
kernels operators/concat_op.cc, split_op.cc, reshape_op.cc, transpose_op.cc,
gather_op.cc, scatter_op.cc, slice_op.cc ...).
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import export as jax_export

from ..core.dispatch import apply_op, in_trace
from ..core.tensor import Tensor
from ..core import errors


def _shape_arg(shape):
    if isinstance(shape, Tensor):
        return tuple(int(v) for v in shape.numpy().reshape(-1))
    out = []
    for s in shape:
        if isinstance(s, Tensor):
            out.append(int(s.numpy()))
        elif jax_export.is_symbolic_dim(s):
            # x.shape[0] under a batch-polymorphic jit.save: the symbol
            # itself is the size (int() of it cannot be decided)
            out.append(s)
        else:
            out.append(int(s))
    return tuple(out)


def concat(x, axis=0, name=None):
    if isinstance(axis, Tensor):
        axis = int(axis.numpy())
    return apply_op("concat", lambda *xs, axis: jnp.concatenate(xs, axis=axis), *x, axis=int(axis))


def stack(x, axis=0, name=None):
    return apply_op("stack", lambda *xs, axis: jnp.stack(xs, axis=axis), *x, axis=int(axis))


def unstack(x, axis=0, num=None):
    n = num if num is not None else x.shape[axis]
    outs = apply_op(
        "unstack",
        lambda x, *, axis, n: tuple(jnp.squeeze(s, axis=axis) for s in jnp.split(x, n, axis=axis)),
        x, axis=int(axis), n=n)
    return list(outs)


def split(x, num_or_sections, axis=0, name=None):
    if isinstance(axis, Tensor):
        axis = int(axis.numpy())
    axis = int(axis)
    dim = x.shape[axis]
    if isinstance(num_or_sections, int):
        sections = None
        num = num_or_sections
    else:
        secs = [int(s.numpy()) if isinstance(s, Tensor) else int(s) for s in num_or_sections]
        rem = dim - sum(s for s in secs if s > 0)
        sections = tuple(s if s > 0 else rem for s in secs)
        num = None

    def _split(x, *, num, sections, axis):
        if sections is None:
            return tuple(jnp.split(x, num, axis=axis))
        idx = np.cumsum(sections)[:-1]
        return tuple(jnp.split(x, idx, axis=axis))

    outs = apply_op("split", _split, x, num=num, sections=sections, axis=axis)
    return list(outs)


def chunk(x, chunks, axis=0, name=None):
    return split(x, chunks, axis)


def unbind(input, axis=0):
    return unstack(input, axis)


def squeeze(x, axis=None, name=None):
    if isinstance(axis, Tensor):
        axis = int(axis.numpy())
    if isinstance(axis, (list, tuple)):
        axis = tuple(int(a) for a in axis)
    elif axis is not None:
        axis = (int(axis),)

    def _squeeze(x, *, axis):
        if axis is None:
            return jnp.squeeze(x)
        ax = tuple(a for a in axis if x.shape[a] == 1)
        return jnp.squeeze(x, axis=ax) if ax else x

    return apply_op("squeeze", _squeeze, x, axis=axis)


def unsqueeze(x, axis, name=None):
    if isinstance(axis, Tensor):
        axis = int(axis.numpy())
    if isinstance(axis, (list, tuple)):
        axis = tuple(int(a.numpy()) if isinstance(a, Tensor) else int(a) for a in axis)
    else:
        axis = (int(axis),)
    return apply_op("unsqueeze", lambda x, *, axis: jnp.expand_dims(x, axis), x, axis=axis)


def reshape(x, shape, name=None):
    shape = _shape_arg(shape)
    return apply_op("reshape", lambda x, *, shape: jnp.reshape(x, shape), x, shape=shape)


def transpose(x, perm, name=None):
    perm = tuple(int(p) for p in perm)
    return apply_op("transpose", lambda x, *, perm: jnp.transpose(x, perm), x, perm=perm)


def moveaxis(x, source, destination, name=None):
    return apply_op(
        "moveaxis",
        lambda x, *, s, d: jnp.moveaxis(x, s, d),
        x, s=tuple(np.atleast_1d(source).tolist()), d=tuple(np.atleast_1d(destination).tolist()))


def swapaxes(x, axis0, axis1, name=None):
    return apply_op("swapaxes", lambda x, *, a, b: jnp.swapaxes(x, a, b), x, a=int(axis0), b=int(axis1))


transpose_ = transpose


def flatten(x, start_axis=0, stop_axis=-1, name=None):
    def _flatten(x, *, start, stop):
        nd = x.ndim
        if nd == 0:
            return x.reshape(1)
        start_ = start % nd
        stop_ = stop % nd
        shape = x.shape[:start_] + (-1,) + x.shape[stop_ + 1:]
        return x.reshape(shape)

    return apply_op("flatten", _flatten, x, start=int(start_axis), stop=int(stop_axis))


def roll(x, shifts, axis=None, name=None):
    sh = tuple(np.atleast_1d(shifts).tolist())
    ax = None if axis is None else tuple(np.atleast_1d(axis).tolist())
    return apply_op(
        "roll",
        lambda x, *, sh, ax: jnp.roll(x, sh if ax is not None else int(np.sum(sh)), axis=ax),
        x, sh=sh, ax=ax)


def flip(x, axis, name=None):
    ax = tuple(np.atleast_1d(axis).tolist())
    return apply_op("flip", lambda x, *, ax: jnp.flip(x, axis=ax), x, ax=ax)


def rot90(x, k=1, axes=(0, 1), name=None):
    return apply_op("rot90", lambda x, *, k, axes: jnp.rot90(x, k, axes), x, k=k, axes=tuple(axes))


def tile(x, repeat_times, name=None):
    reps = _shape_arg(repeat_times)
    return apply_op("tile", lambda x, *, reps: jnp.tile(x, reps), x, reps=reps)


def expand(x, shape, name=None):
    shape = _shape_arg(shape)

    def _expand(x, *, shape):
        tgt = []
        xshape = (1,) * (len(shape) - x.ndim) + x.shape
        for s, xs in zip(shape, xshape):
            tgt.append(xs if s == -1 else s)
        return jnp.broadcast_to(x.reshape(xshape), tuple(tgt))

    return apply_op("expand", _expand, x, shape=shape)


broadcast_to = expand


def expand_as(x, y, name=None):
    return apply_op("expand_as", lambda x, y: jnp.broadcast_to(x, y.shape), x, y)


def broadcast_tensors(input, name=None):
    outs = apply_op("broadcast_tensors", lambda *xs: tuple(jnp.broadcast_arrays(*xs)), *input)
    return list(outs)


def cast(x, dtype):
    from ..core import dtype as dtype_mod

    d = dtype_mod.convert_dtype(dtype)
    token = "bfloat16" if d == np.dtype(jnp.bfloat16) else d.name

    def _cast(x, *, dtype):
        dt = jnp.bfloat16 if dtype == "bfloat16" else np.dtype(dtype)
        return x.astype(dt)

    return apply_op("cast", _cast, x, dtype=token)


def slice(input, axes, starts, ends):
    axes = tuple(int(a) for a in axes)
    starts = tuple(int(s.numpy()) if isinstance(s, Tensor) else int(s) for s in starts)
    ends = tuple(int(e.numpy()) if isinstance(e, Tensor) else int(e) for e in ends)

    def _slice(x, *, axes, starts, ends):
        idx = [builtins_slice(None)] * x.ndim
        for a, s, e in zip(axes, starts, ends):
            idx[a] = builtins_slice(s, e)
        return x[tuple(idx)]

    return apply_op("slice", _slice, input, axes=axes, starts=starts, ends=ends)


builtins_slice = __builtins__["slice"] if isinstance(__builtins__, dict) else __builtins__.slice


def strided_slice(x, axes, starts, ends, strides, name=None):
    axes = tuple(int(a) for a in axes)
    starts = tuple(int(s) for s in starts)
    ends = tuple(int(e) for e in ends)
    strides = tuple(int(s) for s in strides)

    def _ss(x, *, axes, starts, ends, strides):
        idx = [builtins_slice(None)] * x.ndim
        for a, s, e, st in zip(axes, starts, ends, strides):
            idx[a] = builtins_slice(s, e, st)
        return x[tuple(idx)]

    return apply_op("strided_slice", _ss, x, axes=axes, starts=starts, ends=ends, strides=strides)


def gather(x, index, axis=0, name=None):
    if isinstance(axis, Tensor):
        axis = int(axis.numpy())
    return apply_op(
        "gather",
        lambda x, idx, *, axis: jnp.take(x, idx.reshape(-1).astype(jnp.int32), axis=axis),
        x, index, axis=int(axis))


def gather_nd(x, index, name=None):
    def _gather_nd(x, idx):
        idx = idx.astype(jnp.int32)
        comps = tuple(idx[..., i] for i in range(idx.shape[-1]))
        return x[comps]

    return apply_op("gather_nd", _gather_nd, x, index)


def take_along_axis(arr, indices, axis, broadcast=True):
    return apply_op(
        "take_along_axis",
        lambda x, i, *, axis: jnp.take_along_axis(x, i.astype(jnp.int32), axis=axis),
        arr, indices, axis=int(axis))


def put_along_axis(arr, indices, values, axis, reduce="assign"):
    def _paa(x, i, v, *, axis, reduce):
        i = i.astype(jnp.int32)
        v = jnp.broadcast_to(jnp.asarray(v, x.dtype), i.shape)
        dims = [jnp.arange(s) for s in i.shape]
        mesh = jnp.meshgrid(*dims, indexing="ij")
        mesh[axis] = i
        coords = tuple(mesh)
        if reduce == "assign":
            return x.at[coords].set(v)
        if reduce == "add":
            return x.at[coords].add(v)
        if reduce == "multiply" or reduce == "mul":
            return x.at[coords].multiply(v)
        raise ValueError(reduce)

    return apply_op("put_along_axis", _paa, arr, indices, values, axis=int(axis), reduce=reduce)


def scatter(x, index, updates, overwrite=True, name=None):
    """reference: operators/scatter_op.cc — rows of x at `index` replaced/added."""

    def _scatter(x, idx, upd, *, overwrite):
        idx = idx.reshape(-1).astype(jnp.int32)
        if overwrite:
            return x.at[idx].set(upd)
        base = x.at[idx].set(jnp.zeros_like(upd))
        return base.at[idx].add(upd)

    return apply_op("scatter", _scatter, x, index, updates, overwrite=bool(overwrite))


def scatter_nd_add(x, index, updates, name=None):
    def _snd(x, idx, upd):
        idx = idx.astype(jnp.int32)
        comps = tuple(idx[..., i] for i in range(idx.shape[-1]))
        return x.at[comps].add(upd)

    return apply_op("scatter_nd_add", _snd, x, index, updates)


def scatter_nd(index, updates, shape, name=None):
    shape = _shape_arg(shape)

    def _snd(idx, upd, *, shape):
        idx = idx.astype(jnp.int32)
        zeros = jnp.zeros(shape, upd.dtype)
        comps = tuple(idx[..., i] for i in range(idx.shape[-1]))
        return zeros.at[comps].add(upd)

    return apply_op("scatter_nd", _snd, index, updates, shape=shape)


def index_select(x, index, axis=0, name=None):
    return gather(x, index, axis)


def index_sample(x, index):
    def _is(x, idx):
        rows = jnp.arange(x.shape[0])[:, None]
        return x[rows, idx.astype(jnp.int32)]

    return apply_op("index_sample", _is, x, index)


def index_add(x, index, axis, value, name=None):
    def _ia(x, idx, v, *, axis):
        idx = idx.reshape(-1).astype(jnp.int32)
        x_m = jnp.moveaxis(x, axis, 0)
        v_m = jnp.moveaxis(v, axis, 0)
        out = x_m.at[idx].add(v_m)
        return jnp.moveaxis(out, 0, axis)

    return apply_op("index_add", _ia, x, index, value, axis=int(axis))


def masked_select(x, mask, name=None):
    if in_trace():
        raise errors.UnimplementedError(
            "masked_select has a data-dependent output shape and cannot be traced; "
            "use paddle.where / multiplication by mask inside jit")
    arr = np.asarray(x._value)
    m = np.asarray(mask._value)
    return Tensor(arr[m])


def masked_fill(x, mask, value, name=None):
    return apply_op(
        "masked_fill", lambda x, m, v: jnp.where(m, jnp.asarray(v, x.dtype), x), x, mask, value)


def fill_diagonal(x, value, offset=0, wrap=False, name=None):
    if wrap:
        raise errors.UnimplementedError("fill_diagonal(wrap=True) not supported yet")

    def _fd(x, *, value, offset):
        rows, cols = x.shape[0], x.shape[1]
        if offset >= 0:
            n = min(rows, cols - offset)
            r = jnp.arange(max(n, 0))
            return x.at[r, r + offset].set(value)
        n = min(rows + offset, cols)
        r = jnp.arange(max(n, 0))
        return x.at[r - offset, r].set(value)

    out = apply_op("fill_diagonal", _fd, x, value=float(value), offset=int(offset))
    x._assign_result(out)
    return x


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    """reference: operators/pad_op.cc / pad3d. `pad` is per-dim pairs (paddle
    flat format: last-dim-first pairs when len(pad) < 2*ndim)."""
    if isinstance(pad, Tensor):
        pad = [int(v) for v in pad.numpy().reshape(-1)]
    pad = tuple(int(p) for p in pad)

    def _pad(x, *, pad, mode, value, data_format):
        nd = x.ndim
        if len(pad) == 2 * nd:
            width = [(pad[2 * i], pad[2 * i + 1]) for i in range(nd)]
        else:
            # torch-style: pairs for trailing spatial dims (NCHW/NHWC aware)
            npairs = len(pad) // 2
            width = [(0, 0)] * nd
            if data_format.startswith("NC"):
                dims = list(range(nd - npairs, nd))
            else:
                dims = list(range(1, 1 + npairs))
            for i, d in enumerate(reversed(dims)):
                width[d] = (pad[2 * i], pad[2 * i + 1])
        jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge",
                 "circular": "wrap"}[mode]
        if jmode == "constant":
            return jnp.pad(x, width, mode=jmode, constant_values=value)
        return jnp.pad(x, width, mode=jmode)

    return apply_op("pad", _pad, x, pad=pad, mode=mode, value=value, data_format=data_format)


def repeat_interleave(x, repeats, axis=None, name=None):
    if isinstance(repeats, Tensor):
        repeats = tuple(int(v) for v in repeats.numpy().reshape(-1))
    return apply_op(
        "repeat_interleave",
        lambda x, *, repeats, axis: jnp.repeat(x, np.asarray(repeats) if not isinstance(repeats, int) else repeats, axis=axis),
        x, repeats=repeats, axis=None if axis is None else int(axis))


def unique(x, return_index=False, return_inverse=False, return_counts=False, axis=None,
           dtype="int64", name=None):
    if in_trace():
        raise errors.UnimplementedError("unique has data-dependent shape; not traceable")
    arr = np.asarray(x._value)
    res = np.unique(arr, return_index=return_index, return_inverse=return_inverse,
                    return_counts=return_counts, axis=axis)
    if not isinstance(res, tuple):
        return Tensor(res)
    return tuple(Tensor(r) for r in res)


def unique_consecutive(x, return_inverse=False, return_counts=False, axis=None, dtype="int64",
                       name=None):
    if in_trace():
        raise errors.UnimplementedError("unique_consecutive not traceable")
    arr = np.asarray(x._value).reshape(-1) if axis is None else np.asarray(x._value)
    mask = np.ones(len(arr), dtype=bool)
    mask[1:] = arr[1:] != arr[:-1]
    out = arr[mask]
    outs = [Tensor(out)]
    if return_inverse:
        inv = np.cumsum(mask) - 1
        outs.append(Tensor(inv.astype(np.int64)))
    if return_counts:
        idx = np.nonzero(mask)[0]
        counts = np.diff(np.append(idx, len(arr)))
        outs.append(Tensor(counts.astype(np.int64)))
    return outs[0] if len(outs) == 1 else tuple(outs)


def as_complex(x, name=None):
    return apply_op("as_complex", lambda x: jax.lax.complex(x[..., 0], x[..., 1]), x)


def as_real(x, name=None):
    return apply_op("as_real", lambda x: jnp.stack([jnp.real(x), jnp.imag(x)], axis=-1), x)


def crop(x, shape=None, offsets=None, name=None):
    shape = _shape_arg(shape)
    offsets = tuple(int(o) for o in (offsets or [0] * len(shape)))

    def _crop(x, *, shape, offsets):
        idx = tuple(builtins_slice(o, o + s if s != -1 else None) for o, s in zip(offsets, shape))
        return x[idx]

    return apply_op("crop", _crop, x, shape=shape, offsets=offsets)


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    def _shard(x, *, index_num, nshards, shard_id, ignore_value):
        size = (index_num + nshards - 1) // nshards
        lo = shard_id * size
        in_range = (x >= lo) & (x < lo + size)
        return jnp.where(in_range, x - lo, ignore_value)

    return apply_op("shard_index", _shard, input, index_num=index_num, nshards=nshards,
                    shard_id=shard_id, ignore_value=ignore_value)


def tolist(x):
    """Nested Python list of the tensor's values (reference:
    tensor/manipulation.py:45)."""
    import numpy as np

    arr = x.numpy() if hasattr(x, "numpy") else np.asarray(x)
    return arr.tolist()


def crop_tensor(x, shape=None, offsets=None, name=None):
    """Crop ``shape``-sized window at ``offsets`` (reference:
    fluid/layers/nn.py crop_tensor / operators/crop_tensor_op.cc).
    -1 in shape means "to the end of that dim"."""
    from ..core.dispatch import apply_op

    xnd = len(x.shape)
    shape = list(shape if shape is not None else x.shape)
    offsets = list(offsets if offsets is not None else [0] * xnd)

    def _crop(x, *, shape, offsets):
        import builtins

        sl = tuple(
            builtins.slice(o, x.shape[i] if s == -1 else o + s)
            for i, (o, s) in enumerate(zip(offsets, shape)))
        return x[sl]

    return apply_op("crop_tensor", _crop, x, shape=tuple(int(s) for s in shape),
                    offsets=tuple(int(o) for o in offsets))


def reverse(x, axis, name=None):
    """Legacy alias of flip (reference: fluid/layers/nn.py reverse)."""
    return flip(x, axis)

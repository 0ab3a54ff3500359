"""Mixture-of-Experts with expert parallelism over the 'ep' mesh axis.

The reference (~v2.1) predates its MoE work, so this is green-field
TPU-native design (like ring attention). Expert FFN weights are stacked
[E, ...] and SHARDED over 'ep'. Two dispatch modes behind one API:

- ``dense``: every expert's FFN runs for every token and the top-k gate
  mask zeroes the rest; the expert-dim contraction compiles to a psum
  over the ep axis. No capacity overflow, static shapes, but E/k wasted
  FLOPs — right only for small expert counts.
- ``capacity`` (GShard/Switch): each expert processes at most
  C = ceil(capacity_factor * k * N / E) tokens; tokens claim capacity
  slots in order (per-expert cumsum) and overflow tokens DROP that
  expert's contribution, exactly the GShard top-2 formulation. Dispatch
  and combine are one-hot einsums — static shapes end to end — so the
  FFN compute is E*C = k*capacity_factor*N token-slots instead of
  E*N: the compute-sparse path. The [E, C, H] expert buffers inherit
  the 'ep' sharding from the weights, so XLA materialises the
  token->expert shuffle as collectives over ep (the all_to_all of the
  GShard paper) while the FFN einsums stay local per expert shard.

- ``alltoall``: the literal GShard layout under ``jax.shard_map`` —
  tokens batch-sharded over the data axes x ep (GShard's groups), each
  shard routes its LOCAL tokens into [E, C, H] capacity buffers, ``lax.all_to_all`` swaps the
  expert dim across shards (each shard then holds its own E/ep experts'
  tokens from every shard), the FFN runs on local expert weights only,
  and a second all_to_all routes results back. Guaranteed all-to-all on
  ICI + per-shard compute exactly E*C/ep token-slots, independent of
  the XLA partitioner's einsum strategy.

``dispatch_mode='auto'`` (default) picks capacity for E >= 8, dense
below — at tiny E dense dispatch wastes little and never drops.
'alltoall' is explicit: it requires a live global mesh with ep > 1,
batch divisible by ep, and E divisible by ep.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..core.dispatch import apply_op
from jax import shard_map


def _capacity_combine(xf, probs, top_k, cap):
    """GShard combine/dispatch build for one token group (fig. 6 of the
    paper): tokens claim per-expert capacity slots in order, overflow
    drops. Returns (combine [N,E,C] f32, dispatch [N,E,C], top1 idx)."""
    n, e = probs.shape
    topv, topi = jax.lax.top_k(probs, top_k)           # [N, k]
    gates = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    combine = jnp.zeros((n, e, cap), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)              # slots claimed
    for j in range(top_k):
        mask_j = jax.nn.one_hot(topi[:, j], e)         # [N, E]
        # 0-indexed slot: exclusive cumsum over tokens + slots taken by
        # earlier choices (choice 0 claims before choice 1, like GShard)
        pos_in_e = jnp.cumsum(mask_j, axis=0) - mask_j + counts
        counts = counts + jnp.sum(mask_j, axis=0)
        slot = jnp.sum(pos_in_e * mask_j, axis=-1)     # [N]
        keep = (slot < cap).astype(jnp.float32)
        combine = combine + (
            gates[:, j, None, None] * keep[:, None, None]
            * mask_j[:, :, None]
            * jax.nn.one_hot(slot, cap)[:, None, :])
    dispatch = (combine > 0).astype(xf.dtype)
    return combine, dispatch, topi


def _gshard_aux(probs, topi):
    """GShard aux loss from the full softmax + top-1 routing fraction."""
    e = probs.shape[-1]
    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], e), axis=0)
    imp = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac * imp)


class MoELayer(nn.Layer):
    """Top-k gated expert FFN block (pre-norm residual not included).

    forward: [B, S, H] -> [B, S, H]. Gate scores are softmaxed over the
    selected top_k experts (renormalized, Switch/GShard style); the
    auxiliary load-balancing loss (GShard aux) is routed through
    ``nn.aux_loss.emit_aux_loss``: in eager mode it lands on
    ``self.aux_loss`` (add it to the objective yourself); inside
    ``spmd.build_train_step`` / ``comm_opt`` train steps it is collected
    into the compiled loss automatically; in inference traces
    (jit.save / onnx.export / generation) it is dropped so no tracer
    escapes onto the layer. Pipeline/FSDP per-stage applies currently
    drop it too — add the aux term explicitly there if it matters.
    """

    def __init__(self, hidden_size, ffn_hidden, num_experts, top_k=2,
                 shard_axis="ep", aux_weight=0.01, dispatch_mode="auto",
                 capacity_factor=1.25):
        super().__init__()
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.aux_weight = float(aux_weight)
        if dispatch_mode == "auto":
            dispatch_mode = "capacity" if self.num_experts >= 8 else "dense"
        if dispatch_mode not in ("dense", "capacity", "alltoall"):
            raise ValueError(f"dispatch_mode must be 'auto'/'dense'/"
                             f"'capacity'/'alltoall', got {dispatch_mode!r}")
        self.shard_axis = shard_axis
        self.dispatch_mode = dispatch_mode
        self.capacity_factor = float(capacity_factor)
        self.gate = nn.Linear(hidden_size, num_experts)
        k = 1.0 / np.sqrt(hidden_size)
        self.w_up = self.create_parameter(
            [num_experts, hidden_size, ffn_hidden],
            default_initializer=nn.initializer.Uniform(-k, k))
        k2 = 1.0 / np.sqrt(ffn_hidden)
        self.w_down = self.create_parameter(
            [num_experts, ffn_hidden, hidden_size],
            default_initializer=nn.initializer.Uniform(-k2, k2))
        # experts live sharded over 'ep' (spmd.build_train_step honors
        # mp_spec); the contraction over the expert dim emits the psum
        self.w_up.mp_spec = P(shard_axis)
        self.w_down.mp_spec = P(shard_axis)
        self.aux_loss = None

    def forward(self, x):
        logits = self.gate(x)  # [B, S, E]

        def _moe(x, logits, w_up, w_down, *, top_k):
            e = logits.shape[-1]
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            # exact top-k mask from indices (a >=threshold compare would
            # select every tied expert, e.g. all of them on the uniform
            # probs a zero/padding token produces)
            idx = jax.lax.top_k(probs, top_k)[1]            # [B, S, k]
            mask = jnp.sum(jax.nn.one_hot(idx, e, dtype=probs.dtype),
                           axis=-2)
            mask = jnp.minimum(mask, 1.0)
            gates = probs * mask
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
            # dense dispatch: every expert on every token, gated sum.
            # w_up/w_down sharded on e -> per-shard partial experts; the
            # final contraction over e all-reduces over 'ep'.
            h = jnp.einsum("bsh,ehf->besf", x, w_up)
            h = jax.nn.gelu(h)
            y = jnp.einsum("besf,efh->besh", h, w_down)
            out = jnp.einsum("bse,besh->bsh", gates.astype(y.dtype), y)
            # GShard aux loss: E * sum_e (frac tokens routed to e *
            # mean gate prob of e)
            frac = jnp.mean(mask, axis=(0, 1))
            imp = jnp.mean(probs, axis=(0, 1))
            aux = e * jnp.sum(frac / top_k * imp)
            return out, aux.astype(x.dtype)

        def _moe_capacity(x, logits, w_up, w_down, *, top_k, cap_factor):
            """GShard top-k capacity dispatch (Lepikhin et al. 2020,
            algorithm in fig. 6): one-hot dispatch/combine einsums with
            per-expert capacity C and drop-overflow. Static shapes; the
            ep-sharded [E, C, H] buffers make the dispatch einsum the
            cross-expert shuffle (XLA picks the collective)."""
            b, s, hdim = x.shape
            e = logits.shape[-1]
            n = b * s
            cap = max(1, int(np.ceil(cap_factor * top_k * n / e)))
            xf = x.reshape(n, hdim)
            probs = jax.nn.softmax(
                logits.astype(jnp.float32), axis=-1).reshape(n, e)
            combine, dispatch, topi = _capacity_combine(xf, probs, top_k,
                                                        cap)
            buf = jnp.einsum("nec,nh->ech", dispatch, xf)
            h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", buf, w_up))
            y = jnp.einsum("ecf,efh->ech", h, w_down)
            out = jnp.einsum("nec,ech->nh", combine.astype(y.dtype), y)
            aux = _gshard_aux(probs, topi)
            return out.reshape(b, s, hdim), aux.astype(x.dtype)

        if self.dispatch_mode == "alltoall":
            out, aux = self._forward_alltoall(x, logits)
        elif self.dispatch_mode == "capacity":
            out, aux = apply_op("moe_ffn_capacity", _moe_capacity, x,
                                logits, self.w_up, self.w_down,
                                top_k=self.top_k,
                                cap_factor=self.capacity_factor)
        else:
            out, aux = apply_op("moe_ffn", _moe, x, logits, self.w_up,
                                self.w_down, top_k=self.top_k)
        from ..nn.aux_loss import emit_aux_loss

        emit_aux_loss(self, aux * self.aux_weight)
        return out

    def _forward_alltoall(self, x, logits):
        """Explicit GShard a2a dispatch under shard_map over 'ep' (see
        module docstring): tokens batch-sharded, experts local, two
        lax.all_to_all around the expert FFN."""
        from ..distributed import topology

        mesh = topology.get_global_mesh()
        axis = self.shard_axis
        ep = mesh.shape.get(axis, 1)
        e, top_k, cf = self.num_experts, self.top_k, self.capacity_factor
        if ep <= 1:
            raise ValueError(
                "dispatch_mode='alltoall' needs a global mesh with "
                f"{axis!r} > 1 (set_global_mesh(build_mesh(ep=...)))")
        if e % ep:
            raise ValueError(f"num_experts={e} must divide over "
                             f"{axis}={ep} for all_to_all dispatch")
        # tokens stay sharded over the data axes TOO (GShard groups =
        # product of data axes x ep; the a2a rides only the ep sub-axis)
        # — no per-step data->ep resharding. Shares shard_batch's axis
        # derivation so the incoming batch layout always matches.
        from ..distributed.topology import data_axes as _data_axes

        tok_axes = tuple(ax for ax in _data_axes(mesh)
                         if ax != axis) + (axis,)
        groups = int(np.prod([mesh.shape[ax] for ax in tok_axes]))
        b = int(x.shape[0])
        if b % groups:
            raise ValueError(f"batch {b} must be divisible by the token "
                             f"shard count {groups} (axes {tok_axes})")

        def local_fn(x, logits, w_up, w_down):
            # x: [B/groups, S, H] (groups = data axes x ep shards);
            # w_up/w_down: [E/ep, ...] (local experts)
            b_loc, s, hdim = x.shape
            n = b_loc * s
            cap = max(1, int(np.ceil(cf * top_k * n / e)))
            xf = x.reshape(n, hdim)
            probs = jax.nn.softmax(
                logits.astype(jnp.float32), axis=-1).reshape(n, e)
            combine, dispatch, topi = _capacity_combine(xf, probs, top_k,
                                                        cap)
            buf = jnp.einsum("nec,nh->ech", dispatch, xf)  # [E, C, H]
            # shard r keeps experts [r*E/ep, (r+1)*E/ep): swap the
            # expert dim across shards, stacking every shard's tokens
            # for my experts along capacity
            buf = jax.lax.all_to_all(buf, axis, split_axis=0,
                                     concat_axis=1, tiled=True)
            h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", buf, w_up))
            y = jnp.einsum("ecf,efh->ech", h, w_down)
            y = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                                   tiled=True)              # [E, C, H]
            out = jnp.einsum("nec,ech->nh", combine.astype(y.dtype), y)
            aux = jax.lax.pmean(_gshard_aux(probs, topi), tok_axes)
            return out.reshape(b_loc, s, hdim), aux.astype(x.dtype)

        def _a2a(x, logits, w_up, w_down):
            tok = P(tok_axes, None, None)
            wsp = P(axis, None, None)
            fn = shard_map(local_fn, mesh=mesh,
                           in_specs=(tok, tok, wsp, wsp),
                           out_specs=(tok, P()),
                           check_vma=False)
            return fn(x, logits, w_up, w_down)

        from ..core.dispatch import in_trace

        if not in_trace():
            # eager values sit committed on one device; move them onto
            # the mesh IN PLACE (value-preserving, keeps tape identity —
            # the eager-collective placement pattern of collective.py)
            from jax.sharding import NamedSharding

            def _place(t, spec):
                if not isinstance(t._value, jax.core.Tracer):
                    t._value = jax.device_put(t._value,
                                              NamedSharding(mesh, spec))

            _place(x, P())
            _place(logits, P())
            _place(self.w_up, P(axis))
            _place(self.w_down, P(axis))
        # cache key must discriminate everything the closure captures:
        # the mesh's token-shard group count, and the routing params
        # (top_k / capacity_factor / num_experts) — two layers differing
        # only in top_k would otherwise share the cached jit
        return apply_op(
            f"moe_ffn_a2a_{axis}{ep}_g{groups}_m{id(mesh)}"
            f"_k{top_k}_cf{cf}_e{e}",
            _a2a, x, logits, self.w_up, self.w_down)

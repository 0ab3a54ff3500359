"""Activation recompute (reference: fleet/utils/recompute.py PyLayer-based
RecomputeFunction; static analog backward.py:729
_append_backward_ops_with_checkpoints_).

TPU-native: in traced mode this is ``jax.checkpoint`` — XLA rematerialises
the segment in backward — under one policy: what a kernel inside the
segment offered by name (``ops/residuals.py``: the streaming flash kernel's
output and log-sum-exp) is kept from the first forward, so the backward
does not run the kernel a second time to get it; everything else is
recomputed. What is kept follows from the segment's trace alone: a segment
with no such kernel differentiates to what a plain ``jax.checkpoint`` gives.
Where ``function`` is a Layer (or a Layer's bound method), the buffers it
rewrites and the auxiliary losses it emits leave the checkpointed function
as outputs and are put back where an unwrapped call would have left them.
In eager mode recompute is the
identity: the tape's per-op cached vjps already recompute each op's
forward inside the backward (inherent rematerialisation), and wrapping
the segment as one opaque op would hide captured Layer parameters from
the tape.
"""
import jax

from ....core import dispatch
from ....core.tensor import Tensor
from ....ops import residuals


def recompute(function, *args, **kwargs):
    preserve_rng_state = kwargs.pop("preserve_rng_state", True)
    if dispatch.in_trace():
        from ....nn import Layer
        from ....nn.aux_loss import (collect_aux_losses, forward_aux_loss,
                                     total_aux_loss)

        arrs = [a._value if isinstance(a, Tensor) else a for a in args]
        owner = getattr(function, "__self__", function)
        layer = owner if isinstance(owner, Layer) else None

        def pure(*xs):
            # what a segment leaves behind besides its outputs has to leave
            # the checkpointed function as an output too, or a tracer of
            # this inner trace stays on the layer: the buffers it rewrote
            # (batch-norm statistics, a router's selection bias) and the
            # auxiliary losses it emitted
            before = layer.functional_state()[1] if layer else {}
            with collect_aux_losses() as auxes:
                outs = function(*[Tensor(x, stop_gradient=True) for x in xs],
                                **kwargs)
            if isinstance(outs, (tuple, list)):
                outs = tuple(o._value if isinstance(o, Tensor) else o
                             for o in outs)
            elif isinstance(outs, Tensor):
                outs = outs._value
            after = layer.functional_state()[1] if layer else {}
            rewritten = {n: v for n, v in after.items()
                         if v is not before[n]}
            if rewritten:
                layer.load_functional_state(None, before)
            return outs, rewritten, (total_aux_loss(auxes) if auxes else None)

        out, rewritten, aux = jax.checkpoint(
            pure, policy=residuals.keep_offered)(*arrs)
        if rewritten:
            layer.load_functional_state(None, rewritten)
        if aux is not None:
            forward_aux_loss(aux)
        if isinstance(out, tuple):
            return tuple(Tensor(o) for o in out)
        return Tensor(out)

    # Eager: run the segment normally. The tape's per-op vjps already
    # recompute each op's forward inside the cached backward (inherent
    # rematerialisation), and wrapping the segment as one op would hide
    # captured Layer parameters from the tape (their grads would be lost).
    return function(*args, **kwargs)

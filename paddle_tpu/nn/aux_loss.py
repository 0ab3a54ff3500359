"""Auxiliary-loss plumbing for layers whose forward emits a side loss
(MoE load balancing — reference: incubate/distributed/models/moe in later
Paddle revs; GShard aux loss).

The hazard: a layer storing ``self.aux_loss`` during a jax trace leaves
an escaped tracer on the (mutable, long-lived) Layer object, which blows
up the next time anyone touches it. So emission is routed by context:

- under an active ``collect_aux_losses()`` block (train-step builders:
  spmd/comm_opt), values go to the collector and join the objective;
- under a bare trace (jit.save, onnx.export, generation), values are
  DROPPED — inference traces must not retain training-only tracers;
- in eager mode, the concrete value is stored on ``layer.aux_loss`` for
  the user to add to their loss by hand.
"""
import contextlib
import contextvars

from ..core import dispatch

_COLLECTOR = contextvars.ContextVar("aux_loss_collector", default=None)


@contextlib.contextmanager
def collect_aux_losses():
    """Collect every aux loss emitted by layers during the block; yields
    the list (of raw arrays) to add to the training objective."""
    acc = []
    token = _COLLECTOR.set(acc)
    try:
        yield acc
    finally:
        _COLLECTOR.reset(token)


def emit_aux_loss(layer, value):
    """Called by a Layer's forward with its auxiliary loss contribution."""
    from ..core.tensor import Tensor

    raw = value._value if isinstance(value, Tensor) else value
    acc = _COLLECTOR.get()
    if acc is not None:
        acc.append(raw)
        layer.aux_loss = None
    elif dispatch.in_trace():
        layer.aux_loss = None
    else:
        layer.aux_loss = value


def forward_aux_loss(value):
    """Hand a value collected in an inner block (a recomputed segment's
    own collector) on to the enclosing collector; dropped without one,
    as a bare trace drops an emission."""
    acc = _COLLECTOR.get()
    if acc is not None:
        acc.append(value)


def total_aux_loss(collected):
    """Sum a collector's list (0.0 when nothing was emitted)."""
    total = None
    for v in collected:
        total = v if total is None else total + v
    return 0.0 if total is None else total


def clear_direct_aux_losses(layer):
    """Null every sublayer's ``aux_loss`` BEFORE a traced forward, so the
    post-forward sweep only sees losses emitted by *this* trace — not a
    concrete leftover from an earlier eager run of a branch the traced
    forward never executes (which would bake a constant into the jitted
    loss)."""
    for _, sub in layer.named_sublayers(include_self=True):
        if getattr(sub, "aux_loss", None) is not None:
            sub.aux_loss = None


def sweep_direct_aux_losses(layer, collected):
    """Legacy contract: layers that assign ``self.aux_loss`` directly
    (without emit_aux_loss) still get their term collected — and cleared,
    so the tracer never outlives the trace. Call clear_direct_aux_losses
    before the forward and this after it, while still inside the trace.
    emit_aux_loss users are excluded naturally: under a collector it
    nulls ``layer.aux_loss`` itself."""
    from ..core.tensor import Tensor

    for _, sub in layer.named_sublayers(include_self=True):
        aux = getattr(sub, "aux_loss", None)
        if aux is not None:
            collected.append(aux._value if isinstance(aux, Tensor) else aux)
            sub.aux_loss = None

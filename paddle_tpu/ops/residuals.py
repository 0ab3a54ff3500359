"""Kernel residuals that a recomputed block keeps.

A hand-written kernel is the most expensive operation of its block, and
under ``fleet.utils.recompute`` it would run a second time in the backward
pass only to make again what its own backward rule reads. So a kernel's
forward rule passes those arrays through ``offer`` under one of ``NAMES``,
and ``recompute`` checkpoints its block under ``keep_offered``: what a
block's trace offers is saved from its first forward, everything else is
recomputed as before. A block whose trace holds no offer differentiates to
the program ``jax.checkpoint`` alone gives it. Outside a ``jax.checkpoint``
an offer is an identity that lowers to nothing.

Offer what only the kernel can make (its output, its row statistics), not
its inputs: those come back from the block's projections, which the
recomputed forward runs anyway.

The policy reaches the operations a block's trace holds directly, under a
``jit`` and inside a ``shard_map`` (jax's partial evaluation hands it on
into the map's body, and what is kept leaves the map as an output): a
kernel that ``ops.placement.on_mesh`` shards over an announced mesh keeps
its residuals too. The counter says what happened in a trace: ``offered``
by a kernel's forward rule, ``kept`` by a block's policy — a step that
recomputes its blocks and reads ``offered`` without ``kept`` runs its
kernels twice (the policy did not reach them).
"""
import jax
from jax.ad_checkpoint import checkpoint_name

from ..obs import metrics as obs_metrics

# the streaming flash kernel's output and log-sum-exp of its score rows
# (ops/pallas/flash_attention.py _flash_fwd)
NAMES = ("flash_stream.out", "flash_stream.lse")
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*NAMES)

_RESIDUAL_TOTAL = obs_metrics.counter(
    "paddle_tpu_recompute_residual_total",
    "kernel residuals by name (ops/residuals.py NAMES) and event: offered "
    "= a kernel's forward rule tagged one while a program was traced | "
    "kept = the policy of a fleet.utils.recompute block saved one for its "
    "backward instead of running the kernel again; trace time, one count "
    "an array",
    labelnames=("name", "event"))


def offer(x, name):
    """``x`` tagged ``name`` for the blocks that keep it; the same value."""
    if name not in NAMES:
        raise ValueError(f"{name!r} is not one of {NAMES}")
    _RESIDUAL_TOTAL.inc(name=name, event="offered")
    return checkpoint_name(x, name)


def keep_offered(prim, *avals, **params):
    """``jax.checkpoint`` policy: save what ``offer`` tagged, nothing else
    (``save_only_these_names(*NAMES)``, counting what it saves)."""
    keep = _SAVE_NAMED(prim, *avals, **params)
    if keep:
        _RESIDUAL_TOTAL.inc(name=params["name"], event="kept")
    return keep

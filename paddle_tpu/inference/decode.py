"""Continuous-batching autoregressive decode engine (ROADMAP item 1).

The dynamic-batching engine (batching.py) serves ONE-SHOT inference:
a request is a batch of rows, a reply is the whole answer. Token
streaming is a different shape of work — a request is a *sequence*
that produces one token per model step for hundreds of steps, and
sequences finish at wildly different times. Padding a fixed batch to
the slowest member (the one-shot strategy) leaves the chip idle on
every retired row; PERF.md pegs the untuned decode path at 0.2–0.5 of
roofline for exactly this reason. The structural fix is
**iteration-level scheduling** (the continuous-batching design of
Orca/vLLM, and the concurrency lesson of PAPERS.md "Exploring the
limits of Concurrency in ML Training on Google TPUs"): the scheduler
re-forms the running batch EVERY step, so sequences join the moment a
slot frees and leave the moment they finish::

    requests --> bounded queue --> iteration scheduler
                  (shed, purge)        |
                                       v            per-(phase, rows,
      admit joiners ---> PREFILL program             seq) AOT cache
      every iteration     (rows_bucket, prompt_bucket)   |  artifact
                                       |                 |  store keys
      one token/seq  <--- DECODE STEP program  <---------+
      every iteration     (slot_bucket, seq_bucket)
                                       |
      retire on eos/max/deadline; slot freed for the next joiner

**KV slots.** Each running sequence owns a slot of paged host-side
KV-cache storage (:class:`_KVSlots`): per-slot buffers grow in
power-of-2 pages, so memory tracks actual sequence lengths, and each
decode step gathers the active slots into a fixed-shape batch
``[slot_bucket, seq_bucket, ...]`` — the same power-of-2 shape-bucket
machinery the one-shot engine uses, which is what keeps the number of
compiled programs a small ladder instead of one per (batch, length)
pair. Decode-step exports flow through the PR 10 artifact store under
their own keys (phase + seq bucket encoded in the signature), so a
fresh decode replica warms its whole program ladder with zero inline
XLA compiles once any replica has published it.

**Bitwise determinism contract** (verified in tests/test_decode.py):
a sequence decoded inside a continuous batch emits the SAME tokens as
the same sequence decoded solo, under greedy sampling, across
join/leave events and every wire dtype of its feature arrays. This
holds because (a) rows of XLA's row-independent CPU programs are
bitwise stable across batch sizes >= 2 (the PR 4 result; slot buckets
are floored at 2 for exactly this reason), and (b) masked attention
with exact ``-inf`` score masking and post-softmax zeroing is bitwise
stable across KV padding widths — padded positions contribute exact
``0.0`` terms, which pass through XLA's reductions unchanged
(measured on this jaxlib; the model contract below requires that
masking discipline). The engine zero-fills gathered KV beyond each
sequence's length so stale slot contents can never reach a program.

**Model contract** (:class:`DecodeModel`): two pure jax functions
over flat positional args (export-friendly, weights as runtime args):

    prefill_fn(params, tokens[b,p] i32, lengths[b] i32, *feat)
        -> (logits[b, vocab] at each row's LAST valid position,
            *kv[b, p, ...])  — one array per kv_spec entry
    step_fn(params, tokens[b] i32, positions[b] i32,
            *kv[b, s, ...], *feat)
        -> (logits[b, vocab], *new_kv[b, ...])
        The step must write the incoming token's kv at ``positions``
        into its OWN attention (the passed kv buffers are donated
        scratch) and return the new entries for the host to persist.

    Padding rows carry token 0 / length 1 / position 0 / zero kv /
    zero features; the model must produce finite outputs for them
    (mask invalid positions to -inf BEFORE softmax and zero the
    probabilities after, never ``nan``).

**Robustness** is the PR 5 plumbing, unchanged in shape: per-program
circuit breakers (:class:`batching._Breaker`), a scheduler watchdog
(heartbeat per iteration; a dead/wedged scheduler is restarted, the
active sequences fail retryable — wire status 2 — and parked requests
are served by the replacement), bounded queue with
:class:`batching.EngineOverloaded` shedding, and chaos sites
``serving.decode.admit`` / ``serving.decode.prefill`` /
``serving.decode.step``. Deadlines become **per-token SLOs**: a
request's wire budget bounds the time to its FIRST token and every
inter-token gap; a sequence that blows its per-token budget fails
retryable and its KV slot is purged immediately (no slot leak against
the slot cap — chaos-verified at ``serving.decode.step``).

Telemetry: per-token latency and time-to-first-token histograms
(``paddle_decode_ttft_seconds`` / ``paddle_decode_intertoken_seconds``)
are engine-owned obs.metrics instruments exposed through the process
registry (wire cmd 6 / ``/metrics``); traced requests get per-token
``serving.decode.token`` spans in the obs.tracing buffer; every
program materialization lands in the compile ledger under
``decode/...`` labels.

**Stream resume (PR 17).** A running sequence can be checkpointed into
a self-describing *kv-snapshot block* (``wire_spec.encode_kv_snapshot``:
paged KV prefix + prompt + generated-token tail + greedy scalars, under
a versioned header carrying the model fingerprint, weights digest,
quant mode, and mesh descriptor) and resumed on ANY replica of the same
identity via
:meth:`DecodeEngine.resume`, which re-enters the step loop at the exact
sequence position. Greedy decode is RNG-free and the step ladder is
shared, so the resumed suffix is bitwise identical to an unbroken solo
decode — the PR 12 solo-vs-batch contract holds across the migration
boundary. A replica whose identity skews from the header refuses with
:class:`SnapshotRefused` (wire status 2), never silent wrong tokens.
Requests opt in per-sequence (``snapshot_every=N`` — the wire cadence
bits of the 0x5C field); snapshot assembly failures degrade to "no
resume point", never to a failed stream (chaos sites
``serving.decode.snapshot`` / ``serving.decode.resume``).

**KV reuse ladder (PR 19).** Two rungs on top of the substrate above,
both compiled to the same fixed (phase, rows, seq) program ladder —
never data-dependent shapes. (a) *Content-addressed prefix caching*
(:mod:`prefix_cache`): token prefixes hash at page-aligned boundaries
(pages = ``min_seq_bucket`` tokens; chain hashes, so every boundary of
a prompt costs one linear pass) and the KV pages of hot prefixes live
once in the refcounted page pool of :class:`_KVSlots`. A hit installs
the cached pages into a fresh slot by reference — copy-on-write: a
slot writing into a shared page clones it first, and release
decrements, never frees, a page another sequence (or the cache)
holds — so model programs run only over the uncached suffix, fed
token-by-token through the already-warm step rungs. To keep the PR 12
bitwise contract, EVERY emitted first token comes from step-shaped
math: a cold prefill gains one *finishing step* (re-feeding the last
prompt token at its position — the KV row it writes is bitwise equal
to the prefill program's, and its logits are bitwise equal to the
prefill logits on this jaxlib), which is the identical computation the
prefix-hit path's last suffix step performs — hit-vs-cold token
equality holds by construction, not by tolerance. (b) *Speculative
decoding*: a cheap ``DecodeModel.draft`` companion proposes k-1
tokens per iteration and the target verifies all k positions in ONE
batched ``verify`` program — k unrolled step_fn iterations fused in
one jit, bitwise equal per position to k sequential step dispatches —
so greedy accept/reject emits exactly the tokens non-speculative
greedy would (rejected runs roll back by committing only accepted KV
entries; the gathered device buffers are donated scratch). Clients
opt in per request (wire 0x5C bit 61); non-opted streams are
byte-identical.

Env knobs (constructor kwargs override):
    PADDLE_TPU_DECODE_SNAPSHOT_EVERY   default snapshot cadence in
                                       generated tokens (0 = never;
                                       requests override per-sequence)
    PADDLE_TPU_PREFIX_DIR              persistent prefix-cache tier
                                       (artifact-store layout; unset =
                                       in-memory tier only)
    PADDLE_TPU_PREFIX_MAX_BYTES        prefix-cache byte budget
                                       (default 256 MiB)
    PADDLE_TPU_PREFIX_DISABLE          "1" disables prefix caching
    PADDLE_TPU_SPEC_K                  speculative tokens per verify
                                       (k >= 2 enables speculation on
                                       draft-equipped engines; 0 = off)
    PADDLE_TPU_DECODE_MAX_SLOTS        concurrent sequences (default 8)
    PADDLE_TPU_DECODE_MAX_SEQ_LEN      prompt+generated cap (default 256)
    PADDLE_TPU_DECODE_MAX_QUEUE        bounded wait queue (default 64)
    PADDLE_TPU_DECODE_MIN_SEQ_BUCKET   smallest kv/prompt bucket (8)
    PADDLE_TPU_DECODE_MAX_NEW_TOKENS   default per-request cap (64)
    PADDLE_TPU_DECODE_MAX_PROMPT_LEN   admission cap on prompt length
                                       (default max_seq_len)
    PADDLE_TPU_SERVING_MESH            serving mesh descriptor ("tp2",
                                       "fsdp2xtp2"; default single) —
                                       params shard once and the whole
                                       program ladder becomes
                                       per-(bucket, mesh) pjit programs
    (breaker/watchdog knobs: the PADDLE_TPU_SERVING_* family)
"""
import hashlib
import os
import threading
import time
import traceback
import weakref

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import tracing as obs_tracing
from ..obs.ledger import LEDGER
from ..resilience import chaos
from ..resilience.retry import _env_float, _env_int
from ..serialize import artifact_store as _artifacts
from . import sharding as _sharding
from . import wire_spec as _wire_spec
from .prefix_cache import PrefixCache, feature_seed, prefix_hashes
from ..serialize.export import (canonical_module_bytes, deserialize_exported,
                                model_fingerprint, serialize_exported)
from .batching import (BucketQuarantined, DeadlineExceeded, EngineClosed,
                       EngineOverloaded, RetryableError, SchedulerRestarted,
                       _Breaker, bucket_rows, store_backed_compile)

# numpy dtypes the spec admits as decode prompts/token ids (wire codes
# in wire_spec.TOKEN_DTYPE_CODES; the token chunks echo the prompt's
# dtype bit for bit)
_TOKEN_DTYPES = frozenset(_wire_spec.NUMPY_BY_CODE[c]
                          for c in _wire_spec.TOKEN_DTYPE_CODES)


class SnapshotRefused(RetryableError):
    """A kv snapshot does not match this replica's identity
    (fingerprint / quant / mesh / shape contract skew) or cannot fit
    its configured limits. Maps to wire status 2: the stream is
    resumable on a matching replica — refusing is ALWAYS preferable to
    decoding garbage from a foreign KV layout."""

# Machine-checked lock order (tools/tracelint.py --concurrency, TPU309):
# the decode engine lock is a SUBSYSTEM lock like BatchingEngine's —
# obs instrument/registry locks nest strictly inside it, never the
# reverse (exposition must not deadlock the decode loop).
# tpu-lock-order: DecodeEngine._lock < Metric._lock  # subsystem -> instrument
# tpu-lock-order: DecodeEngine._lock < Registry._lock  # collectors run OUTSIDE the registry lock


def seq_bucket(n, min_bucket, max_len):
    """Power-of-2 sequence-length bucket: next pow2 >= n, floored at
    ``min_bucket``, clamped to ``max_len`` (the ladder's top rung)."""
    if n <= 0:
        raise ValueError(f"need length >= 1, got {n}")
    return max(min_bucket, bucket_rows(n, max_len))


class DecodeModel:
    """Adapter holding the prefill/step jax functions, their runtime
    parameters, and the shape contract (see module docstring).

    ``kv_spec`` / ``feature_spec``: tuples of ``(trailing_shape,
    dtype)`` per KV buffer / per-sequence feature array. A KV buffer's
    full shape is ``[rows, seq, *trailing]``; a feature's is
    ``[rows, *trailing]`` (constant per sequence — e.g. a user
    embedding or per-sequence temperature, any wire dtype).

    ``fingerprint``: content identity for the artifact store. Default:
    computed lazily (sha256 of the step program's serialized export at
    a canonical shape — same identity rule as jit.save: the traced
    computation + avals, never the weight values).

    ``quant``: the serving quantization mode the params/functions were
    built under (``quantization.quantize_decode_model`` sets it;
    None = f32). It rides in every program ArtifactKey, ledger event,
    and compile metric, and folds into the lazy fingerprint — a
    quantized decode ladder never collides with the f32 one in the
    artifact store.

    ``draft``: an optional companion DecodeModel for speculative
    decoding — a much cheaper model over the SAME vocab and
    feature_spec (its kv_spec may differ freely). The engine drives it
    through its own program ladder and KV pool; greedy output stays
    bitwise-equal to decoding without it, so a draft can only ever buy
    speed, never change tokens."""

    def __init__(self, params, prefill_fn, step_fn, kv_spec, vocab_size,
                 feature_spec=(), eos_token_id=None, fingerprint=None,
                 quant=None, draft=None):
        self.params = list(params)
        self.prefill_fn = prefill_fn
        self.step_fn = step_fn
        self.kv_spec = tuple((tuple(int(d) for d in tr), np.dtype(dt))
                             for tr, dt in kv_spec)
        self.feature_spec = tuple((tuple(int(d) for d in tr), np.dtype(dt))
                                  for tr, dt in feature_spec)
        self.vocab_size = int(vocab_size)
        self.eos_token_id = (None if eos_token_id is None
                             else int(eos_token_id))
        self._fingerprint = fingerprint
        self.quant = quant
        self.draft = draft


class _Programs:
    """Per-(phase, rows, seq) AOT program cache backend for the decode
    engine — the decode twin of batching.AotLayerRunner. ``compile``
    returns ``(run, source)`` via the shared
    :func:`batching.store_backed_compile` flow, so decode-step exports
    persist in the PR 10 artifact store (own keys: the phase and seq
    bucket ride in the signature) with the same single-flight /
    verify-then-quarantine / degrade-to-inline semantics."""

    def __init__(self, model, store=None, mesh=None, spec_k=0):
        import jax

        self._jax = jax
        self._model = model
        # k for the "verify" phase: one program checks k speculative
        # positions per dispatch — k unrolled step_fn iterations fused
        # in one jit, each reading the KV entries the previous ones
        # wrote. Bitwise equal per position to k sequential step
        # dispatches (measured on this jaxlib: the per-position math
        # is the step program's, only the dispatch boundary moves).
        self._spec_k = int(spec_k)
        self._store = store if store is not None \
            else _artifacts.default_store()
        self._warmup_wait_s = _env_float(
            "PADDLE_TPU_ARTIFACT_WARMUP_WAIT_S", 120.0)
        self._fp_lock = threading.Lock()
        self._weights_digest_cached = None
        # serving mesh: single runs the historical path byte-for-byte;
        # sharded commits the params to the mesh ONCE here (the
        # residents every phase program shares as runtime args) and
        # every (phase, rows, seq) rung compiles as a pjit program
        # with weight in_shardings + replicated batch/kv/outputs. The
        # descriptor rides in every ArtifactKey: the sharded decode
        # ladder is its own store identity.
        self._mesh = _sharding.resolve(mesh)
        self.mesh_desc = self._mesh.descriptor
        self._sharded_params = None
        if not self._mesh.is_single:
            self._mesh.build()  # fail fast with the device-count remedy
            self._sharded_params = self._mesh.shard_arrays(
                [jax.numpy.asarray(p) for p in model.params])

    # ----------------------------------------------------------- identity
    def _fingerprint(self):
        """Model identity for store keys and KV-snapshot headers,
        computed once: sha256 of the step program's *location-free*
        module text at the canonical (2, 8) shape (the raw serialized
        export embeds MLIR debug locations that vary with in-process
        trace order — see ``canonical_module_bytes``; a snapshot resume
        between replicas must compare program identity, not tracing
        provenance). Returns None when the model cannot export (store
        is then skipped — inline compiles, the store-less behaviour)."""
        m = self._model
        if m._fingerprint is None:
            with self._fp_lock:
                if m._fingerprint is None:
                    try:
                        blob = canonical_module_bytes(
                            self._export("step", 2, 8))
                        m._fingerprint = model_fingerprint(
                            blob, quant=getattr(m, "quant", None))
                    except Exception:  # noqa: BLE001 - store-less fallback
                        m._fingerprint = False
        return m._fingerprint or None

    def _weights_digest(self):
        """Parameter-VALUE identity for KV-snapshot headers: sha256
        over every param's dtype/shape/bytes. The program fingerprint
        deliberately excludes weight values (they are runtime args, so
        compiled artifacts are reusable across fine-tunes) — but a KV
        cache is a function of the weights, so resume must compare
        them. Computed once per model; weights are immutable in a
        serving replica."""
        if self._weights_digest_cached is None:
            with self._fp_lock:
                if self._weights_digest_cached is None:
                    h = hashlib.sha256()
                    for p in self._model.params:
                        a = np.ascontiguousarray(np.asarray(p))
                        h.update(str(a.dtype).encode())
                        h.update(str(a.shape).encode())
                        h.update(a.tobytes())
                    self._weights_digest_cached = h.hexdigest()
        return self._weights_digest_cached

    def _active_store(self):
        if self._store is None or _artifacts.disabled():
            return None
        if self._fingerprint() is None:
            return None
        return self._store

    def _quant_extra(self):
        """Ledger-event mode/mesh tags (empty for f32/single —
        historical event shapes stay byte-identical)."""
        extra = {}
        q = getattr(self._model, "quant", None)
        if q:
            extra["quant"] = q
        if self.mesh_desc != _sharding.SINGLE:
            extra["mesh"] = self.mesh_desc
        return extra

    def _artifact_key(self, phase, rows, seq):
        # the phase + seq bucket ride in the signature (the ArtifactKey
        # schema has one integer bucket): a synthetic leading entry
        # ("decode:<phase>", (seq,)) keys them unambiguously alongside
        # the kv/feature avals
        m = self._model
        if phase == "verify":
            # k is part of the program's identity: a k=3 verify ladder
            # never collides with a k=4 one in the store
            sig = (("decode:verify", (int(seq), self._spec_k)),)
        else:
            sig = ((f"decode:{phase}", (int(seq),)),)
        sig += tuple((str(dt), tr) for tr, dt in m.kv_spec)
        sig += tuple((str(dt), tr) for tr, dt in m.feature_spec)
        sig += ((f"vocab{m.vocab_size}", ()),)
        return _artifacts.ArtifactKey(self._fingerprint(), int(rows), sig,
                                      mesh=self.mesh_desc,
                                      quant=getattr(m, "quant", None))

    # ------------------------------------------------------------- shapes
    def _in_specs(self, phase, rows, seq):
        """ShapeDtypeStructs for one program's inputs (past params)."""
        jax = self._jax
        m = self._model
        i32 = np.dtype(np.int32)
        if phase == "prefill":
            specs = [jax.ShapeDtypeStruct((rows, seq), i32),   # tokens
                     jax.ShapeDtypeStruct((rows,), i32)]       # lengths
        elif phase == "verify":
            specs = [jax.ShapeDtypeStruct((rows, self._spec_k), i32),
                     jax.ShapeDtypeStruct((rows,), i32)]       # start pos
            specs += [jax.ShapeDtypeStruct((rows, seq) + tr, dt)
                      for tr, dt in m.kv_spec]
        else:
            specs = [jax.ShapeDtypeStruct((rows,), i32),       # tokens
                     jax.ShapeDtypeStruct((rows,), i32)]       # positions
            specs += [jax.ShapeDtypeStruct((rows, seq) + tr, dt)
                      for tr, dt in m.kv_spec]
        specs += [jax.ShapeDtypeStruct((rows,) + tr, dt)
                  for tr, dt in m.feature_spec]
        return specs

    def _flat_fn(self, phase):
        m = self._model
        if phase == "verify":
            return self._verify_fn()

        def flat(param_list, *args):
            fn = m.prefill_fn if phase == "prefill" else m.step_fn
            out = fn(param_list, *args)
            return tuple(out) if isinstance(out, (tuple, list)) else (out,)

        return flat

    def _verify_fn(self):
        """The batched speculative-verify program, auto-derived from
        the model's step_fn: k unrolled step-shaped iterations in ONE
        jit. Iteration i feeds ``tokens[:, i]`` at ``positions + i``
        against the KV written so far (the incoming gathered buffers
        plus the i entries the earlier iterations produced — committed
        in-program with the same ``.at[...].set`` write the host
        performs between sequential dispatches). Returns the per-
        position logits ``[rows, k, vocab]`` and the fresh KV entries
        ``[rows, k, *tr]``; the HOST commits only the accepted prefix
        of those entries (rejected-run rollback = don't write)."""
        m = self._model
        K = self._spec_k
        nkv = len(m.kv_spec)
        jnp = self._jax.numpy

        def flat(param_list, tokens, positions, *rest):
            kv = list(rest[:nkv])
            feats = rest[nkv:]
            rows = jnp.arange(tokens.shape[0])
            logits, entries = [], [[] for _ in range(nkv)]
            for i in range(K):
                out = m.step_fn(param_list, tokens[:, i], positions + i,
                                *kv, *feats)
                logits.append(out[0])
                for j in range(nkv):
                    entries[j].append(out[1 + j])
                    kv[j] = kv[j].at[rows, positions + i].set(out[1 + j])
            return ((jnp.stack(logits, axis=1),)
                    + tuple(jnp.stack(e, axis=1) for e in entries))

        return flat

    def _state(self, phase, rows, seq):
        jax = self._jax
        if self._sharded_params is not None:
            param_arrays, p_sh = self._sharded_params
            repl = self._mesh.replicated()
            param_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=s)
                           for a, s in zip(param_arrays, p_sh)]
            in_specs = [jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=repl)
                        for s in self._in_specs(phase, rows, seq)]
        else:
            param_arrays = [jax.numpy.asarray(p)
                            for p in self._model.params]
            param_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in param_arrays]
            in_specs = self._in_specs(phase, rows, seq)
        donate = ()
        if phase in ("step", "verify"):
            # donate the gathered kv scratch buffers (args: params,
            # tokens, positions, kv..., feat...): they are rebuilt
            # host-side every step, so the program may overwrite them
            nkv = len(self._model.kv_spec)
            donate = tuple(range(3, 3 + nkv))
        return param_arrays, param_specs, in_specs, donate

    def _jit(self, phase, donate, n_inputs):
        """One jit construction for both the inline compile and the
        export. Single mesh: the historical call, byte-for-byte.
        Sharded: params in their discipline layout, every batch/kv
        input and every output replicated — the host engine's shapes
        (and the wire) are mesh-invariant."""
        jax = self._jax
        if self._sharded_params is None:
            return jax.jit(self._flat_fn(phase), donate_argnums=donate)
        _, p_sh = self._sharded_params
        repl = self._mesh.replicated()
        return jax.jit(self._flat_fn(phase), donate_argnums=donate,
                       in_shardings=(list(p_sh), *([repl] * n_inputs)),
                       out_shardings=repl)

    # ------------------------------------------------------------ compile
    def _export(self, phase, rows, seq, state=None):
        from jax import export as jax_export

        _, param_specs, in_specs, donate = \
            state if state is not None else self._state(phase, rows, seq)
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jax_export.export(
                self._jit(phase, donate, len(in_specs)))(
                    param_specs, *in_specs)

    def _probe_batch(self, phase, rows, seq):
        m = self._model
        i32 = np.int32
        if phase == "prefill":
            batch = [np.zeros((rows, seq), i32), np.ones((rows,), i32)]
        elif phase == "verify":
            batch = [np.zeros((rows, self._spec_k), i32),
                     np.zeros((rows,), i32)]
            batch += [np.zeros((rows, seq) + tr, dt)
                      for tr, dt in m.kv_spec]
        else:
            batch = [np.zeros((rows,), i32), np.zeros((rows,), i32)]
            batch += [np.zeros((rows, seq) + tr, dt)
                      for tr, dt in m.kv_spec]
        batch += [np.zeros((rows,) + tr, dt) for tr, dt in m.feature_spec]
        return batch

    def _check_outputs(self, outs, phase, rows):
        m = self._model
        want = 1 + len(m.kv_spec)
        if len(outs) != want:
            raise ValueError(
                f"{phase} program returned {len(outs)} outputs, "
                f"expected logits + {len(m.kv_spec)} kv arrays")
        lg = outs[0]
        want_lg = ((rows, self._spec_k, m.vocab_size)
                   if phase == "verify" else (rows, m.vocab_size))
        if tuple(getattr(lg, "shape", ())) != want_lg:
            raise ValueError(
                f"{phase} logits shape {getattr(lg, 'shape', ())} != "
                f"{want_lg}")
        for o in outs[1:]:
            if getattr(o, "ndim", 0) == 0 or o.shape[0] != rows:
                raise ValueError(
                    f"{phase} kv output shape {getattr(o, 'shape', ())} "
                    f"does not keep the {rows}-row batch dim")

    def _make_run(self, exported, phase, rows, seq, state=None):
        """Run callable over an exported module, gated by everything
        bytes alone cannot prove (aval match, zero-batch probe) —
        mirrors AotLayerRunner._make_run."""
        param_arrays, param_specs, in_specs, _ = \
            state if state is not None else self._state(phase, rows, seq)
        # defense in depth against a copied store dir / hand-loaded
        # blob: key.mesh already makes skew a clean miss
        _sharding.check_nr_devices(
            exported, None if self._sharded_params is None else self._mesh)
        canon = self._jax.dtypes.canonicalize_dtype
        expect = [(tuple(s.shape), np.dtype(canon(s.dtype)))
                  for s in (*param_specs, *in_specs)]
        got = [(tuple(a.shape), np.dtype(a.dtype))
               for a in exported.in_avals]
        if got != expect:
            raise ValueError(
                f"aval mismatch: artifact {got} vs expected {expect}")

        def run(batch):
            out = exported.call(param_arrays, *batch)
            return [np.asarray(o) for o in out]

        outs = run(self._probe_batch(phase, rows, seq))
        self._check_outputs(outs, phase, rows)
        return run

    def _compile_inline(self, phase, rows, seq):
        param_arrays, param_specs, in_specs, donate = \
            self._state(phase, rows, seq)
        t0 = time.monotonic()
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = (self._jit(phase, donate, len(in_specs))
                        .lower(param_specs, *in_specs).compile())
        LEDGER.record(f"decode/{phase}{rows}x{seq}",
                      duration_s=time.monotonic() - t0, compiled=compiled,
                      kind="aot",
                      extra={"phase": phase, "bucket": rows, "seq": seq,
                             **self._quant_extra()})

        def run(batch):
            out = compiled(param_arrays, *batch)
            return [np.asarray(o) for o in out]

        outs = run(self._probe_batch(phase, rows, seq))
        self._check_outputs(outs, phase, rows)
        return run

    def compile(self, phase, rows, seq, warming=False):
        """-> (run, source) for one ladder rung, through the shared
        store-backed flow (store load / export+publish / inline)."""
        store = self._active_store()
        if store is None:
            return self._compile_inline(phase, rows, seq), "inline"
        key = self._artifact_key(phase, rows, seq)

        def export_and_run():
            t0 = time.monotonic()
            state = self._state(phase, rows, seq)
            exported = self._export(phase, rows, seq, state=state)
            blob = serialize_exported(exported)
            run = self._make_run(exported, phase, rows, seq, state=state)
            LEDGER.record(f"decode/{phase}{rows}x{seq}",
                          duration_s=time.monotonic() - t0, kind="aot",
                          extra={"phase": phase, "bucket": rows,
                                 "seq": seq, "via": "export",
                                 **self._quant_extra()})
            return blob, run

        def run_from_payload(payload):
            t0 = time.monotonic()
            try:
                exported = deserialize_exported(payload)
                run = self._make_run(exported, phase, rows, seq)
            except Exception as e:  # noqa: BLE001 - bad artifact degrades
                store.quarantine(key, str(e))
                return None
            LEDGER.record(f"decode/{phase}{rows}x{seq}",
                          duration_s=time.monotonic() - t0, kind="store",
                          extra={"phase": phase, "bucket": rows,
                                 "seq": seq, "artifact": key.digest(),
                                 **self._quant_extra()})
            return run

        return store_backed_compile(
            store, key,
            inline_fn=lambda: self._compile_inline(phase, rows, seq),
            export_and_run=export_and_run,
            run_from_payload=run_from_payload,
            warming=warming, warmup_wait_s=self._warmup_wait_s)

    def store_stats(self):
        store = self._active_store()
        return store.stats() if store is not None else None


class _KVSlots:
    """Paged per-sequence KV storage over a REFCOUNTED page pool.

    Each slot's KV is a list of fixed-size pages (``page_len`` =
    ``min_bucket`` tokens) drawn from a shared pool, so host memory
    tracks actual sequence lengths AND hot prefixes can live once:
    the prefix cache installs its pages into a fresh slot by reference
    (:meth:`install_shared`). Sharing is copy-on-write — any write
    into a page with refcount > 1 clones it first, so two sequences
    sharing a prefix then diverging can never see each other's pages.
    Release DECREMENTS, never frees: a page the cache or another
    sequence still holds survives a slot's release (the shared-page
    half of the exactly-once release discipline — a watchdog restart's
    sweep decrefs shared pages, it cannot double-free them). ``gather``
    assembles the fixed-shape step batch, zero-filling rows beyond
    each sequence's length so stale contents never reach a program."""

    def __init__(self, max_slots, max_seq_len, kv_spec, min_bucket=8):
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.kv_spec = kv_spec
        self.min_bucket = int(min_bucket)
        self.page_len = self.min_bucket
        self._free = list(range(self.max_slots - 1, -1, -1))
        self._slot_pages = [[] for _ in range(self.max_slots)]
        self._pages = {}       # page id -> [np (page_len, *tr) per kv]
        self._rc = {}          # page id -> refcount
        self._spare = []       # recycled page array lists (no realloc
        self._next_pid = 0     # churn at steady state)

    def free_count(self):
        return len(self._free)

    def page_bytes(self):
        """Host bytes of ONE page across every kv buffer (what the
        prefix cache budgets with)."""
        return sum(self.page_len * int(np.prod(tr)) * dt.itemsize
                   for tr, dt in self.kv_spec)

    # ------------------------------------------------------------ pages
    # tpu-resource: acquires=kv_page
    def _page_alloc(self):
        """One fresh page (refcount 1) — recycled arrays when possible.
        Recycled contents are NOT zeroed: every read path copies only
        positions a sequence actually wrote (gather/snapshot bound by
        length), so stale bytes can never reach a program."""
        pid = self._next_pid
        self._next_pid += 1
        if self._spare:
            self._pages[pid] = self._spare.pop()
        else:
            self._pages[pid] = [np.zeros((self.page_len,) + tr, dt)
                                for tr, dt in self.kv_spec]
        self._rc[pid] = 1
        return pid

    # tpu-resource: releases=kv_page
    def _page_reclaim(self, pid):
        """Refcount hit zero: return the arrays to the spare pool."""
        self._spare.append(self._pages.pop(pid))
        del self._rc[pid]

    def retain_page(self, pid):
        self._rc[pid] += 1

    def drop_page(self, pid):
        rc = self._rc[pid] - 1
        if rc:
            self._rc[pid] = rc
        else:
            self._page_reclaim(pid)

    def shared_pages(self):
        """Pages held by more than one owner (slots + cache entries)."""
        return sum(1 for rc in self._rc.values() if rc > 1)

    def live_pages(self):
        return len(self._pages)

    def _ensure(self, slot, n):
        """Grow the slot's page list to cover n positions."""
        if n > self.max_seq_len:
            raise ValueError(f"sequence length {n} exceeds max_seq_len "
                             f"{self.max_seq_len}")
        pages = self._slot_pages[slot]
        need = -(-n // self.page_len)
        while len(pages) < need:
            pages.append(self._page_alloc())

    def _writable(self, slot, page_idx):
        """The slot's page arrays at ``page_idx``, cloned first if the
        page is shared — the copy-on-write barrier every write path
        goes through."""
        pages = self._slot_pages[slot]
        pid = pages[page_idx]
        if self._rc[pid] > 1:
            new = self._page_alloc()
            for dst, src in zip(self._pages[new], self._pages[pid]):
                dst[:] = src
            self.drop_page(pid)
            pages[page_idx] = new
            pid = new
        return self._pages[pid]

    # ------------------------------------------------------------ slots
    # tpu-resource: acquires=kv_slot
    def alloc(self):
        return self._free.pop() if self._free else None

    # tpu-resource: releases=kv_slot
    def release(self, slot):
        """Free the slot: DECREMENT every page (reclaimed only when no
        other sequence or cache entry holds it)."""
        pages = self._slot_pages[slot]
        self._slot_pages[slot] = []
        for pid in pages:
            self.drop_page(pid)
        self._free.append(slot)

    def install_shared(self, slot, pages):
        """Seed a freshly-allocated slot with cached prefix pages by
        reference (each page's refcount grows; the first divergent
        write clones via :meth:`_writable`)."""
        for pid in pages:
            self.retain_page(pid)
        self._slot_pages[slot] = list(pages)

    def export_pages(self, slot, n_pages):
        """The slot's first ``n_pages`` page ids (for the prefix cache
        to retain — the pages themselves stay put)."""
        return list(self._slot_pages[slot][:n_pages])

    def pages_from_arrays(self, kv_arrays, length):
        """Materialize contiguous KV arrays (a store-loaded prefix)
        into fresh pool pages; returns the page ids, refcount 1 each,
        owned by the caller."""
        pages = []
        pl = self.page_len
        for pi in range(-(-length // pl)):
            pid = self._page_alloc()
            lo = pi * pl
            m = min(pl, length - lo)
            for a, src in zip(self._pages[pid], kv_arrays):
                a[:m] = src[lo:lo + m]
            pages.append(pid)
        return pages

    # ----------------------------------------------------------- writes
    def write_prefill(self, slot, kv_arrays, length):
        """Install a fresh sequence's prompt kv (row slices of the
        prefill program's [rows, prompt_bucket, ...] outputs)."""
        length = max(length, 1)
        self._ensure(slot, length)
        pl = self.page_len
        for pi in range(-(-length // pl)):
            lo = pi * pl
            m = min(pl, length - lo)
            arrays = self._writable(slot, pi)
            for a, src in zip(arrays, kv_arrays):
                a[:m] = src[lo:lo + m]

    def write_entry(self, slot, pos, entries):
        """Append one decode step's kv entries at position ``pos``."""
        self._ensure(slot, pos + 1)
        arrays = self._writable(slot, pos // self.page_len)
        o = pos % self.page_len
        for a, e in zip(arrays, entries):
            a[o] = e

    # ------------------------------------------------------------ reads
    def snapshot(self, slot, length):
        """Copy slot ``slot``'s first ``length`` KV entries out (one
        contiguous array per kv_spec entry) — the paged-KV payload of
        a resumable stream snapshot. Pure read: the slot stays live."""
        out = [np.zeros((length,) + tr, dt) for tr, dt in self.kv_spec]
        pl = self.page_len
        for pi, pid in enumerate(self._slot_pages[slot]):
            lo = pi * pl
            if lo >= length:
                break
            m = min(pl, length - lo)
            for o, a in zip(out, self._pages[pid]):
                o[lo:lo + m] = a[:m]
        return out

    # tpu-resource: acquires=kv_slot
    def restore(self, kv_arrays, length):
        """Allocate a slot and install a snapshot's KV prefix into it
        (the write_prefill of a resumed sequence). Returns the slot,
        or None when no slot is free."""
        slot = self.alloc()
        if slot is None:
            return None
        self.write_prefill(slot, kv_arrays, length)
        return slot

    def gather(self, slots, lengths, rows_bucket, seq_b):
        """[rows_bucket, seq_b, *tr] per kv buffer: row i carries slot
        ``slots[i]``'s first ``lengths[i]`` entries, zeros elsewhere
        (zero pad rows AND zero beyond-length tails — finite by
        construction, masked out by the model)."""
        out = [np.zeros((rows_bucket, seq_b) + tr, dt)
               for tr, dt in self.kv_spec]
        pl = self.page_len
        for i, (slot, n) in enumerate(zip(slots, lengths)):
            n = min(n, seq_b)
            if n <= 0:
                continue
            for pi, pid in enumerate(self._slot_pages[slot]):
                lo = pi * pl
                if lo >= n:
                    break
                m = min(pl, n - lo)
                for o, a in zip(out, self._pages[pid]):
                    o[i, lo:lo + m] = a[:m]
        return out


_RETIRE_REASONS = ("eos", "max_tokens", "max_seq_len", "deadline",
                   "error", "cancelled")


class DecodeRequest:
    """One streaming decode request: thread-safe token sink the engine
    pushes into and a consumer (the server handler, or a direct
    :meth:`result` caller) drains.

    Consumer API:
      - ``next_tokens(timeout)`` -> ``(tokens, done)``: blocks for new
        tokens; delivers whatever accumulated since the last call.
        Once the terminal error (if any) is the only thing left, it
        raises it — delivered tokens always come out first, so a
        streaming client sees the real prefix then the retryable
        error, never a truncated-but-ok sequence.
      - ``result(timeout)`` -> full token array (raises on error).
      - ``cancel()``: abandon; the engine purges the KV slot at the
        next iteration boundary and stops spending compute.
    """

    __slots__ = ("prompt", "features", "max_new_tokens", "eos_token_id",
                 "token_budget_s", "trace_id", "token_dtype", "t_enqueue",
                 "snapshot_every", "speculative", "_cond", "_tokens",
                 "_taken", "_done", "_error", "_snap", "_snap_fresh",
                 "finish_reason", "cancelled")

    def __init__(self, prompt, features, max_new_tokens, eos_token_id,
                 token_budget_s, trace_id, token_dtype):
        self.prompt = prompt
        self.features = features
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.token_budget_s = token_budget_s
        self.trace_id = trace_id
        self.token_dtype = token_dtype
        self.t_enqueue = time.monotonic()
        self.snapshot_every = 0
        self.speculative = False
        self._cond = threading.Condition()
        self._tokens = []
        self._taken = 0
        self._done = False
        self._error = None
        self._snap = None
        self._snap_fresh = False
        self.finish_reason = None
        self.cancelled = False

    # ------------------------------------------------------- engine side
    def _push(self, token):
        with self._cond:
            if self._done:
                return  # a superseded scheduler's late result: discard
            self._tokens.append(token)
            self._cond.notify_all()

    def _finish(self, reason):
        with self._cond:
            if not self._done:
                self._done = True
                self.finish_reason = reason
                self._cond.notify_all()

    def _fail(self, error):
        with self._cond:
            if not self._done:
                self._done = True
                self._error = error
                self.finish_reason = "error"
                self._cond.notify_all()

    def _push_snapshot(self, blob, n_generated):
        """Install the latest kv-snapshot block for this sequence
        (engine side, at the request's cadence). Only the newest
        snapshot is kept — a resume always restarts from the most
        recent position. ``n_generated`` rides along so the server can
        hold a snapshot frame until every token it covers is on the
        wire (the router's dedup arithmetic needs delivered >= G)."""
        with self._cond:
            if self._done:
                return
            self._snap = (blob, int(n_generated))
            self._snap_fresh = True
            self._cond.notify_all()

    # ----------------------------------------------------- consumer side
    def cancel(self):
        """Abandon the request: tokens stop, the engine frees the KV
        slot at its next iteration boundary (or drops the request from
        the queue if it never joined)."""
        with self._cond:
            self.cancelled = True
            if not self._done:
                self._done = True
                self.finish_reason = "cancelled"
                self._cond.notify_all()

    def next_tokens(self, timeout=None):
        """-> (new_tokens_list, done). Raises the terminal error once
        every delivered token has been consumed; raises TimeoutError
        if nothing happens within ``timeout``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                if self._taken < len(self._tokens):
                    out = self._tokens[self._taken:]
                    self._taken = len(self._tokens)
                    return out, self._done and self._error is None
                if self._done:
                    if self._error is not None:
                        raise self._error
                    return [], True
                left = (None if deadline is None
                        else deadline - time.monotonic())
                if left is not None and left <= 0:
                    raise TimeoutError(
                        "no decode progress within timeout")
                self._cond.wait(left)  # tpu-lint: disable=TPU303  # bounded by caller timeout; None is the documented no-timeout mode

    def result(self, timeout=None):
        """Block until the sequence finishes; -> 1-D token array in the
        request's token dtype."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while not self._done:
                left = (None if deadline is None
                        else deadline - time.monotonic())
                if left is not None and left <= 0:
                    raise TimeoutError("decode did not finish in time")
                self._cond.wait(left)  # tpu-lint: disable=TPU303  # bounded by caller timeout; None is the documented no-timeout mode
            if self._error is not None:
                raise self._error
            return np.asarray(self._tokens, dtype=self.token_dtype)

    def tokens_so_far(self):
        with self._cond:
            return list(self._tokens)

    def take_snapshot(self):
        """-> ``(block, n_generated)`` for the newest kv-snapshot not
        yet taken, or None. Take-once semantics: the server handler
        calls this after each token drain and forwards the block as a
        snapshot frame once ``n_generated`` tokens have been sent."""
        with self._cond:
            if not self._snap_fresh:
                return None
            self._snap_fresh = False
            return self._snap

    def latest_snapshot(self):
        """-> the newest kv-snapshot block (without consuming it), or
        None if the sequence never reached its cadence."""
        with self._cond:
            return None if self._snap is None else self._snap[0]


class _Seq:
    """One RUNNING sequence: its request, KV slot, and positions.
    ``draft_slot``/``draft_pos`` track the speculative companion's KV
    (allocated lazily on the first speculative iteration; rollback
    after a rejected run is just moving ``draft_pos`` back — the stale
    entries beyond it are never gathered)."""

    __slots__ = ("req", "slot", "pos", "last_token", "n_generated",
                 "t_last", "draft_slot", "draft_pos")

    def __init__(self, req, slot, pos, last_token, now):
        self.req = req
        self.slot = slot
        self.pos = pos  # kv entries cached so far
        self.last_token = last_token
        self.n_generated = 1  # prefill emitted the first token
        self.t_last = now
        self.draft_slot = None
        self.draft_pos = 0


class DecodeEngine:
    """Continuous-batching decode front end (see module docstring).

    ``submit`` enqueues a sequence and returns its
    :class:`DecodeRequest` (stream with ``next_tokens`` or block with
    ``result``); ``generate`` is the blocking convenience. Any number
    of threads may submit concurrently; one scheduler thread runs the
    iteration loop."""

    def __init__(self, model, max_slots=None, max_seq_len=None,
                 max_queue=None, min_seq_bucket=None, max_prompt_len=None,
                 default_max_new_tokens=None, name="decode", store=None,
                 breaker_threshold=None, breaker_cooldown=None,
                 watchdog_interval=None, wedge_timeout=None, quant=None,
                 mesh=None, phase=None, spec_k=None, prefix=None,
                 prefix_dir=None, prefix_max_bytes=None):
        # quant: serve this model under a quantization mode ("w8" |
        # "bf16w"; env default PADDLE_TPU_SERVING_QUANT — the one-knob
        # fleet flip). An unquantized model is wrapped via
        # quantization.quantize_decode_model; a model ALREADY carrying
        # a mode must match the request (a replica told to serve w8
        # must never silently serve something else).
        # mesh: serving mesh descriptor ("tp2" | "fsdp2xtp2" | ...; env
        # default PADDLE_TPU_SERVING_MESH) — params shard once at
        # construction and the whole (phase, rows, seq) program ladder
        # becomes per-(bucket, mesh) pjit programs with their own
        # artifact-store identities (README "Sharded serving").
        if quant is None:
            quant = os.environ.get("PADDLE_TPU_SERVING_QUANT") or None
        # capture the draft companion BEFORE any quant wrapping:
        # quantize_decode_model builds a NEW DecodeModel and would drop
        # the attribute. The draft follows the target's serving mode
        # unless it already carries its own (a pre-quantized draft —
        # the bf16w/w8 draft of the ISSUE contract — wins).
        draft_model = getattr(model, "draft", None)
        model_quant = getattr(model, "quant", None)
        if quant is not None and quant != (model_quant or "f32"):
            if model_quant is not None:
                raise ValueError(
                    f"model is quantized as {model_quant!r} but the "
                    f"engine was asked to serve {quant!r}")
            if quant != "f32":
                from ..quantization.serving import quantize_decode_model

                model = quantize_decode_model(model, quant)
                if (draft_model is not None
                        and getattr(draft_model, "quant", None) is None):
                    draft_model = quantize_decode_model(draft_model, quant)
        if draft_model is not None:
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.vocab_size} != target "
                    f"vocab {model.vocab_size}; speculative verify "
                    "compares argmaxes over the SAME vocab")
            if draft_model.feature_spec != model.feature_spec:
                raise ValueError(
                    "draft feature_spec differs from the target's; "
                    "both models consume the request's feature arrays")
        self._model = model
        self.max_slots = int(
            max_slots if max_slots is not None
            else _env_int("PADDLE_TPU_DECODE_MAX_SLOTS", 8))
        self.max_seq_len = int(
            max_seq_len if max_seq_len is not None
            else _env_int("PADDLE_TPU_DECODE_MAX_SEQ_LEN", 256))
        self.max_queue = int(
            max_queue if max_queue is not None
            else _env_int("PADDLE_TPU_DECODE_MAX_QUEUE", 64))
        self.min_seq_bucket = int(
            min_seq_bucket if min_seq_bucket is not None
            else _env_int("PADDLE_TPU_DECODE_MIN_SEQ_BUCKET", 8))
        self.max_prompt_len = int(
            max_prompt_len if max_prompt_len is not None
            else _env_int("PADDLE_TPU_DECODE_MAX_PROMPT_LEN",
                          self.max_seq_len))
        self.default_max_new_tokens = int(
            default_max_new_tokens if default_max_new_tokens is not None
            else _env_int("PADDLE_TPU_DECODE_MAX_NEW_TOKENS", 64))
        self.default_snapshot_every = max(0, _env_int(
            "PADDLE_TPU_DECODE_SNAPSHOT_EVERY", 0))
        # phase: this engine's pool in a disaggregated fleet ("prefill"
        # | "decode" | "both"; env default PADDLE_TPU_DECODE_PHASE).
        # Phase is a PLACEMENT attribute — it shapes the warmup ladder
        # and is reported in health/stats for the router, but the
        # engine still serves every request kind so a fleet whose other
        # pool collapsed can degrade to colocated serving here.
        if phase is None:
            phase = os.environ.get("PADDLE_TPU_DECODE_PHASE") or "both"
        if phase not in _wire_spec.REPLICA_PHASES:
            raise ValueError(
                f"unknown engine phase {phase!r} (expected one of "
                f"{_wire_spec.REPLICA_PHASES})")
        self.phase = phase
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        # row buckets are floored at 2 even for a max_slots=1 engine
        # (one pad row): batch-1 float matmuls hit XLA's gemv regime,
        # whose rounding differs from the gemm every batch >= 2 uses —
        # keeping EVERY dispatch in the gemm regime is what makes a
        # solo decode bitwise comparable to the same sequence inside a
        # continuous batch (the PR 4 lesson, applied per decode step)
        self._rows_cap = max(2, self.max_slots)
        if self.max_prompt_len > self.max_seq_len:
            raise ValueError("max_prompt_len cannot exceed max_seq_len")
        self.breaker_threshold = int(
            breaker_threshold if breaker_threshold is not None
            else _env_int("PADDLE_TPU_SERVING_BREAKER_THRESHOLD", 3))
        self.breaker_cooldown = float(
            breaker_cooldown if breaker_cooldown is not None
            else _env_float("PADDLE_TPU_SERVING_BREAKER_COOLDOWN", 5.0))
        self.watchdog_interval = float(
            watchdog_interval if watchdog_interval is not None
            else _env_float("PADDLE_TPU_SERVING_WATCHDOG_INTERVAL", 0.5))
        self.wedge_timeout = float(
            wedge_timeout if wedge_timeout is not None
            else _env_float("PADDLE_TPU_SERVING_WEDGE_TIMEOUT", 30.0))
        self.name = name
        # speculative decode: active only with a draft companion AND
        # k >= 2 (k-1 proposed tokens + the always-correct first
        # position per verify dispatch)
        self._spec_k = int(spec_k if spec_k is not None
                           else _env_int("PADDLE_TPU_SPEC_K", 0))
        if draft_model is None or self._spec_k < 2:
            self._spec_k = 0
        self.spec_enabled = self._spec_k >= 2
        self._programs = _Programs(model, store=store, mesh=mesh,
                                   spec_k=self._spec_k)
        self.mesh_desc = self._programs.mesh_desc
        self._draft_programs = None
        self._draft_slots = None
        if self.spec_enabled:
            self._draft_programs = _Programs(draft_model, store=store,
                                             mesh=mesh)
            self._draft_slots = _KVSlots(
                self.max_slots, self.max_seq_len, draft_model.kv_spec,
                min_bucket=self.min_seq_bucket)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending = []  # FIFO of DecodeRequest
        self._pending_resume = []  # FIFO of (req, kv_arrays, state dict)
        self._n_snapshots = 0       # blocks assembled (stats view)
        self._n_resumes_ok = 0      # resume joins admitted
        self._n_resumes_refused = 0  # identity-skew refusals
        self._n_spec_iters = 0      # speculative bursts applied
        self._n_spec_accepted = 0   # draft tokens accepted by verify
        self._active = []   # list of _Seq (scheduler-owned mutation)
        self._inflight_join = []  # joiners popped but not yet prefilled:
        # a scheduler that dies holding them must not strand them — the
        # watchdog restart fails exactly these (retryable), like the
        # one-shot engine's _inflight group
        self._slots = _KVSlots(self.max_slots, self.max_seq_len,
                               model.kv_spec,
                               min_bucket=self.min_seq_bucket)
        # content-addressed prefix cache over the slot page pool (ON
        # by default — in-memory sharing alone; the persistent tier
        # needs PADDLE_TPU_PREFIX_DIR)
        if prefix is None:
            prefix = os.environ.get("PADDLE_TPU_PREFIX_DISABLE") != "1"
        self._prefix = None
        if prefix:
            self._prefix = PrefixCache(
                self._slots, identity_fn=self._prefix_identity,
                max_bytes=prefix_max_bytes, store_dir=prefix_dir,
                name=f"{name}-prefix")
        self._cache = {}      # (phase, rows, seq) -> run
        self._compiling = {}  # (phase, rows, seq) -> Event
        self._breakers = {}   # (phase, rows, seq) -> _Breaker
        self._compile_counts = {}  # (phase, rows, seq) -> {source: n}
        self._declared = []
        self._closed = False
        self._closed_ev = threading.Event()
        self._sched_gen = 0
        self._heartbeat = time.monotonic()
        self._init_metrics()
        self._watchdog = None
        self._scheduler = threading.Thread(
            target=self._run_scheduler, args=(0,),
            name=f"{name}-scheduler", daemon=True)
        self._scheduler.start()
        if self.watchdog_interval > 0:
            self._watchdog = threading.Thread(
                target=self._run_watchdog, name=f"{name}-watchdog",
                daemon=True)
            self._watchdog.start()

    # -------------------------------------------------------- telemetry
    def _init_metrics(self):
        cl = {"engine": self.name}
        M = obs_metrics
        lat = M.log_buckets(0.0001, 4.0, 10)
        self._m_requests = M.Counter(
            "paddle_decode_requests_total",
            "Decode requests admitted", const_labels=cl)
        self._m_tokens = M.Counter(
            "paddle_decode_tokens_total",
            "Tokens generated", const_labels=cl)
        self._m_shed = M.Counter(
            "paddle_decode_shed_total",
            "Requests shed (reason: queue_full | quarantine | "
            "no_free_slot — the last is the kv_put seed preflight)",
            labelnames=("reason",), const_labels=cl)
        self._m_retired = M.Counter(
            "paddle_decode_retired_total",
            "Sequences retired, by reason",
            labelnames=("reason",), const_labels=cl)
        self._m_deadline = M.Counter(
            "paddle_decode_deadline_total",
            "Per-token deadline outcomes (stage: expired = purged "
            "before joining, zero compute; late = blew a per-token "
            "budget mid-sequence)",
            labelnames=("stage",), const_labels=cl)
        self._m_restarts = M.Counter(
            "paddle_decode_scheduler_restarts_total",
            "Watchdog scheduler restarts", const_labels=cl)
        self._m_compiles = M.Counter(
            "paddle_decode_compiles_total",
            "Program materializations (source: inline = real XLA "
            "compile, store = artifact-store load; quant: the serving "
            "quantization mode; mesh: the serving mesh descriptor)",
            labelnames=("phase", "source"),
            const_labels={
                **cl,
                "quant": getattr(self._model, "quant", None) or "f32",
                "mesh": self.mesh_desc})
        self._m_steps = M.Counter(
            "paddle_decode_steps_total",
            "Model program dispatches, by phase",
            labelnames=("phase",), const_labels=cl)
        self._m_ttft = M.Histogram(
            "paddle_decode_ttft_seconds",
            "Time from enqueue to a sequence's FIRST token",
            const_labels=cl, buckets=lat)
        self._m_intertoken = M.Histogram(
            "paddle_decode_intertoken_seconds",
            "Gap between consecutive tokens of one sequence",
            const_labels=cl, buckets=lat)
        self._m_step_exec = M.Histogram(
            "paddle_decode_step_seconds",
            "Program execute duration, by phase",
            labelnames=("phase",), const_labels=cl, buckets=lat)
        self._m_occupancy = M.Histogram(
            "paddle_decode_batch_occupancy",
            "Active sequences / slot bucket per decode step",
            const_labels=cl,
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
        self._m_active = M.Gauge(
            "paddle_decode_active_slots",
            "Sequences currently holding a KV slot", const_labels=cl)
        self._m_queue = M.Gauge(
            "paddle_decode_queue_depth",
            "Requests waiting for a slot", const_labels=cl)
        self._m_prefix_hits = M.Counter(
            "paddle_prefix_hits_total",
            "Prefix-cache hits (a joiner installed cached KV pages and "
            "skipped prefill over them)", const_labels=cl)
        self._m_prefix_misses = M.Counter(
            "paddle_prefix_misses_total",
            "Prefix-cache misses (hashed prompts with no cached "
            "boundary)", const_labels=cl)
        self._m_prefix_evictions = M.Counter(
            "paddle_prefix_evictions_total",
            "Prefix-cache entries evicted under the byte budget",
            const_labels=cl)
        self._m_shared_pages = M.Gauge(
            "paddle_decode_shared_pages",
            "KV pages referenced by more than one owner (slots + "
            "prefix-cache entries)", const_labels=cl)
        self._m_live_pages = M.Gauge(
            "paddle_decode_live_pages",
            "KV pages currently allocated (target + draft pools)",
            const_labels=cl)
        self._m_spec_accept = M.Histogram(
            "paddle_spec_accept_ratio",
            "Accepted draft tokens / proposed (k-1) per speculative "
            "verify",
            const_labels={
                **cl,
                "quant": getattr(self._model, "quant", None) or "f32",
                "mesh": self.mesh_desc},
            buckets=(0.0, 0.25, 0.5, 0.75, 1.0))
        self._instruments = [
            self._m_requests, self._m_tokens, self._m_shed,
            self._m_retired, self._m_deadline, self._m_restarts,
            self._m_compiles, self._m_steps, self._m_ttft,
            self._m_intertoken, self._m_step_exec, self._m_occupancy,
            self._m_active, self._m_queue, self._m_prefix_hits,
            self._m_prefix_misses, self._m_prefix_evictions,
            self._m_shared_pages, self._m_live_pages,
            self._m_spec_accept]
        ref = weakref.ref(self)

        def _collector():
            eng = ref()
            return eng._collect_families() if eng is not None else None

        self._obs_collector = _collector
        obs_metrics.REGISTRY.register_collector(_collector)

    def _collect_families(self):
        with self._lock:
            self._m_queue.set(len(self._pending))
            self._m_active.set(len(self._active))
            shared = self._slots.shared_pages()
            live = self._slots.live_pages()
            if self._draft_slots is not None:
                shared += self._draft_slots.shared_pages()
                live += self._draft_slots.live_pages()
            self._m_shared_pages.set(shared)
            self._m_live_pages.set(live)
            return [m.collect() for m in self._instruments]

    def _prefix_identity(self):
        """Replica identity for persistent prefix-cache keys/headers —
        the same fields a kv-snapshot resume compares (PR 17's skew-
        refusal discipline). Called lazily, OUTSIDE the engine lock
        (the fingerprint has its own lock)."""
        fp = self._programs._fingerprint()
        if fp is None:
            return None
        return {"fingerprint": fp,
                "weights": self._programs._weights_digest(),
                "quant": getattr(self._model, "quant", None) or "f32",
                "mesh": self.mesh_desc}

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens=None, features=(),
               token_budget_s=None, trace_id=None, eos_token_id=None,
               snapshot_every=None, speculative=False):
        """Enqueue one sequence; -> :class:`DecodeRequest`.

        ``prompt``: 1-D (or [1, P]) int32/int64 token ids (the output
        token dtype echoes it). ``features``: per-sequence arrays
        matching the model's ``feature_spec`` (any wire dtype).
        ``token_budget_s``: per-token SLO — bounds time-to-first-token
        and every inter-token gap; a blown budget fails the request
        retryable and frees its slot. ``snapshot_every``: emit a
        resumable kv-snapshot block every N generated tokens
        (``DecodeRequest.take_snapshot``; 0 = never, None = the
        engine's env-configured default). ``speculative``: opt in to
        draft-and-verify decoding (wire 0x5C bit 61) — tokens stay
        bitwise-equal to non-speculative greedy; a no-op on an engine
        without a draft model."""
        chaos.hit("serving.decode.admit")
        prompt = np.asarray(prompt)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(
                f"prompt must be a non-empty 1-D token array "
                f"(got shape {tuple(prompt.shape)})")
        if prompt.dtype in _TOKEN_DTYPES:
            # the spec's token-dtype set (wire codes 1/2): streamed
            # chunks echo exactly this dtype back on the wire
            token_dtype = prompt.dtype.type
        else:
            raise ValueError(
                f"prompt dtype {prompt.dtype} is not a token dtype "
                "(int32 / int64)")
        if prompt.size > self.max_prompt_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds max_prompt_len="
                f"{self.max_prompt_len}")
        prompt_i32 = np.ascontiguousarray(prompt.astype(np.int32))
        spec = self._model.feature_spec
        features = [np.ascontiguousarray(np.asarray(f)) for f in features]
        if len(features) != len(spec):
            raise ValueError(
                f"model expects {len(spec)} feature array(s), "
                f"got {len(features)}")
        for f, (tr, dt) in zip(features, spec):
            if tuple(f.shape) != tr or f.dtype != dt:
                raise ValueError(
                    f"feature shape/dtype {f.shape}/{f.dtype} does not "
                    f"match spec {tr}/{dt}")
        if max_new_tokens is None:
            max_new_tokens = self.default_max_new_tokens
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eos = (self._model.eos_token_id if eos_token_id is None
               else eos_token_id)
        if trace_id is None:
            trace_id = obs_tracing.current_trace_id()
        req = DecodeRequest(prompt_i32, features, max_new_tokens, eos,
                            token_budget_s, trace_id, token_dtype)
        req.snapshot_every = max(0, int(
            self.default_snapshot_every if snapshot_every is None
            else snapshot_every))
        req.speculative = bool(speculative)
        with self._cond:
            if self._closed:
                raise EngineClosed(f"{self.name} is closed")
            if len(self._pending) >= self.max_queue:
                self._m_shed.inc(reason="queue_full")
                raise EngineOverloaded(
                    f"{self.name} decode queue full "
                    f"({len(self._pending)} waiting, cap {self.max_queue})"
                    "; request shed")
            self._pending.append(req)
            self._m_requests.inc()
            self._cond.notify_all()
        return req

    def generate(self, prompt, timeout=None, **kw):
        """Blocking convenience: submit + result."""
        return self.submit(prompt, **kw).result(timeout)

    def cancel(self, req):
        """Abandon a request: if still queued it is dropped here; if
        running, the scheduler purges its KV slot at the next
        iteration boundary (before any further compute)."""
        req.cancel()
        with self._cond:
            try:
                self._pending.remove(req)
            except ValueError:
                pass  # already joined (or finished); scheduler purges
            self._cond.notify_all()

    # ---------------------------------------------------- stream resume
    def _build_snapshot(self, req, kv_copies, pos, last_token,
                        n_generated):
        """Encode one kv-snapshot block for a running sequence (runs
        OUTSIDE the engine lock: the lazy fingerprint has its own
        lock and must not nest inside ours)."""
        m = self._model
        header = {
            "fingerprint": self._programs._fingerprint(),
            "weights": self._programs._weights_digest(),
            "quant": getattr(m, "quant", None) or "f32",
            "mesh": self.mesh_desc,
            "pos": int(pos),
            "last_token": int(last_token),
            "n_generated": int(n_generated),
            "prompt_len": int(req.prompt.size),
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": req.eos_token_id,
            "n_kv": len(m.kv_spec),
        }
        tail = np.asarray(req.tokens_so_far()[:n_generated],
                          dtype=req.token_dtype)
        arrays = [req.prompt, tail] + list(kv_copies) + list(req.features)
        return _wire_spec.encode_kv_snapshot(header, arrays)

    def _refuse(self, why):
        with self._lock:
            self._n_resumes_refused += 1
        raise SnapshotRefused(f"{self.name}: snapshot refused ({why}); "
                              "resume on a replica matching the "
                              "snapshot's identity")

    def check_snapshot(self, payload):
        """Parse + validate one kv-snapshot block against THIS
        replica's identity and limits; -> (header, arrays).

        Raises ValueError for a malformed or internally inconsistent
        block (permanent — wire status 1) and :class:`SnapshotRefused`
        for an identity or capacity skew (retryable — wire status 2:
        the snapshot is fine, this replica is the wrong home for it).
        The cmd kv_put preflight and :meth:`resume` share this check:
        validation cannot drift from what a resume actually demands."""
        header, arrays, _ = _wire_spec.decode_kv_snapshot_off(payload)
        m = self._model
        pos = int(header["pos"])
        n_gen = int(header["n_generated"])
        plen = int(header["prompt_len"])
        prompt, tail = arrays[0], arrays[1]
        n_kv = int(header.get("n_kv", len(m.kv_spec)))
        if n_gen < 1:
            raise ValueError("kv snapshot carries no generated tokens")
        if prompt.ndim != 1 or prompt.size != plen:
            raise ValueError(
                f"kv snapshot prompt shape {tuple(prompt.shape)} does "
                f"not match its declared prompt_len {plen}")
        if tail.ndim != 1 or tail.size != n_gen:
            raise ValueError(
                f"kv snapshot token tail of {tail.size} does not match "
                f"its declared n_generated {n_gen}")
        if tail.dtype not in _TOKEN_DTYPES:
            raise ValueError(
                f"kv snapshot token tail dtype {tail.dtype} is not a "
                "token dtype (int32 / int64)")
        if pos != plen + n_gen - 1:
            raise ValueError(
                f"kv snapshot position invariant broken: pos {pos} != "
                f"prompt_len {plen} + n_generated {n_gen} - 1")
        if int(header["last_token"]) != int(tail[-1]):
            raise ValueError(
                "kv snapshot last_token does not match its token tail")
        fp = self._programs._fingerprint()
        if header["fingerprint"] != fp:
            self._refuse(f"model fingerprint "
                         f"{header['fingerprint']!r} != {fp!r}")
        wd = self._programs._weights_digest()
        if header["weights"] != wd:
            self._refuse("weights digest mismatch: same architecture, "
                         "different parameter values — a foreign KV "
                         "cache would decode garbage")
        quant = getattr(m, "quant", None) or "f32"
        if header["quant"] != quant:
            self._refuse(f"quant mode {header['quant']!r} != {quant!r}")
        if header["mesh"] != self.mesh_desc:
            self._refuse(f"mesh {header['mesh']!r} != "
                         f"{self.mesh_desc!r}")
        if n_kv != len(m.kv_spec):
            self._refuse(f"{n_kv} kv buffers != this model's "
                         f"{len(m.kv_spec)}")
        if len(arrays) != 2 + n_kv + len(m.feature_spec):
            self._refuse(
                f"{len(arrays)} arrays != prompt + tail + {n_kv} kv + "
                f"{len(m.feature_spec)} features")
        if pos > self.max_seq_len:
            self._refuse(f"position {pos} exceeds this engine's "
                         f"max_seq_len {self.max_seq_len}")
        for a, (tr, dt) in zip(arrays[2:2 + n_kv], m.kv_spec):
            if (a.ndim != 1 + len(tr) or tuple(a.shape[1:]) != tr
                    or a.dtype != dt or a.shape[0] < pos):
                self._refuse(
                    f"kv buffer {tuple(a.shape)}/{a.dtype} does not "
                    f"match kv_spec {tr}/{dt} at position {pos}")
        for f, (tr, dt) in zip(arrays[2 + n_kv:], m.feature_spec):
            if tuple(f.shape) != tr or f.dtype != dt:
                self._refuse(
                    f"feature {tuple(f.shape)}/{f.dtype} does not "
                    f"match feature_spec {tr}/{dt}")
        return header, arrays

    def seed_check(self, payload):
        """cmd kv_put preflight for a prefill->decode handoff: validate
        the block against THIS replica (sharing :meth:`check_snapshot`
        with the resume path) AND confirm the engine can seed a FRESH
        slot for it now; -> (header, arrays).

        A handoff places the sequence before the stream commits, so a
        replica with no free KV slot and a backed-up queue refuses
        retryable here — the router tries the next decode replica —
        instead of absorbing a sequence it cannot start. This is the
        capacity half kv_put adds over a plain resume of a broken
        stream (which already holds its position and must queue)."""
        header, arrays = self.check_snapshot(payload)
        with self._lock:
            if self._closed:
                raise EngineClosed(f"{self.name} is closed")
            waiting = len(self._pending) + len(self._pending_resume)
            if waiting >= self.max_queue:
                self._m_shed.inc(reason="queue_full")
                raise EngineOverloaded(
                    f"{self.name} decode queue full; seed the handoff "
                    "elsewhere")
            if self._slots.free_count() == 0 and waiting > 0:
                self._m_shed.inc(reason="no_free_slot")
                raise EngineOverloaded(
                    f"{self.name} has no free KV slot and {waiting} "
                    "sequences already waiting; seed the handoff "
                    "elsewhere")
        return header, arrays

    def resume(self, snapshot, token_budget_s=None, trace_id=None,
               snapshot_every=None, max_new_tokens=None,
               speculative=False):
        """Resume a snapshotted sequence on THIS engine at its exact
        position; -> :class:`DecodeRequest`.

        The returned request's ``next_tokens`` yields only the tokens
        AFTER the snapshot position (what a resumed wire stream must
        carry) while ``result`` returns the full sequence including
        the snapshot's tail. The join enters the step loop through the
        already-warm (rows, seq) ladder — no new program shapes, so a
        resume costs zero post-warmup compiles — and greedy decode is
        RNG-free, so the suffix is bitwise identical to an unbroken
        solo decode of the same prompt."""
        chaos.hit("serving.decode.resume")
        header, arrays = self.check_snapshot(snapshot)
        m = self._model
        n_kv = int(header.get("n_kv", len(m.kv_spec)))
        prompt, tail = arrays[0], arrays[1]
        kv_arrays = list(arrays[2:2 + n_kv])
        feats = [np.ascontiguousarray(a) for a in arrays[2 + n_kv:]]
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else header.get("max_new_tokens")
                      or self.default_max_new_tokens)
        eos = header.get("eos_token_id")
        pos = int(header["pos"])
        n_gen = int(header["n_generated"])
        last = int(header["last_token"])
        if trace_id is None:
            trace_id = obs_tracing.current_trace_id()
        req = DecodeRequest(np.ascontiguousarray(prompt.astype(np.int32)),
                            feats, max_new, eos, token_budget_s,
                            trace_id, tail.dtype.type)
        req.snapshot_every = max(0, int(
            self.default_snapshot_every if snapshot_every is None
            else snapshot_every))
        req.speculative = bool(speculative)
        # pre-fill the snapshot's tail as already-consumed: result()
        # sees the full sequence, the stream re-emits nothing
        req._tokens = [int(t) for t in tail]
        req._taken = n_gen
        # a snapshot taken AT a stop boundary resumes to an immediate
        # clean finish — never a slot occupied for zero steps
        if eos is not None and last == eos:
            done = "eos"
        elif n_gen >= max_new:
            done = "max_tokens"
        elif pos >= self.max_seq_len:
            done = "max_seq_len"
        else:
            done = None
        if done is not None:
            with self._lock:
                self._n_resumes_ok += 1
            self._m_retired.inc(reason=done)
            req._finish(done)
            return req
        state = {"pos": pos, "last_token": last, "n_generated": n_gen}
        with self._cond:
            if self._closed:
                raise EngineClosed(f"{self.name} is closed")
            if (len(self._pending) + len(self._pending_resume)
                    >= self.max_queue):
                self._m_shed.inc(reason="queue_full")
                raise EngineOverloaded(
                    f"{self.name} decode queue full; resume shed")
            self._pending_resume.append((req, kv_arrays, state))
            self._m_requests.inc()
            self._cond.notify_all()
        return req

    # --------------------------------------------------------- scheduler
    def _run_scheduler(self, gen):
        try:
            self._scheduler_loop(gen)
        except Exception:  # noqa: BLE001 - watchdog owns recovery
            traceback.print_exc()
            if self._watchdog is None:
                self._restart_scheduler(gen, "died (watchdog disabled)")

    def _scheduler_loop(self, gen):
        while True:
            # GIL-atomic monotonic bump, same contract as batching.py
            self._heartbeat = time.monotonic()  # tpu-lint: disable=TPU305  # benign race: GIL-atomic monotonic bump
            joiners = self._wait_for_work(gen)
            if joiners is None:
                return  # closed and drained, or superseded
            chaos.hit("serving.decode.loop")
            if joiners:
                self._prefill(gen, joiners)
            if self._superseded(gen):
                return
            self._purge_blown_budgets(gen)
            if self._active:
                self._decode_step(gen)
            if self._superseded(gen):
                return

    def _superseded(self, gen):
        with self._lock:
            return self._sched_gen != gen or self._closed

    # tpu-resource: acquires=kv_slot releases=kv_slot
    def _join_resumes_locked(self, now):
        """Re-admit resume joiners FIRST (they already paid their
        prefill elsewhere), while slots are free, entirely under the
        caller's ``_cond`` hold — restore is pure host memcpy, so a
        resumed sequence can never be stranded in-flight by a
        scheduler death. The restored slot is owned by the active
        sequence from birth and freed through the normal retire
        paths."""
        while self._pending_resume and self._slots.free_count() > 0:
            req, kv_arrays, st = self._pending_resume.pop(0)
            slot = self._slots.restore(kv_arrays, st["pos"])
            s = _Seq(req, slot, st["pos"], st["last_token"], now)
            s.n_generated = st["n_generated"]
            self._active.append(s)
            self._n_resumes_ok += 1

    def _wait_for_work(self, gen):
        """Park until there is something to do; pop this iteration's
        joiners (bounded by free slots). None = exit this thread."""
        with self._cond:
            while True:
                if self._sched_gen != gen:
                    return None
                now = time.monotonic()
                self._purge_expired_pending_locked(now)
                self._drop_cancelled_locked()
                if self._active or self._pending or self._pending_resume:
                    break
                if self._closed:
                    return None
                self._cond.wait()  # tpu-lint: disable=TPU303  # submit/cancel/close/restart all notify_all under _cond
            self._join_resumes_locked(now)
            joiners = []
            free = self._slots.free_count()
            while self._pending and len(joiners) < free:
                joiners.append(self._pending.pop(0))
            self._inflight_join = joiners
            return joiners

    def _purge_expired_pending_locked(self, now):
        """Per-token SLO on the FIRST token: a queued request whose
        budget already elapsed is purged before any compute (resume
        joiners: the first RESUMED token — same clock, same status)."""
        expired = [r for r in self._pending
                   if r.token_budget_s is not None
                   and now - r.t_enqueue >= r.token_budget_s]
        for r in expired:
            self._pending.remove(r)
            self._m_deadline.inc(stage="expired")
            r._fail(DeadlineExceeded(
                f"{self.name}: per-token budget elapsed before the "
                "sequence could join; dropped without compute"))
        expired_resume = [e for e in self._pending_resume
                          if e[0].token_budget_s is not None
                          and now - e[0].t_enqueue
                          >= e[0].token_budget_s]
        for e in expired_resume:
            self._pending_resume.remove(e)
            self._m_deadline.inc(stage="expired")
            e[0]._fail(DeadlineExceeded(
                f"{self.name}: per-token budget elapsed before the "
                "resumed sequence could join; dropped without compute"))

    def _drop_cancelled_locked(self):
        self._pending[:] = [r for r in self._pending if not r.cancelled]
        self._pending_resume[:] = [e for e in self._pending_resume
                                   if not e[0].cancelled]

    # tpu-resource: releases=kv_slot
    def _purge_blown_budgets(self, gen):
        """Retire active sequences that were cancelled or blew their
        per-token budget — BEFORE the next step, so a dead client's
        slot frees immediately instead of riding the batch to
        max_new_tokens (the slot-leak audit of ISSUE 12). Slot release
        and the active-list update happen under ONE lock acquisition:
        a concurrent watchdog restart releases every active slot, and
        interleaving with it would double-free a slot into the pool."""
        now = time.monotonic()
        purged = []
        with self._lock:
            if self._sched_gen != gen or self._closed:
                # a stale (restarted-away) scheduler must not touch
                # the replacement's active list or free slots it no
                # longer owns — the restart handled every sequence it
                # knew about
                return
            keep = []
            for s in self._active:
                if s.req.cancelled:
                    purged.append((s, "cancelled", None))
                    self._release_seq(s)
                elif (s.req.token_budget_s is not None
                        and now - s.t_last > s.req.token_budget_s):
                    purged.append((s, "deadline", DeadlineExceeded(
                        f"{self.name}: per-token budget "
                        f"{s.req.token_budget_s}s blown after "
                        f"{s.n_generated} tokens; slot purged")))
                    self._release_seq(s)
                else:
                    keep.append(s)
            self._active[:] = keep
        for s, reason, err in purged:
            self._notify_retired(s, reason, err)

    # ----------------------------------------------------------- prefill
    # tpu-resource: acquires=kv_slot releases=kv_slot
    def _prefill(self, gen, joiners):
        """Admit joiners: consult the prefix cache, run the prefill
        program over cache-miss prompts ONLY, install shared pages for
        hits, then feed every joiner's uncached suffix token-by-token
        through the already-warm step rungs. The LAST suffix step is
        the *finishing step* — the last prompt token fed at position
        P-1 — and its logits produce the first emitted token for cold
        and hit joiners alike, so the first token always comes from
        the identical step-shaped computation: prefix-hit-vs-cold
        bitwise equality holds by construction, not by tolerance."""
        plans = []
        for r in joiners:
            plan = {"req": r, "hashes": [], "hit": None, "load": None}
            if self._prefix is not None:
                hashes = prefix_hashes(r.prompt, self._slots.page_len,
                                       feature_seed(r.features))
                plan["hashes"] = hashes
                if hashes:
                    hit = self._prefix.lookup(hashes)
                    if hit is not None:
                        plan["hit"] = hit
                        self._m_prefix_hits.inc()
                    else:
                        self._m_prefix_misses.inc()
                        # persistent tier: file IO, engine lock NOT held
                        plan["load"] = self._prefix.load_store(
                            hashes, r.prompt)
            plans.append(plan)
        cold = [p for p in plans
                if p["hit"] is None and p["load"] is None]
        kv_cold = None
        if cold:
            rows = bucket_rows(max(len(cold), 2), self._rows_cap)
            p_bucket = seq_bucket(max(p["req"].prompt.size for p in cold),
                                  self.min_seq_bucket, self.max_seq_len)
            key = ("prefill", rows, p_bucket)
            if not self._breaker_allows(key, joiners):
                with self._lock:
                    if self._sched_gen == gen and not self._closed:
                        # stale schedulers must not wipe the REPLACEMENT
                        # scheduler's in-flight joiner record
                        self._inflight_join = []
                return
            t0 = time.monotonic()
            try:
                run = self._program(key, warming=False,
                                    trace_id=next(
                                        (r.trace_id for r in joiners
                                         if r.trace_id is not None),
                                        None))
                tokens = np.zeros((rows, p_bucket), np.int32)
                lengths = np.ones((rows,), np.int32)  # pad rows: len 1
                for i, p in enumerate(cold):
                    tokens[i, :p["req"].prompt.size] = p["req"].prompt
                    lengths[i] = p["req"].prompt.size
                batch = [tokens, lengths] + self._feature_batch(
                    [p["req"] for p in cold], rows)
                chaos.hit("serving.decode.prefill")
                outs = run(batch)
            except Exception as e:  # noqa: BLE001 - fail these joiners
                self._record_breaker(key, ok=False)
                err = e if isinstance(e, RetryableError) \
                    else RetryableError(
                        f"{self.name}: prefill failed "
                        f"({type(e).__name__}: {e}); retry the request")
                with self._lock:
                    if self._sched_gen == gen and not self._closed:
                        self._inflight_join = []
                for r in joiners:
                    r._fail(err)
                    self._m_retired.inc(reason="error")
                return
            self._record_breaker(key, ok=True)
            dt = time.monotonic() - t0
            self._m_steps.inc(phase="prefill")
            self._m_step_exec.observe(dt, phase="prefill")
            obs_tracing.observe("serving.decode.prefill", dt)
            kv_cold = outs[1:]
            for i, p in enumerate(cold):
                p["cold_row"] = i
        # --- install: slots alloc + page installs + active-list entry
        # in ONE lock acquisition, so the sequences are restart-visible
        # from the instant they hold slots (a watchdog sweep releases
        # and fails exactly these — no leak window)
        now = time.monotonic()
        admitted = []  # feed state: {"s", "q", "installed", "plan"}
        stale = False
        with self._lock:
            if self._sched_gen != gen or self._closed:
                stale = True
            else:
                self._inflight_join = []
                for p in plans:
                    r = p["req"]
                    P = r.prompt.size
                    # guaranteed non-None: admission was bounded by
                    # the free count
                    slot = self._slots.alloc()
                    if p["hit"] is not None:
                        installed, pages = p["hit"]
                        self._slots.install_shared(slot, pages)
                    elif p["load"] is not None:
                        hx, installed, kv_arrays = p["load"]
                        pages = self._prefix.install_arrays(
                            hx, installed, kv_arrays)
                        self._slots.install_shared(slot, pages)
                    else:
                        installed = P
                        self._slots.write_prefill(
                            slot, [k[p["cold_row"]] for k in kv_cold], P)
                    s = _Seq(r, slot, installed, 0, now)
                    s.n_generated = 0  # nothing emitted until the
                    # finishing step's logits land
                    self._active.append(s)
                    admitted.append({"s": s, "q": min(installed, P - 1),
                                     "installed": installed, "plan": p})
        if stale:
            err = SchedulerRestarted(
                f"{self.name} decode scheduler was restarted while this "
                "sequence was in prefill; retry the request")
            for r in joiners:
                r._fail(err)
            return
        # --- suffix feed: token-by-token through the step ladder,
        # every joiner in one batch (a cold joiner feeds exactly its
        # finishing step; a hit joiner feeds positions c..P-1)
        feed = list(admitted)
        prefill_chaos = not cold  # admissions with zero cold prompts
        # still traverse the prefill chaos site exactly once
        while feed:
            n = len(feed)
            rows = bucket_rows(max(n, 2), self._rows_cap)
            need = max(f["q"] + 1 for f in feed)
            seq_b = seq_bucket(need, self.min_seq_bucket,
                               self.max_seq_len)
            key = ("step", rows, seq_b)
            if not self._breaker_allows(key, [f["s"].req
                                              for f in admitted]):
                self._drop_admitted(gen, admitted)
                return
            t0 = time.monotonic()
            try:
                run = self._program(key, warming=False,
                                    trace_id=next(
                                        (f["s"].req.trace_id
                                         for f in feed
                                         if f["s"].req.trace_id
                                         is not None), None))
                tokens = np.zeros((rows,), np.int32)
                positions = np.zeros((rows,), np.int32)
                for i, f in enumerate(feed):
                    tokens[i] = int(f["s"].req.prompt[f["q"]])
                    positions[i] = f["q"]
                kv = self._slots.gather([f["s"].slot for f in feed],
                                        [f["q"] for f in feed],
                                        rows, seq_b)
                batch = ([tokens, positions] + kv
                         + self._feature_batch(
                             [f["s"].req for f in feed], rows))
                if prefill_chaos:
                    chaos.hit("serving.decode.prefill")
                    prefill_chaos = False
                outs = run(batch)
            except Exception as e:  # noqa: BLE001 - abort the admission
                self._record_breaker(key, ok=False)
                err = e if isinstance(e, RetryableError) \
                    else RetryableError(
                        f"{self.name}: prefix fill failed "
                        f"({type(e).__name__}: {e}); retry the request")
                self._drop_admitted(gen, admitted, err)
                return
            self._record_breaker(key, ok=True)
            dt = time.monotonic() - t0
            self._m_steps.inc(phase="prefix_fill")
            self._m_step_exec.observe(dt, phase="prefix_fill")
            obs_tracing.observe("serving.decode.prefix_fill", dt)
            logits = outs[0]
            entries = outs[1:]
            with self._lock:
                if self._sched_gen != gen or self._closed:
                    return  # restart failed + released the admitted
                for i, f in enumerate(feed):
                    s = f["s"]
                    if f["q"] >= f["installed"]:
                        self._slots.write_entry(s.slot, f["q"],
                                                [e[i] for e in entries])
                    # else: the computed KV row is bitwise equal to the
                    # installed shared page — skip the host write so
                    # COW never clones over an identical value
                    if f["q"] == s.req.prompt.size - 1:
                        f["first"] = int(np.argmax(logits[i]))
                    f["q"] += 1
            feed = [f for f in feed if f["q"] < f["s"].req.prompt.size]
        # --- emit first tokens + cache inserts, one lock acquisition
        now = time.monotonic()
        finished = []  # (seq, reason, err) notified post-lock
        snaps = []     # (seq, kv copies, pos, last, n_gen) — encoded
        # after the lock, same discipline as the step path
        pubs = []      # persistent-tier publishes (file IO, post-lock)
        with self._lock:
            if self._sched_gen != gen or self._closed:
                return  # restart failed + released the admitted
            drop = set()
            for f in admitted:
                s = f["s"]
                r = s.req
                tok = f["first"]
                s.pos = r.prompt.size
                s.last_token = tok
                s.n_generated = 1
                if (r.token_budget_s is not None
                        and now - r.t_enqueue > r.token_budget_s):
                    # the FIRST token is a token too: a blown TTFT
                    # budget fails retryable and frees the slot
                    drop.add(id(s))
                    self._release_seq(s)
                    finished.append((s, "deadline", DeadlineExceeded(
                        f"{self.name}: first token arrived past the "
                        f"per-token budget {r.token_budget_s}s")))
                    continue
                self._m_ttft.observe(now - r.t_enqueue)
                self._emit(s, tok, now, ttft=True)
                # prefill-boundary snapshot (cadence 1 only): the
                # n_generated=1 block IS the prefill->decode handoff
                # format, and it must exist even when the sequence
                # retires right here (a handoff request runs with
                # max_new_tokens=1) — so the kv copies are taken
                # BEFORE the slot can be released
                if r.snapshot_every == 1:
                    snaps.append(
                        (s, self._slots.snapshot(s.slot, s.pos),
                         s.pos, s.last_token, s.n_generated))
                hashes = f["plan"]["hashes"]
                if self._prefix is not None and hashes:
                    # retain EVERY chain boundary (pages are shared
                    # between them, so a shorter shared prefix still
                    # hits); evictions ride the LRU byte budget
                    ev = 0
                    for n_tok, hx in hashes:
                        ev += self._prefix.insert(
                            hx, n_tok,
                            self._slots.export_pages(
                                s.slot,
                                n_tok // self._slots.page_len))
                    if ev:
                        self._m_prefix_evictions.inc(ev)
                    n_tok, hx = hashes[-1]
                    if self._prefix.needs_publish(hx):
                        pubs.append((hx, n_tok, r.prompt,
                                     self._slots.snapshot(s.slot,
                                                          n_tok)))
                reason = self._stop_reason(s)
                if reason is not None:
                    drop.add(id(s))
                    self._release_seq(s)
                    finished.append((s, reason, None))
            if drop:
                self._active[:] = [x for x in self._active
                                   if id(x) not in drop]
        # push snapshots BEFORE retirement notification: _push_snapshot
        # on a finished request is a no-op, and the handoff flow needs
        # the n_generated=1 block of a max_new_tokens=1 sequence
        for s, kv_copies, pos, last, n_gen in snaps:
            try:
                chaos.hit("serving.decode.snapshot")
                s.req._push_snapshot(self._build_snapshot(
                    s.req, kv_copies, pos, last, n_gen), n_gen)
                with self._lock:
                    self._n_snapshots += 1
            except Exception:  # noqa: BLE001 - degraded, never fatal
                # a failed snapshot just means no resume point for this
                # window; the stream itself must keep flowing
                pass
        for hx, n_tok, prompt, kv_copies in pubs:
            try:
                self._prefix.publish(hx, n_tok, prompt, kv_copies)
            except Exception:  # noqa: BLE001 - publish is best-effort
                pass
        for s, reason, err in finished:
            self._notify_retired(s, reason, err)

    # tpu-resource: releases=kv_slot
    def _drop_admitted(self, gen, admitted, err=None):
        """Abort a mid-prefill admission: pull the sequences off the
        active list and free their slots atomically against a restart
        sweep; fail the requests when ``err`` is given (a breaker shed
        already failed them in ``_breaker_allows``)."""
        with self._lock:
            if self._sched_gen != gen or self._closed:
                return  # the restart swept these already
            drop = {id(f["s"]) for f in admitted}
            self._active[:] = [x for x in self._active
                               if id(x) not in drop]
            for f in admitted:
                self._release_seq(f["s"])
        if err is not None:
            for f in admitted:
                self._m_retired.inc(reason="error")
                f["s"].req._fail(err)

    # ------------------------------------------------------- decode step
    def _decode_step(self, gen):
        """One scheduler iteration over the active set: members that
        opted into speculation (and have headroom) take a draft+verify
        burst; everyone else takes one plain step. A draft-side
        failure NEVER fails a request — the speculative group falls
        back to the plain step path for this iteration."""
        active = list(self._active)
        spec, normal = [], []
        for s in active:
            (spec if self._spec_ok(s) else normal).append(s)
        if spec and not self._spec_group(gen, spec):
            normal += spec  # draft fallback: plain-step this iteration
        if normal:
            self._step_group(gen, normal)

    def _spec_ok(self, s):
        """May this sequence take a K-token speculative burst now?
        Needs opt-in, room for K kv entries, and at least 2 tokens of
        budget left (a 1-token tail is cheaper as a plain step)."""
        return (self.spec_enabled and s.req.speculative
                and s.pos + self._spec_k <= self.max_seq_len
                and s.req.max_new_tokens - s.n_generated >= 2)

    # tpu-resource: releases=kv_slot
    def _step_group(self, gen, active):
        n = len(active)
        rows = bucket_rows(max(n, 2), self._rows_cap)
        need = max(s.pos + 1 for s in active)
        seq_b = seq_bucket(need, self.min_seq_bucket, self.max_seq_len)
        key = ("step", rows, seq_b)
        if not self._breaker_allows(key, [s.req for s in active]):
            with self._lock:
                if self._sched_gen == gen and not self._closed:
                    drop = {id(s) for s in active}
                    for s in active:
                        self._release_seq(s)
                    self._active[:] = [x for x in self._active
                                       if id(x) not in drop]
            return
        t0 = time.monotonic()
        try:
            run = self._program(key, warming=False,
                                trace_id=next((s.req.trace_id
                                               for s in active
                                               if s.req.trace_id
                                               is not None), None))
            tokens = np.zeros((rows,), np.int32)
            positions = np.zeros((rows,), np.int32)
            for i, s in enumerate(active):
                tokens[i] = s.last_token
                positions[i] = s.pos
            kv = self._slots.gather([s.slot for s in active],
                                    [s.pos for s in active], rows, seq_b)
            batch = ([tokens, positions] + kv
                     + self._feature_batch([s.req for s in active], rows))
            chaos.hit("serving.decode.step")
            outs = run(batch)
        except Exception as e:  # noqa: BLE001 - fail the whole step batch
            # the step's kv writes never happened (the program raised),
            # but exactly-once token delivery is gone for this batch:
            # fail every member retryable and free the slots — clients
            # retry, parked requests join a healthy next iteration.
            # Release + clear happen atomically with the generation
            # check: a restart that raced us already did both.
            self._record_breaker(key, ok=False)
            err = e if isinstance(e, RetryableError) else RetryableError(
                f"{self.name}: decode step failed "
                f"({type(e).__name__}: {e}); retry the request")
            with self._lock:
                if self._sched_gen != gen or self._closed:
                    return  # restart already failed + released all
                drop = {id(s) for s in active}
                for s in active:
                    self._release_seq(s)
                self._active[:] = [x for x in self._active
                                   if id(x) not in drop]
            for s in active:
                self._m_retired.inc(reason="error")
                s.req._fail(err)
            return
        self._record_breaker(key, ok=True)
        now = time.monotonic()
        dt = now - t0
        self._m_steps.inc(phase="step")
        self._m_step_exec.observe(dt, phase="step")
        self._m_occupancy.observe(n / rows)
        obs_tracing.observe("serving.decode.step", dt)
        logits = outs[0]
        entries = outs[1:]
        finished = []  # (seq, reason, err) — notified after the lock
        snaps = []     # (seq, kv copies, pos, last, n_gen) — encoded
        # after the lock: header assembly touches the fingerprint lock
        # and json, neither of which may nest inside the engine lock
        with self._lock:
            if self._sched_gen != gen or self._closed:
                # superseded mid-step: the restart failed these
                # sequences and released their slots — our results
                # are late zombies and must not touch slot state
                # (_push on a done request is already a no-op)
                return
            # the whole result application is ONE lock acquisition:
            # slot writes/releases and the active-list update can
            # never interleave with a restart's release sweep
            drop = set()
            for i, s in enumerate(active):
                self._slots.write_entry(s.slot, s.pos,
                                        [e[i] for e in entries])
                s.pos += 1
                tok = int(np.argmax(logits[i]))
                s.last_token = tok
                s.n_generated += 1
                # per-token SLO enforced AT EMIT: a token that arrived
                # past the budget is an SLO miss — the client gave up
                # by its own timeout, so fail retryable and free the
                # slot rather than refresh t_last and pretend it was
                # on time
                if (s.req.token_budget_s is not None
                        and now - s.t_last > s.req.token_budget_s):
                    self._release_seq(s)
                    drop.add(id(s))
                    finished.append((s, "deadline", DeadlineExceeded(
                        f"{self.name}: token {s.n_generated} arrived "
                        f"{now - s.t_last:.3f}s after the previous one "
                        f"(per-token budget {s.req.token_budget_s}s); "
                        "slot purged")))
                    continue
                self._emit(s, tok, now)
                reason = self._stop_reason(s)
                if reason is None:
                    if (s.req.snapshot_every
                            and s.n_generated % s.req.snapshot_every
                            == 0):
                        snaps.append(
                            (s, self._slots.snapshot(s.slot, s.pos),
                             s.pos, s.last_token, s.n_generated))
                else:
                    self._release_seq(s)
                    drop.add(id(s))
                    finished.append((s, reason, None))
            if drop:
                self._active[:] = [x for x in self._active
                                   if id(x) not in drop]
        for s, kv_copies, pos, last, n_gen in snaps:
            try:
                chaos.hit("serving.decode.snapshot")
                s.req._push_snapshot(self._build_snapshot(
                    s.req, kv_copies, pos, last, n_gen), n_gen)
                with self._lock:
                    self._n_snapshots += 1
            except Exception:  # noqa: BLE001 - degraded, never fatal
                # a failed snapshot just means no resume point for this
                # window; the stream itself must keep flowing
                pass
        for s, reason, err in finished:
            self._notify_retired(s, reason, err)

    def _token_at(self, s, p):
        """The sequence's REAL token at absolute position ``p`` — the
        draft catch-up feed. Invariant: s.pos = plen + n_generated - 1,
        so positions below plen come from the prompt, s.pos carries
        last_token, and the span between is already-emitted output."""
        plen = s.req.prompt.size
        if p < plen:
            return int(s.req.prompt[p])
        if p == s.pos:
            return int(s.last_token)
        return int(s.req.tokens_so_far()[p - plen])

    # ---------------------------------------------------- speculative
    # tpu-resource: releases=kv_slot
    def _spec_group(self, gen, group):
        """One draft+verify burst for ``group``. Returns False when the
        DRAFT side cannot run (program failure, quarantine) — the
        caller then plain-steps the group, so draft trouble degrades
        throughput, never correctness. A VERIFY-side failure also
        falls back: no engine state mutates until verify results are
        applied host-side under the lock.

        Greedy equivalence: verify feeds [last_token, d_1..d_{K-1}] at
        positions pos..pos+K-1 through K UNROLLED step_fn iterations in
        one program — bitwise-identical per position to K sequential
        step dispatches — and the accept loop enters position j+1 only
        while d_j == argmax(logits_j), so every emitted token and every
        committed kv entry is exactly what non-speculative greedy
        decode would have produced. Rejected-run rollback is simply
        never writing the rejected entries."""
        K = self._spec_k
        # --- draft prefill for members that never drafted before
        fresh = [s for s in group if s.draft_slot is None]
        if fresh:
            rows = bucket_rows(max(len(fresh), 2), self._rows_cap)
            p_bucket = seq_bucket(max(s.req.prompt.size for s in fresh),
                                  self.min_seq_bucket, self.max_seq_len)
            key = ("draft_prefill", rows, p_bucket)
            if not self._breaker_probe(key):
                return False
            t0 = time.monotonic()
            try:
                run = self._program(key, warming=False)
                tokens = np.zeros((rows, p_bucket), np.int32)
                lengths = np.ones((rows,), np.int32)
                for i, s in enumerate(fresh):
                    tokens[i, :s.req.prompt.size] = s.req.prompt
                    lengths[i] = s.req.prompt.size
                batch = [tokens, lengths] + self._feature_batch(
                    [s.req for s in fresh], rows)
                outs = run(batch)
            except Exception:  # noqa: BLE001 - draft is best-effort
                self._record_breaker(key, ok=False)
                return False
            self._record_breaker(key, ok=True)
            self._m_steps.inc(phase="draft_prefill")
            self._m_step_exec.observe(time.monotonic() - t0,
                                      phase="draft_prefill")
            kv = outs[1:]
            with self._lock:
                if self._sched_gen != gen or self._closed:
                    return True  # restart owns the group now
                for i, s in enumerate(fresh):
                    # bounded: one draft slot per active sequence and
                    # the draft pool is sized like the target pool
                    s.draft_slot = self._draft_slots.alloc()
                    self._draft_slots.write_prefill(
                        s.draft_slot, [k[i] for k in kv],
                        s.req.prompt.size)
                    s.draft_pos = s.req.prompt.size
        # --- catch-up + propose: feed the draft model one token per
        # dispatch until every member's draft saw positions
        # 0..pos+K-2; feeds at >= pos come from last_token then the
        # draft's own proposals (the logits of feeds at >= pos ARE the
        # proposals d_1..d_{K-1})
        drafts = {id(s): [] for s in group}
        while True:
            todo = [s for s in group if s.draft_pos < s.pos + K - 1]
            if not todo:
                break
            rows = bucket_rows(max(len(todo), 2), self._rows_cap)
            need = max(s.draft_pos + 1 for s in todo)
            seq_b = seq_bucket(need, self.min_seq_bucket,
                               self.max_seq_len)
            key = ("draft_step", rows, seq_b)
            if not self._breaker_probe(key):
                return False
            feeds = []
            for s in todo:
                p = s.draft_pos
                if p <= s.pos:
                    feeds.append(self._token_at(s, p))
                else:
                    feeds.append(drafts[id(s)][p - s.pos - 1])
            t0 = time.monotonic()
            try:
                run = self._program(key, warming=False)
                tokens = np.zeros((rows,), np.int32)
                positions = np.zeros((rows,), np.int32)
                for i, s in enumerate(todo):
                    tokens[i] = feeds[i]
                    positions[i] = s.draft_pos
                kv = self._draft_slots.gather(
                    [s.draft_slot for s in todo],
                    [s.draft_pos for s in todo], rows, seq_b)
                batch = ([tokens, positions] + kv
                         + self._feature_batch([s.req for s in todo],
                                               rows))
                outs = run(batch)
            except Exception:  # noqa: BLE001 - draft is best-effort
                self._record_breaker(key, ok=False)
                return False
            self._record_breaker(key, ok=True)
            self._m_steps.inc(phase="draft_step")
            self._m_step_exec.observe(time.monotonic() - t0,
                                      phase="draft_step")
            logits = outs[0]
            entries = outs[1:]
            with self._lock:
                if self._sched_gen != gen or self._closed:
                    return True  # restart owns the group now
                for i, s in enumerate(todo):
                    self._draft_slots.write_entry(
                        s.draft_slot, s.draft_pos,
                        [e[i] for e in entries])
                    if s.draft_pos >= s.pos:
                        drafts[id(s)].append(int(np.argmax(logits[i])))
                    s.draft_pos += 1
        # --- verify: ONE batched target program over all K positions
        rows = bucket_rows(max(len(group), 2), self._rows_cap)
        need = max(s.pos + K for s in group)
        seq_b = seq_bucket(need, self.min_seq_bucket, self.max_seq_len)
        key = ("verify", rows, seq_b)
        if not self._breaker_probe(key):
            return False
        t0 = time.monotonic()
        try:
            run = self._program(key, warming=False,
                                trace_id=next((s.req.trace_id
                                               for s in group
                                               if s.req.trace_id
                                               is not None), None))
            tokens = np.zeros((rows, K), np.int32)
            positions = np.zeros((rows,), np.int32)
            for i, s in enumerate(group):
                tokens[i, 0] = s.last_token
                tokens[i, 1:] = drafts[id(s)]
                positions[i] = s.pos
            kv = self._slots.gather([s.slot for s in group],
                                    [s.pos for s in group], rows, seq_b)
            batch = ([tokens, positions] + kv
                     + self._feature_batch([s.req for s in group], rows))
            outs = run(batch)
        except Exception:  # noqa: BLE001 - fall back, requests unharmed
            self._record_breaker(key, ok=False)
            return False
        self._record_breaker(key, ok=True)
        now = time.monotonic()
        dt = now - t0
        self._m_steps.inc(phase="verify")
        self._m_step_exec.observe(dt, phase="verify")
        obs_tracing.observe("serving.decode.verify", dt)
        logits = outs[0]    # (rows, K, vocab)
        entries = outs[1:]  # each (rows, K, ...)
        finished = []
        snaps = []
        with self._lock:
            if self._sched_gen != gen or self._closed:
                return True  # restart owns the group now
            drop = set()
            for i, s in enumerate(group):
                d = drafts[id(s)]
                n0 = s.n_generated
                accepted = 0
                retired = False
                for j in range(K):
                    u = int(np.argmax(logits[i, j]))
                    # iteration j runs only while the fed token at j
                    # is the REAL token (j=0 feeds last_token; j>0
                    # guarded by the d[j-1]==u break below), so this
                    # kv entry is exactly the plain-step entry —
                    # rejected entries are simply never written
                    self._slots.write_entry(s.slot, s.pos,
                                            [e[i, j] for e in entries])
                    s.pos += 1
                    s.last_token = u
                    s.n_generated += 1
                    if j > 0:
                        accepted += 1
                    if (s.req.token_budget_s is not None
                            and now - s.t_last > s.req.token_budget_s):
                        self._release_seq(s)
                        drop.add(id(s))
                        finished.append((s, "deadline",
                                         DeadlineExceeded(
                            f"{self.name}: token {s.n_generated} "
                            f"arrived {now - s.t_last:.3f}s after the "
                            f"previous one (per-token budget "
                            f"{s.req.token_budget_s}s); slot purged")))
                        retired = True
                        break
                    self._emit(s, u, now)
                    reason = self._stop_reason(s)
                    if reason is not None:
                        self._release_seq(s)
                        drop.add(id(s))
                        finished.append((s, reason, None))
                        retired = True
                        break
                    if j + 1 < K and d[j] != u:
                        break  # first rejection ends the burst
                self._m_spec_accept.observe(accepted / (K - 1))
                self._n_spec_iters += 1
                self._n_spec_accepted += accepted
                if retired:
                    continue
                # rollback-by-pointer: draft entries past the accepted
                # run were computed from rejected tokens; the next
                # catch-up overwrites them before they become visible
                s.draft_pos = min(s.draft_pos, s.pos)
                if (s.req.snapshot_every
                        and s.n_generated // s.req.snapshot_every
                        > n0 // s.req.snapshot_every):
                    snaps.append(
                        (s, self._slots.snapshot(s.slot, s.pos),
                         s.pos, s.last_token, s.n_generated))
            if drop:
                self._active[:] = [x for x in self._active
                                   if id(x) not in drop]
        for s, kv_copies, pos, last, n_gen in snaps:
            try:
                chaos.hit("serving.decode.snapshot")
                s.req._push_snapshot(self._build_snapshot(
                    s.req, kv_copies, pos, last, n_gen), n_gen)
                with self._lock:
                    self._n_snapshots += 1
            except Exception:  # noqa: BLE001 - degraded, never fatal
                pass
        for s, reason, err in finished:
            self._notify_retired(s, reason, err)
        return True

    # ----------------------------------------------------------- helpers
    def _feature_batch(self, reqs, rows):
        spec = self._model.feature_spec
        out = [np.zeros((rows,) + tr, dt) for tr, dt in spec]
        for i, r in enumerate(reqs):
            for o, f in zip(out, r.features):
                o[i] = f
        return out

    def _emit(self, s, tok, now, ttft=False):
        gap = now - (s.req.t_enqueue if ttft else s.t_last)
        if not ttft:
            self._m_intertoken.observe(gap)
        s.t_last = now
        self._m_tokens.inc()
        if s.req.trace_id is not None:
            obs_tracing.record_span(
                "serving.decode.token", gap,
                trace_id=s.req.trace_id, engine=self.name,
                index=s.n_generated - 1, first=ttft)
        s.req._push(tok)

    def _stop_reason(self, s):
        """Why this sequence retires now, or None (pure check — the
        caller owns the slot release)."""
        if s.req.eos_token_id is not None \
                and s.last_token == s.req.eos_token_id:
            return "eos"
        if s.n_generated >= s.req.max_new_tokens:
            return "max_tokens"
        if s.pos >= self.max_seq_len:
            return "max_seq_len"
        if s.req.cancelled:
            return "cancelled"
        return None

    def _notify_retired(self, s, reason, err=None):
        """Counters + request completion for a sequence whose slot the
        caller already released. Runs OUTSIDE the engine lock."""
        if reason == "deadline":
            self._m_deadline.inc(stage="late")
        self._m_retired.inc(reason=reason)
        if err is not None:
            s.req._fail(err)
        else:
            s.req._finish(reason)
            if s.req.trace_id is not None:
                obs_tracing.record_span(
                    "serving.decode.request",
                    time.monotonic() - s.req.t_enqueue,
                    trace_id=s.req.trace_id, engine=self.name,
                    tokens=s.n_generated, reason=reason)

    # tpu-resource: releases=kv_slot
    def _release_seq(self, s):
        """Free EVERY slot an active sequence holds (target + draft).
        The single release point for active sequences: keeping the
        exactly-once discipline in one place is what keeps the shared-
        page refcounts balanced across purge / retire / restart /
        close paths. Callers hold the engine lock."""
        self._slots.release(s.slot)
        if s.draft_slot is not None:
            self._draft_slots.release(s.draft_slot)
            s.draft_slot = None

    def _breaker_probe(self, key):
        """Breaker check WITHOUT the fail-fast side effect — for the
        draft/verify ladder, where a quarantined program means 'fall
        back to plain steps', never 'fail the requests'."""
        now = time.monotonic()
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                br = self._breakers[key] = _Breaker(
                    self.breaker_threshold, self.breaker_cooldown)
            return br.allow(now)

    def _breaker_allows(self, key, reqs):
        """Check/trip the program-key breaker; on shed, fail ``reqs``
        fast with the retryable quarantine status."""
        now = time.monotonic()
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                br = self._breakers[key] = _Breaker(self.breaker_threshold,
                                                   self.breaker_cooldown)
            allowed = br.allow(now)
            if not allowed:
                br.shed += len(reqs)
                self._m_shed.inc(len(reqs), reason="quarantine")
        if not allowed:
            err = BucketQuarantined(
                f"{self.name} program {key} is quarantined after "
                f"{br.failures} consecutive failures; retry after "
                f"cooldown ({self.breaker_cooldown}s)")
            for r in reqs:
                r._fail(err)
                self._m_retired.inc(reason="error")
        return allowed

    def _record_breaker(self, key, ok):
        now = time.monotonic()
        with self._lock:
            br = self._breakers.get(key)
            if br is not None:
                br.record_success() if ok else br.record_failure(now)

    # ----------------------------------------------------------- programs
    def _program(self, key, warming=False, trace_id=None):
        """Materialize-once per (phase, rows, seq) — the decode twin of
        BatchingEngine._compiled (in-flight event so warmup and the
        scheduler never compile the same key twice). ``draft_*`` phases
        route to the draft model's program set; they share this cache,
        the compile counters, and the breakers under their full key."""
        phase, rows, seq_b = key
        while True:
            with self._lock:
                run = self._cache.get(key)
                if run is not None:
                    return run
                ev = self._compiling.get(key)
                if ev is None:
                    ev = self._compiling[key] = threading.Event()
                    mine = True
                else:
                    mine = False
            if not mine:
                # bounded like batching's cold-compile wait: a wedged
                # owner must fail this caller retryably, not park it
                # forever (the owner's compile may still land and cache
                # the program for the next attempt)
                if not ev.wait(_env_float(
                        "PADDLE_TPU_SERVING_COLD_COMPILE_TIMEOUT", 300.0)):
                    raise RetryableError(
                        f"{self.name}: compile for {key} still in "
                        "flight after the cold-compile timeout; retry")
                continue
            try:
                chaos.hit("serving.decode.compile")
                t0 = time.monotonic()
                if phase.startswith("draft_"):
                    run, source = self._draft_programs.compile(
                        phase[len("draft_"):], rows, seq_b,
                        warming=warming)
                else:
                    run, source = self._programs.compile(
                        phase, rows, seq_b, warming=warming)
            except BaseException:
                with self._lock:
                    self._compiling.pop(key, None)
                ev.set()
                raise
            dt = time.monotonic() - t0
            if trace_id is not None:
                obs_tracing.record_span("serving.decode.compile", dt,
                                        trace_id=trace_id,
                                        engine=self.name, phase=phase,
                                        rows=rows, seq=seq_b,
                                        source=source)
            else:
                obs_tracing.observe("serving.decode.compile", dt)
            with self._lock:
                self._cache[key] = run
                cc = self._compile_counts.setdefault(
                    key, {"inline": 0, "store": 0})
                cc[source] = cc.get(source, 0) + 1
                self._m_compiles.inc(phase=phase, source=source)
                self._compiling.pop(key, None)
            ev.set()
            return run

    def warmup(self, slot_buckets=None, seq_buckets=None,
               prompt_buckets=None):
        """Precompile the program ladder so no sequence pays a compile
        (and, with an artifact store attached, so a fresh replica
        loads the whole ladder with zero inline XLA compiles).
        Defaults: slot buckets = the power-of-2 ladder up to
        ``max_slots``; seq/prompt buckets = the power-of-2 ladder from
        ``min_seq_bucket`` up to ``max_seq_len`` / ``max_prompt_len``.
        Returns the declared (phase, rows, seq) list.

        A phased engine narrows its default ladder to its pool's hot
        programs: a ``prefill`` engine warms the full prompt ladder but
        only the smallest step bucket (its sequences stop at the first
        token; the residual step ladder exists solely for degraded
        colocated traffic), a ``decode`` engine warms the full step
        ladder but only the smallest prompt bucket (its sequences
        arrive as KV snapshots that already paid prefill elsewhere).
        Explicit bucket arguments always win."""
        def ladder(lo, hi):
            out, b = [], lo
            while b < hi:
                out.append(b)
                b <<= 1
            out.append(hi)
            return sorted(set(out))

        if slot_buckets is None:
            # the runtime floors every dispatch at 2 rows (gemm
            # regime), so the declared ladder starts there too — a
            # max_slots=1 engine runs its one sequence at rows=2
            slot_buckets = ladder(2, self._rows_cap)
        if seq_buckets is None:
            # a prefill-phase engine still runs the suffix-feed /
            # finishing step through the step ladder, so its step
            # rungs must reach the prompt bucket (not just the
            # smallest one)
            seq_buckets = (
                ladder(self.min_seq_bucket,
                       seq_bucket(self.max_prompt_len,
                                  self.min_seq_bucket, self.max_seq_len))
                if self.phase == "prefill"
                else ladder(self.min_seq_bucket, self.max_seq_len))
        if prompt_buckets is None:
            prompt_buckets = (
                [self.min_seq_bucket] if self.phase == "decode"
                else ladder(
                    self.min_seq_bucket,
                    seq_bucket(self.max_prompt_len, self.min_seq_bucket,
                               self.max_seq_len)))
        declared = []
        for rows in slot_buckets:
            rows = bucket_rows(int(rows), self._rows_cap)
            for sb in seq_buckets:
                declared.append(("step", rows,
                                 seq_bucket(int(sb), self.min_seq_bucket,
                                            self.max_seq_len)))
            for pb in prompt_buckets:
                declared.append(("prefill", rows,
                                 seq_bucket(int(pb), self.min_seq_bucket,
                                            self.max_seq_len)))
            if self.spec_enabled:
                # the speculative rungs: K-token verify + the draft
                # model's own step/prefill ladders — all plain
                # (phase, rows, seq) ArtifactKeys, warmed exactly
                # like the base ladder
                for sb in ladder(self.min_seq_bucket, self.max_seq_len):
                    declared.append(("verify", rows, sb))
                    declared.append(("draft_step", rows, sb))
                for pb in ladder(
                        self.min_seq_bucket,
                        seq_bucket(self.max_prompt_len,
                                   self.min_seq_bucket,
                                   self.max_seq_len)):
                    declared.append(("draft_prefill", rows, pb))
        declared = sorted(set(declared))
        for key in declared:
            self._program(key, warming=True)
        with self._lock:
            self._declared = declared
        return declared

    # -------------------------------------------------------------- views
    def stats(self):
        """Engine counters (merged into the cmd-5 ``stats`` wire view).
        One lock acquisition: never a torn snapshot."""
        with self._lock:
            programs = {}
            for key, cc in sorted(self._compile_counts.items()):
                phase, rows, seq_b = key
                d = {"compiles": cc.get("inline", 0),
                     "store_loads": cc.get("store", 0)}
                br = self._breakers.get(key)
                if br is not None:
                    d["breaker"] = br.as_dict()
                programs[f"{phase}{rows}x{seq_b}"] = d
            return {
                "name": self.name,
                "phase": self.phase,
                "quant": getattr(self._model, "quant", None) or "f32",
                "mesh": self.mesh_desc,
                "max_slots": self.max_slots,
                "max_seq_len": self.max_seq_len,
                "max_queue": self.max_queue,
                "active": len(self._active),
                "queue_depth": len(self._pending),
                "requests": int(self._m_requests.value()),
                "tokens": int(self._m_tokens.value()),
                "shed_count": int(self._m_shed.value(reason="queue_full")),
                "quarantine_shed": int(
                    self._m_shed.value(reason="quarantine")),
                "deadline_expired": int(
                    self._m_deadline.value(stage="expired")),
                "deadline_late": int(
                    self._m_deadline.value(stage="late")),
                "scheduler_restarts": int(self._m_restarts.value()),
                "snapshots": self._n_snapshots,
                "resume_queue_depth": len(self._pending_resume),
                "resumes": {"ok": self._n_resumes_ok,
                            "refused": self._n_resumes_refused},
                "retired": {r: int(self._m_retired.value(reason=r))
                            for r in _RETIRE_REASONS},
                "prefills": int(self._m_steps.value(phase="prefill")),
                "steps": int(self._m_steps.value(phase="step")),
                "prefix_fill_steps": int(
                    self._m_steps.value(phase="prefix_fill")),
                "prefix": (self._prefix.stats()
                           if self._prefix is not None else None),
                "shared_pages": (
                    self._slots.shared_pages()
                    + (self._draft_slots.shared_pages()
                       if self._draft_slots is not None else 0)),
                "live_pages": (
                    self._slots.live_pages()
                    + (self._draft_slots.live_pages()
                       if self._draft_slots is not None else 0)),
                "spec": {
                    "enabled": self.spec_enabled,
                    "k": self._spec_k,
                    "iterations": self._n_spec_iters,
                    "accepted": self._n_spec_accepted,
                    "verify_steps": int(
                        self._m_steps.value(phase="verify")),
                    "draft_steps": int(
                        self._m_steps.value(phase="draft_step")),
                    "draft_prefills": int(
                        self._m_steps.value(phase="draft_prefill")),
                },
                "compiles": sum(cc.get("inline", 0)
                                for cc in self._compile_counts.values()),
                "store_loads": sum(cc.get("store", 0)
                                   for cc in self._compile_counts.values()),
                "declared_programs": len(self._declared),
                "programs": programs,
            }

    def health(self):
        now = time.monotonic()
        store_stats = self._programs.store_stats()
        with self._lock:
            alive = self._scheduler.is_alive()
            quarantined = sorted(
                f"{k[0]}{k[1]}x{k[2]}" for k, br in self._breakers.items()
                if br.state != _Breaker.CLOSED)
            return {
                "ok": alive and not self._closed,
                "closed": self._closed,
                "phase": self.phase,
                "scheduler_alive": alive,
                "heartbeat_age_s": round(now - self._heartbeat, 3),
                "scheduler_restarts": int(self._m_restarts.value()),
                "active": len(self._active),
                "free_slots": self._slots.free_count(),
                "queue_depth": len(self._pending),
                "quarantined_programs": quarantined,
                "declared_programs": len(self._declared),
                "mesh": self.mesh_desc,
                "artifact_store": store_stats,
                "prefix_entries": (self._prefix.stats()["entries"]
                                   if self._prefix is not None else 0),
                "spec_enabled": self.spec_enabled,
            }

    # ----------------------------------------------------------- watchdog
    def _run_watchdog(self):
        """Restart a dead or wedged scheduler: active sequences fail
        retryable (their step state is owner-bound; a client retry
        re-decodes from the prompt), parked requests stay queued and
        are served by the replacement — same contract as the one-shot
        engine's watchdog."""
        while not self._closed_ev.wait(self.watchdog_interval):
            with self._lock:
                if self._closed:
                    return
                gen = self._sched_gen
                th = self._scheduler
                hb = self._heartbeat
                head = self._pending[0] if self._pending else None
                active = list(self._active)
            now = time.monotonic()
            dead = not th.is_alive()
            if head is not None:
                oldest = head.t_enqueue
            elif active:
                oldest = min(s.t_last for s in active)
            else:
                oldest = None
            wedged = (oldest is not None
                      and now - hb > self.wedge_timeout
                      and now - oldest > self.wedge_timeout)
            if dead:
                self._restart_scheduler(gen, "died")
            elif wedged:
                self._restart_scheduler(gen, "wedged (heartbeat stale)")

    # tpu-resource: releases=kv_slot
    def _restart_scheduler(self, observed_gen, reason):
        with self._cond:
            if self._closed or observed_gen != self._sched_gen:
                return
            self._sched_gen += 1
            gen = self._sched_gen
            stranded = list(self._active)
            self._active[:] = []
            stranded_join = list(self._inflight_join)
            self._inflight_join = []
            for s in stranded:
                # refcount-aware sweep: pages shared with the prefix
                # cache (or other survivors) are DECREMENTED here,
                # never freed out from under their other holders
                self._release_seq(s)
            self._m_restarts.inc()
            self._heartbeat = time.monotonic()
            t = threading.Thread(target=self._run_scheduler, args=(gen,),
                                 name=f"{self.name}-scheduler-g{gen}",
                                 daemon=True)
            self._scheduler = t
            # start INSIDE the lock: close() must never join an
            # unstarted thread (same rationale as batching.py)
            t.start()  # tpu-lint: disable=TPU304  # load-bearing: close() must never join an unstarted thread
            self._cond.notify_all()
        if stranded or stranded_join:
            err = SchedulerRestarted(
                f"{self.name} decode scheduler {reason} and was "
                "restarted; this sequence was mid-decode — its tokens "
                "so far were delivered but no more will come; retry the "
                "request")
            for s in stranded:
                self._m_retired.inc(reason="error")
                s.req._fail(err)
            for r in stranded_join:
                self._m_retired.inc(reason="error")
                r._fail(err)

    # -------------------------------------------------------------- close
    # tpu-resource: releases=kv_slot
    def close(self, timeout=5.0):
        """Stop the scheduler. Active sequences fail retryable (a
        close mid-stream is a shed, not silent truncation); queued
        requests fail retryable too; new submissions raise
        EngineClosed."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._closed_ev.set()
            pending = list(self._pending)
            self._pending[:] = []
            pending += [e[0] for e in self._pending_resume]
            self._pending_resume[:] = []
            active = list(self._active)
            self._active[:] = []
            for s in active:
                self._release_seq(s)
            if self._prefix is not None:
                # drop the cache's page references AFTER the active
                # sweep so every kv page's refcount walks to zero
                self._prefix.clear()
            self._cond.notify_all()
            sched = self._scheduler
        obs_metrics.REGISTRY.unregister_collector(self._obs_collector)
        err = EngineClosed(f"{self.name} is closing; retry elsewhere")
        for r in pending:
            r._fail(err)
        for s in active:
            s.req._fail(err)
        sched.join(timeout)
        if self._watchdog is not None:
            self._watchdog.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

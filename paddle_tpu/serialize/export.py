"""Shared ``jax.export`` helpers — the one program wire format.

``jit.save`` writes a serialized exported module (``.pdmodel``), the
serving engine publishes per-bucket exported programs into the
artifact store, and both read them back through here. Centralizing the
calls keeps the format decisions (and their failure modes) in one
place:

- ``serialize_exported`` / ``deserialize_exported``: byte-level
  round-trip of a ``jax.export.Exported``. Serialization is
  deterministic for a fixed program + jaxlib (verified in
  tests/test_artifact_store.py), which is what makes the artifact
  store content-addressable and lets jax's persistent compile cache
  key stably on the deserialized module across processes.
- ``model_fingerprint``: sha256 of the serialized module bytes. The
  compiled program depends on the traced computation and the
  shapes/dtypes of its inputs — not on weight *values* (weights are
  runtime arguments) — so the module bytes are exactly the right
  identity for the artifact-store key.
- ``runtime_version``: the jax/jaxlib/backend triple an artifact is
  tied to. A deserialized module is only guaranteed loadable under a
  compatible runtime, so this string is part of the store key: a
  version skew is a clean store *miss* (recompile), never a crash.

A **bit-flipped export blob is not rejected cleanly**: the flatbuffer
has no integrity check of the embedded StableHLO payload, so a flipped
bit can deserialize and execute silently wrong (seen on an earlier
jaxlib) or take the process down with a segfault inside deserialize
(seen on jaxlib 0.9.0, PR 21 probe) — which is why every consumer
of these bytes must verify a sha256 over them BEFORE deserializing.
The artifact store's MANIFEST does exactly that; ``jit.load`` trusts
local files the same way it always has.
"""
import hashlib


def serialize_exported(exported):
    """``jax.export.Exported`` -> bytes (the one on-disk format)."""
    return exported.serialize()


def deserialize_exported(blob):
    """bytes -> ``jax.export.Exported``. Raises on any malformed or
    version-incompatible payload — callers that cannot tolerate a
    raise (the artifact store load path) catch broadly and degrade."""
    from jax import export as jax_export

    return jax_export.deserialize(blob)


def canonical_module_bytes(exported):
    """Location-free identity bytes for a ``jax.export.Exported``.

    The serialized export embeds MLIR *debug locations* (``#locN``
    tables and inline ``loc(...)`` attributes) whose numbering depends
    on how many programs were traced earlier in the process — two
    byte-for-byte identical models can serialize differently depending
    on trace order (seen on an earlier jaxlib; a small re-export on
    0.9.0 came out byte-equal, so treat it as possible, not certain).
    Anything that
    keys on *model identity* — the artifact store, the decode
    KV-snapshot header — must therefore hash the module with every
    location stripped, or a resume between two processes at different
    trace positions is refused as "foreign model" when it is not.

    Returns the pretty-printed StableHLO text with all ``loc``
    attributes and ``#loc`` definition lines removed, UTF-8 encoded.
    Computation structure, shapes, and dtypes are all still in the
    text, so distinct programs still hash apart."""
    out = []
    for line in exported.mlir_module().splitlines():
        if line.lstrip().startswith("#loc"):
            continue
        out.append(_strip_locs(line))
    return "\n".join(out).encode("utf-8")


def _strip_locs(line):
    """Remove every balanced ``loc(...)`` attribute from one line of
    MLIR text (quote-aware: parens inside string literals don't
    count)."""
    res = []
    i, n = 0, len(line)
    while i < n:
        j = line.find("loc(", i)
        # only a real loc attribute when at start or after a delimiter
        while j > 0 and line[j - 1] not in " (,=":
            j = line.find("loc(", j + 1)
        if j == -1:
            res.append(line[i:])
            break
        res.append(line[i:j].rstrip())
        k, depth, in_str = j + 4, 1, False
        while k < n and depth:
            c = line[k]
            if in_str:
                if c == "\\":
                    k += 1
                elif c == '"':
                    in_str = False
            elif c == '"':
                in_str = True
            elif c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
            k += 1
        i = k
    return "".join(res)


def model_fingerprint(module_bytes, quant=None):
    """Content identity of a saved model: sha256 hex over its
    serialized exported-module bytes.

    ``quant`` (a serving quant mode: ``"w8"`` / ``"w8a8"`` /
    ``"bf16w"``) folds into the hash, so a quantized export is a
    DISTINCT artifact-store identity even in the degenerate case where
    two modes lower to byte-identical modules — a w8 program can never
    be served to an f32 request (or vice versa) on fingerprint grounds
    alone. ``None`` and the explicit ``"f32"`` spelling both keep the
    historical hash: every existing store and saved model keys
    identically regardless of which f32 spelling a caller uses."""
    h = hashlib.sha256(module_bytes)
    if quant is not None and quant != "f32":
        h.update(b"\x00quant:" + str(quant).encode("utf-8"))
    return h.hexdigest()


def runtime_version(backend=None):
    """The runtime an exported artifact is tied to, as one stable
    string: ``jax-<ver>/jaxlib-<ver>/<platform>``. Part of the
    artifact-store key, so artifacts written by a different runtime
    are simply never found (a miss, not a corruption)."""
    import jax

    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "unknown")
    except Exception:  # noqa: BLE001 - jaxlib may not expose a version
        jl = "unknown"
    if backend is None:
        try:
            backend = jax.default_backend()
        except Exception:  # noqa: BLE001 - no backend yet: still keyable
            backend = "unknown"
    return f"jax-{jax.__version__}/jaxlib-{jl}/{backend}"

"""Serving mesh layer: shard a served model's weights across chips
once at load, and name the layout so compiled programs key on it.

The serving stack (batching.py one-shot engine, decode.py continuous
batching) ran every model on ONE chip: models bigger than one chip's
HBM — the Llama-7B+ scenario the decode engine points at — could not
be served at all. The training side already proves the meshes work
(``distributed/topology.build_mesh`` + ``distributed/spmd.py``'s
PartitionSpec discipline, green over gloo CPU collectives in the
MULTICHIP dryruns); this module is the thin serving-side counterpart:

- :class:`ServingMesh` — a CANONICAL mesh descriptor (``"single"``,
  ``"tp2"``, ``"fsdp2"``, ``"fsdp2xtp2"``) parsed from
  ``serve_model(mesh=...)`` / ``DecodeEngine(mesh=...)`` or the
  ``PADDLE_TPU_SERVING_MESH`` env knob. The descriptor string IS the
  artifact-store key component (``ArtifactKey.mesh``): a sharded
  export can never satisfy a single-chip key and vice versa, and
  sharded programs persist / single-flight / cold-start across a
  replica fleet exactly like f32 and quantized ones.
- **Axes** (the SpecLayout fsdp×tp discipline, SNIPPETS [2], mapped
  onto ``topology.build_mesh``): ``tp`` (tensor parallel, the
  topology's ``mp`` axis — innermost, highest-bandwidth ICI ring)
  shards every weight's LAST dim; ``fsdp`` (the topology's
  ``sharding`` axis) shards the FIRST dim of >= 2-D weights. A dim
  that does not divide stays replicated — the discipline degrades
  per-tensor, never refuses a model.
- **Shard once at load**: :meth:`shard_arrays` commits the resident
  weights to the mesh with ``jax.device_put``; per-bucket programs
  are then compiled with those shardings as ``in_shardings`` (weights
  stay runtime args, shared across buckets, exactly like the
  single-chip engines) and replicated batch inputs/outputs, so the
  wire protocol is untouched — sharding is invisible to all four
  clients.

Determinism contract (measured on this jaxlib, pinned by
tests/test_sharded_serving.py): a program whose sharded dims are all
OUTPUT dims (the tp discipline on feed-forward layers) is **bitwise
identical** to its single-chip twin — each output element is computed
whole on one device and concatenated exactly. Sharding a CONTRACTION
dim (fsdp on a weight's first dim, or tp feeding an attention
contraction) makes XLA insert a psum whose reduction order differs
from the single-chip gemm: replies then agree within
:data:`SHARDED_FLOAT_TOL` (measured ~1e-6 relative on this jaxlib),
never bitwise. Solo-vs-batch decode determinism is bitwise PER MESH
regardless: row independence and masked-attention padding stability
survive sharding because every device sees whole rows.

The descriptor grammar is deliberately tiny and closed: new axes
(``pp``, ``ep``, ``sp`` serving) must extend :data:`_DESCRIPTOR_RE`
and the canonical ordering here, in ONE place, or the artifact store
would silently fork identities ("tp2xfsdp2" vs "fsdp2xtp2").
"""
import os
import re

SINGLE = "single"

# relative tolerance for sharded-vs-single float replies when a
# contraction dim is sharded (psum reduction-order drift; measured
# ~1e-6 on this jaxlib — the bound is deliberately 10x the observation)
SHARDED_FLOAT_TOL = 1e-5

# canonical axis order in descriptors: fsdp (the topology 'sharding'
# axis) before tp (the topology 'mp' axis)
_DESCRIPTOR_RE = re.compile(r"^(?:fsdp(?P<fsdp>[0-9]+))?"
                            r"(?:x?tp(?P<tp>[0-9]+))?$")
# accepted aliases for the tp axis (the reference's model-parallel
# serving expectation spells it mp)
_ALIAS_RE = re.compile(r"^mp(?P<tp>[0-9]+)$")


class ServingMesh:
    """One serving mesh: fsdp x tp shard counts plus the lazily-built
    jax Mesh. Immutable after construction; the canonical
    ``descriptor`` string is its identity everywhere (artifact keys,
    metrics labels, health/stats, ledger events, the wire's cmd-3/5
    JSON)."""

    __slots__ = ("fsdp", "tp", "_mesh")

    def __init__(self, fsdp=1, tp=1):
        fsdp, tp = int(fsdp), int(tp)
        if fsdp < 1 or tp < 1:
            raise ValueError(
                f"mesh axis sizes must be >= 1 (got fsdp={fsdp}, tp={tp})")
        self.fsdp = fsdp
        self.tp = tp
        self._mesh = None

    # ------------------------------------------------------- identity
    @property
    def descriptor(self):
        """Canonical string form — the ``ArtifactKey.mesh`` value."""
        if self.is_single:
            return SINGLE
        parts = []
        if self.fsdp > 1:
            parts.append(f"fsdp{self.fsdp}")
        if self.tp > 1:
            parts.append(f"tp{self.tp}")
        return "x".join(parts)

    @property
    def is_single(self):
        return self.fsdp == 1 and self.tp == 1

    @property
    def n_shards(self):
        """Devices this mesh spans (the exported program's device
        count — :func:`check_nr_devices` gates store loads on it)."""
        return self.fsdp * self.tp

    def __repr__(self):
        return f"ServingMesh({self.descriptor!r})"

    def __eq__(self, other):
        return (isinstance(other, ServingMesh)
                and other.fsdp == self.fsdp and other.tp == self.tp)

    def __hash__(self):
        return hash((self.fsdp, self.tp))

    # -------------------------------------------------------- parsing
    @classmethod
    def parse(cls, spec):
        """Descriptor -> ServingMesh. Accepts None / ``""`` /
        ``"single"`` (single-chip), ``"tp<k>"``, ``"fsdp<m>"``,
        ``"fsdp<m>xtp<k>"``, the ``"mp<k>"`` alias (normalized to
        ``tp<k>`` — the reference's model-parallel spelling), and a
        ServingMesh (passed through)."""
        if isinstance(spec, ServingMesh):
            return spec
        if spec is None:
            return cls()
        s = str(spec).strip().lower()
        if s in ("", SINGLE, "f32"):  # "f32" guard: a swapped quant/mesh
            if s == "f32":            # knob pair should say so, not parse
                raise ValueError(
                    "'f32' is a quant mode, not a mesh descriptor — did "
                    "you swap PADDLE_TPU_SERVING_QUANT and "
                    "PADDLE_TPU_SERVING_MESH?")
            return cls()
        m = _ALIAS_RE.match(s)
        if m:
            return cls(tp=int(m.group("tp")))
        m = _DESCRIPTOR_RE.match(s)
        if not m or (m.group("fsdp") is None and m.group("tp") is None):
            raise ValueError(
                f"unknown serving mesh descriptor {spec!r}: expected "
                "'single', 'tp<k>', 'mp<k>', 'fsdp<m>' or "
                "'fsdp<m>xtp<k>' (e.g. mesh='tp2', mesh='fsdp2xtp2')")
        return cls(fsdp=int(m.group("fsdp") or 1),
                   tp=int(m.group("tp") or 1))

    # ------------------------------------------------------ jax build
    def build(self):
        """The jax Mesh (lazy, cached). Raises with the remedy when
        the process has fewer devices than the mesh needs — on a CPU
        box that is the ``--xla_force_host_platform_device_count``
        XLA flag, on a TPU pod it is the slice topology."""
        if self._mesh is not None:
            return self._mesh
        if self.is_single:
            raise ValueError("a single-chip mesh has no device Mesh; "
                             "callers must branch on is_single")
        import jax

        have = len(jax.devices())
        if have < self.n_shards:
            raise ValueError(
                f"serving mesh {self.descriptor!r} needs "
                f"{self.n_shards} devices but this process has {have} "
                "(CPU: set XLA_FLAGS=--xla_force_host_platform_device_"
                "count=N before jax initializes; TPU: use a slice with "
                "enough chips)")
        from ..distributed import topology

        # tp -> the topology's innermost 'mp' axis (highest-bandwidth
        # ICI ring, the tensor-parallel placement rule); fsdp -> its
        # 'sharding' axis — the same mapping the training side uses
        self._mesh = topology.build_mesh(sharding=self.fsdp, mp=self.tp)
        return self._mesh

    # ------------------------------------------- PartitionSpec layout
    def param_spec(self, shape):
        """The SpecLayout fsdp x tp discipline for one weight:

        - >= 2-D: first dim over fsdp, last dim over tp (each only
          when it divides — an indivisible dim stays replicated);
        - 1-D: over tp when divisible (bias rides its matmul's
          output-dim layout);
        - 0-D: replicated.

        Returns a ``jax.sharding.PartitionSpec`` over the topology
        axis names (``sharding`` = fsdp, ``mp`` = tp)."""
        from jax.sharding import PartitionSpec as P

        shape = tuple(int(d) for d in shape)
        if not shape:
            return P()
        if len(shape) == 1:
            if self.tp > 1 and shape[0] % self.tp == 0:
                return P("mp")
            return P()
        dims = [None] * len(shape)
        if self.fsdp > 1 and shape[0] % self.fsdp == 0:
            dims[0] = "sharding"
        if self.tp > 1 and shape[-1] % self.tp == 0:
            dims[-1] = "mp"
        return P(*dims)

    def param_sharding(self, shape):
        from jax.sharding import NamedSharding

        return NamedSharding(self.build(), self.param_spec(shape))

    def replicated(self):
        """The sharding of everything that is NOT a weight: batch
        inputs, outputs, KV scratch — replicated, so the wire sees
        identical bytes and the host-side engines stay unchanged."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.build(), P())

    def shard_arrays(self, arrays):
        """Commit weights to the mesh ONCE at load: returns
        ``(placed, shardings)`` where ``placed[i]`` is ``arrays[i]``
        device_put under its discipline sharding. The engines hold
        these as the runtime args every bucket program shares."""
        import jax

        shardings = [self.param_sharding(getattr(a, "shape", ()))
                     for a in arrays]
        return ([jax.device_put(a, s) for a, s in zip(arrays, shardings)],
                shardings)

    def shard_fraction(self, shape):
        """1 / (shards this weight is split across) under the
        discipline — the per-device residency factor."""
        spec = self.param_spec(shape)
        frac = 1.0
        for dim_axes in spec:
            if dim_axes is None:
                continue
            for ax in ((dim_axes,) if isinstance(dim_axes, str)
                       else dim_axes):
                frac /= self.fsdp if ax == "sharding" else self.tp
        return frac

    def per_shard_bytes(self, arrays):
        """Weight bytes RESIDENT PER DEVICE under this mesh (a model
        whose per-shard bytes fit HBM serves even when its total bytes
        do not)."""
        import numpy as np

        total = 0.0
        for a in arrays:
            shape = tuple(getattr(a, "shape", ()))
            nbytes = (getattr(a, "nbytes", None)
                      or int(np.prod(shape or (1,)))
                      * np.dtype(getattr(a, "dtype", np.float32)).itemsize)
            total += nbytes * self.shard_fraction(shape)
        return int(total)


def resolve(mesh=None):
    """One resolution rule for every entry point: explicit arg >
    ``PADDLE_TPU_SERVING_MESH`` env > single-chip. Always returns a
    ServingMesh."""
    if mesh is None:
        mesh = os.environ.get("PADDLE_TPU_SERVING_MESH") or None
    return ServingMesh.parse(mesh)


def check_nr_devices(exported, mesh):
    """Gate a (store-loaded or freshly-built) exported program on its
    recorded device count matching the mesh. The artifact KEY already
    separates meshes, so in the normal flow this never fires — it is
    the defense in depth against a copied/renamed store dir or a
    hand-loaded export: a 4-device program must never reach a
    single-chip call site (where it would fail mid-request, or worse).
    Raises ValueError on skew."""
    want = 1 if mesh is None or mesh.is_single else mesh.n_shards
    got = int(getattr(exported, "nr_devices", 1))
    if got != want:
        desc = SINGLE if mesh is None else mesh.descriptor
        raise ValueError(
            f"mesh skew: exported program spans {got} device(s) but the "
            f"engine's mesh {desc!r} expects {want}")

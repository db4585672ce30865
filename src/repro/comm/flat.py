"""Flat wire-buffer layout shared by every comm stream — and, since
the flat-resident engine refactor, the canonical **in-round
representation** of all client-visible state (params, Sophia m/h,
EF residuals, downlink replicas; docs/architecture.md "Memory
layout") outside the local loop.  The local loop runs in the model's
leaf layout: `unpack_leaves` / `pack_leaves` cross between the two
once per client-round, each buffer keeping its resident dtype.

Every leaf of the pytree is flattened to fp32, concatenated,
zero-padded and reshaped to a (rows, cols) buffer.  Rows double as
the quantization scale groups, so one packed layout serves every
compressor and the Pallas kernels tile it directly.  All three named
streams of a round — the uplink model delta, the downlink broadcast
delta, and the hessian-EMA — share the flattened coordinate order
(the model and its Sophia ``h`` state have identical pytree
structure) but may disagree on the (rows, cols) geometry: each
stream's ``cols`` is its own ``quant_block`` (`CommConfig.stream`),
and `repack` re-lays a buffer between stream geometries.  Only the
true ``total`` coordinates ever count as wire bytes (the pad tail is
a simulation artifact — see docs/wire-format.md).

Helper semantics (the contracts the flat-resident engine relies on):

* `aval_key(tree)` — a hashable fingerprint of a pytree's structure
  plus leaf (shape, dtype) avals.  It deliberately ignores leaf
  *values* and shardings: two pytrees with the same key pack to the
  same `FlatSpec`, so engines memoize spec/compressor construction on
  it across traces (`FedEngine.comm_runtime`).  Works on concrete
  arrays, tracers and ShapeDtypeStructs alike.
* `zeros(spec, lead, dtype)` — allocates a zeroed flat state buffer
  in ``spec``'s wire layout without a donor pytree (per-client state
  gets leading axes via ``lead``).  ``dtype`` is the *storage* dtype
  (`CommConfig.state_dtype`); the zero pad tail is a fixed point of
  every engine op, so buffers from `zeros` stay valid wire buffers
  forever.
* `repack(flat, from_spec, to_spec)` — re-lays a packed buffer
  between two stream geometries that share the flattened ``total``
  coordinate order (different ``quant_block`` ⇒ different
  (rows, cols)).  Matching geometries return the *same array object*
  (zero ops in the traced graph) — callers must not mutate the result
  in place assuming it is a copy.

Donation-safety contract: the flat-resident engine donates its state
buffers to the jitted round (`FedEngine.round_fn`), so on
donation-capable backends every buffer reachable from the state dict
passed in — packed params, (C, rows, cols) m/h/EF/replica stacks —
is INVALIDATED by the call and aliased by the returned state.  A
caller that keeps a reference (for eval, checkpointing, or a
same-geometry `repack` view) must copy it out *before* the round, or
use the undonated entry point.  See docs/architecture.md
"Memory layout: the life of a round".

This module also owns the versioned wire **header** (`Header`): the
24-byte preamble every serialized payload carries, and the layout
fingerprint checkpoints store so comm/EF state written under one
config is never silently reinterpreted under another
(`check_headers`).
"""
from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

#: magic + version of the serialized wire-buffer format.  Version 2
#: (FSWB v2) carries the resident-state dtype in the previously
#: reserved flags byte; version-1 payloads/manifests (flags = 0) are
#: still accepted and decode as float32 (docs/wire-format.md).
WIRE_MAGIC = b"FSWB"
WIRE_VERSION = 2
#: versions `Header.unpack` / `check_headers` accept
SUPPORTED_WIRE_VERSIONS = (1, 2)
#: <magic 4s><version u16><compressor u8><flags u8><total u64>
#: <quant_block u32><aux u32>, little-endian (docs/wire-format.md).
#: flags (v2): low 4 bits = state-dtype id, high 4 bits reserved.
_HEADER_STRUCT = struct.Struct("<4sHBBQII")
HEADER_BYTES = _HEADER_STRUCT.size          # 24

#: stable on-the-wire compressor ids (never renumber — append only)
COMPRESSOR_IDS = {"identity": 0, "int8": 1, "int4": 2, "topk": 3,
                  "signsgd": 4}
_ID_COMPRESSORS = {v: k for k, v in COMPRESSOR_IDS.items()}

#: stable state-dtype ids carried in the v2 flags byte (append only);
#: 0 == float32 keeps v1 payloads (flags == 0) meaning what they meant.
#: ids 2/3 are the fp8 resident formats (E4M3 for moments, E5M2 for
#: the wider-range hessian EMA — docs/wire-format.md)
STATE_DTYPE_IDS = {"float32": 0, "bfloat16": 1,
                   "float8_e4m3fn": 2, "float8_e5m2": 3}
_ID_STATE_DTYPES = {v: k for k, v in STATE_DTYPE_IDS.items()}
#: name -> storage dtype; one registry for validation AND lookup, so
#: appending a dtype id without its jnp mapping is a loud error, never
#: a silent float32 fallback
_STATE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                 "float8_e4m3fn": jnp.float8_e4m3fn,
                 "float8_e5m2": jnp.float8_e5m2}
assert set(_STATE_DTYPES) == set(STATE_DTYPE_IDS)


def as_dtype(state_dtype: str):
    """`CommConfig.state_dtype` name -> jnp dtype (storage dtype of
    the resident wire-layout state)."""
    try:
        return _STATE_DTYPES[state_dtype]
    except KeyError:
        raise ValueError(
            f"unknown state_dtype {state_dtype!r} "
            f"(want one of {tuple(_STATE_DTYPES)})") from None


@dataclass(frozen=True)
class Header:
    """The versioned 24-byte preamble of every serialized payload.

    Also the checkpoint-manifest fingerprint of wire-layout engine
    state (uplink EF residuals, downlink replicas): restoring under a
    different geometry would silently misinterpret the packed rows, so
    `check_headers` rejects any mismatch with a clear error.

    ``aux`` carries the compressor-specific layout parameter (top-k:
    ``k``); 0 otherwise.  ``state_dtype`` (v2) is the storage dtype of
    resident wire-layout state written under this header — the wire
    *payload* bytes are dtype'd by the compressor, not this field.
    Version-1 headers decode with ``state_dtype="float32"``.
    """
    compressor: str
    total: int
    quant_block: int
    aux: int = 0
    version: int = WIRE_VERSION
    state_dtype: str = "float32"

    def pack(self) -> bytes:
        if self.compressor not in COMPRESSOR_IDS:
            raise ValueError(f"unknown compressor {self.compressor!r}")
        if self.state_dtype not in STATE_DTYPE_IDS:
            raise ValueError(f"unknown state_dtype {self.state_dtype!r}")
        flags = STATE_DTYPE_IDS[self.state_dtype]
        if self.version == 1 and flags:
            raise ValueError(
                "wire-format v1 cannot carry a non-float32 state_dtype "
                "(the flags byte was reserved = 0); write v2")
        return _HEADER_STRUCT.pack(
            WIRE_MAGIC, self.version, COMPRESSOR_IDS[self.compressor],
            flags, self.total, self.quant_block, self.aux)

    @classmethod
    def unpack(cls, buf: bytes) -> "Header":
        if len(buf) < HEADER_BYTES:
            raise ValueError(
                f"wire buffer too short for a header: {len(buf)} < "
                f"{HEADER_BYTES} bytes")
        magic, ver, comp_id, flags, total, qb, aux = \
            _HEADER_STRUCT.unpack_from(buf)
        if magic != WIRE_MAGIC:
            raise ValueError(
                f"not a Fed-Sophia wire buffer (magic {magic!r}, "
                f"expected {WIRE_MAGIC!r})")
        if ver not in SUPPORTED_WIRE_VERSIONS:
            raise ValueError(
                f"unsupported wire-format version {ver} (this build "
                f"speaks versions {SUPPORTED_WIRE_VERSIONS}); re-encode "
                f"the payload or upgrade")
        if comp_id not in _ID_COMPRESSORS:
            raise ValueError(f"unknown wire compressor id {comp_id}")
        if ver == 1:
            # v1 reserved the flags byte: anything nonzero is corrupt
            if flags:
                raise ValueError(
                    f"wire-format v1 header with nonzero reserved flags "
                    f"byte ({flags:#x})")
            sdt = "float32"
        else:
            if flags & 0xF0:
                # the high nibble is reserved = 0 in v2: nonzero means
                # corruption or a future format this build can't read
                raise ValueError(
                    f"wire-format v2 header with nonzero reserved flag "
                    f"bits ({flags:#x})")
            dt_id = flags & 0x0F
            if dt_id not in _ID_STATE_DTYPES:
                raise ValueError(f"unknown wire state-dtype id {dt_id}")
            sdt = _ID_STATE_DTYPES[dt_id]
        return cls(compressor=_ID_COMPRESSORS[comp_id], total=total,
                   quant_block=qb, aux=aux, version=ver, state_dtype=sdt)

    def to_dict(self) -> Dict[str, Any]:
        return {"version": self.version, "compressor": self.compressor,
                "total": self.total, "quant_block": self.quant_block,
                "aux": self.aux, "state_dtype": self.state_dtype}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Header":
        # v1 manifests predate the state_dtype field: default float32
        return cls(compressor=d["compressor"], total=int(d["total"]),
                   quant_block=int(d["quant_block"]),
                   aux=int(d.get("aux", 0)),
                   version=int(d.get("version", 1)),
                   state_dtype=d.get("state_dtype", "float32"))


def check_headers(saved: Dict[str, Dict[str, Any]],
                  current: Dict[str, Dict[str, Any]]) -> None:
    """Validate checkpointed per-stream wire headers against the
    current engine's (`FedEngine.wire_headers`).  Raises ValueError
    naming every mismatched stream/field — comm/EF state saved under
    one layout must never be reinterpreted under another.

    Versioning: headers saved under any `SUPPORTED_WIRE_VERSIONS`
    format load under the current one — a v1 manifest (no
    ``state_dtype`` field) is exactly a v2 header with
    ``state_dtype="float32"``, so upgrading the build never orphans a
    checkpoint; only the *layout* fields (compressor, total,
    quant_block, aux) must match.  ``state_dtype`` is deliberately NOT
    compared: checkpoints store the dtype-agnostic params pytree (the
    resident EF/replica/optimizer buffers are rebuilt on restore, not
    read back), so the resident storage dtype is a runtime choice —
    resuming an fp32 run with ``state_dtype="bfloat16"`` (or back) is
    a supported upgrade, not a reinterpretation."""
    if not saved:
        raise ValueError(
            "the checkpoint manifest carries no wire headers (it "
            "predates the versioned wire format, or was saved without "
            "FedEngine.wire_headers) — cannot prove the comm/EF "
            "layouts match; re-save the checkpoint with this build")
    problems = []
    for stream in sorted(set(saved) | set(current)):
        if stream not in saved:
            problems.append(
                f"stream {stream!r}: active now but the checkpoint has "
                f"no wire header for it (saved under a config without "
                f"this stream)")
            continue
        if stream not in current:
            problems.append(
                f"stream {stream!r}: present in the checkpoint but not "
                f"active under the current config")
            continue
        s, c = saved[stream], current[stream]
        for d, when in ((s, "save time"), (c, "now")):
            ver = int(d.get("version", 1))
            if ver not in SUPPORTED_WIRE_VERSIONS:
                problems.append(
                    f"stream {stream!r}: wire-format version {ver} "
                    f"({when}) is not supported by this build "
                    f"({SUPPORTED_WIRE_VERSIONS})")
        for field_ in ("compressor", "total", "quant_block", "aux"):
            if s.get(field_) != c.get(field_):
                problems.append(
                    f"stream {stream!r}: {field_} was "
                    f"{s.get(field_)!r} at save time but is "
                    f"{c.get(field_)!r} now")
    if problems:
        raise ValueError(
            "wire-layout mismatch between checkpoint and current comm "
            "config — restoring would misinterpret packed comm/EF "
            "state:\n  " + "\n  ".join(problems))


@dataclass(frozen=True)
class FlatSpec:
    """Static description of the packed layout (trace-time only)."""
    treedef: Any
    sizes: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    total: int                 # true element count (pre-padding)
    rows: int
    cols: int

    @property
    def padded(self) -> int:
        return self.rows * self.cols


def flat_spec(tree, cols: int = 1024) -> FlatSpec:
    """Build the layout spec from a (concrete or ShapeDtypeStruct) pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = tuple(int(l.size) for l in leaves)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    total = sum(sizes)
    rows = -(-total // cols)
    return FlatSpec(treedef, sizes, shapes, dtypes, total, rows, cols)


def aval_key(tree) -> Tuple:
    """Hashable fingerprint of a pytree's structure + leaf avals.

    Works on concrete arrays, tracers and ShapeDtypeStructs alike —
    the memoization key for spec/compressor caches (specs are pure
    static metadata, so one build serves every trace of the same
    abstract shape)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((tuple(l.shape), jnp.dtype(l.dtype).str)
                           for l in leaves))


def zeros(spec: FlatSpec, lead: Tuple[int, ...] = (),
          dtype=jnp.float32) -> jnp.ndarray:
    """A zeroed flat state buffer in ``spec``'s wire layout, with
    optional leading (e.g. per-client) axes.

    ``dtype`` is the STORAGE dtype of the buffer (resident engine
    state follows `CommConfig.state_dtype`); in-round compute always
    upcasts to fp32.  Zero is exactly representable in every supported
    dtype, and the pad tail is a fixed point of all engine ops, so the
    result is a valid wire buffer under any later `unpack`/`repack`.
    """
    return jnp.zeros(tuple(lead) + (spec.rows, spec.cols), dtype)


def _row_runs(spec: FlatSpec) -> Tuple[Tuple[int, int, int, int], ...]:
    """``(first leaf, end leaf, first row, rows)`` of each run of
    consecutive leaves whose flat span starts and ends on a row
    boundary (the last run ends at the pad).  Leaves a multiple of
    ``cols`` long are runs of their own, so `unpack_leaves` and
    `pack_leaves` move them as whole row blocks and never build the
    whole model as one flat vector."""
    runs, first, start, off = [], 0, 0, 0
    for i, sz in enumerate(spec.sizes):
        off += sz
        if off % spec.cols == 0 or i == len(spec.sizes) - 1:
            r0 = start // spec.cols
            runs.append((first, i + 1, r0, -(-off // spec.cols) - r0))
            first, start = i + 1, off
    return tuple(runs)


def unpack_leaves(flat: jnp.ndarray, spec: FlatSpec):
    """(..., rows, cols) buffer -> pytree of (..., *leaf_shape) leaves
    in the buffer's OWN dtype, leading (e.g. per-client) axes kept.

    The layout crossing of the local loops (`FedEngine._local_sophia_
    flat`): it runs once per client-round, and the loop then carries
    the leaves in their resident dtype.  Followed by `model_view`, it
    gives what `unpack` gives."""
    lead = flat.shape[:-2]
    out: List[jnp.ndarray] = []
    for i, j, r0, n in _row_runs(spec):
        v = flat[..., r0:r0 + n, :].reshape(lead + (-1,))
        off = 0
        for sz, shp in zip(spec.sizes[i:j], spec.shapes[i:j]):
            out.append(v[..., off:off + sz].reshape(lead + shp))
            off += sz
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def pack_leaves(tree, spec: FlatSpec) -> jnp.ndarray:
    """Inverse of `unpack_leaves`: leaves of one dtype, with the same
    leading axes, -> (..., rows, cols) buffer in that dtype (zero pad
    at the tail): `pack` without its fp32 detour, so equal to
    `pack(tree, spec, dtype)` wherever that dtype holds the leaves
    exactly."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    l0 = leaves[0]
    lead = l0.shape[:l0.ndim - len(spec.shapes[0])]
    blocks = []
    for i, j, r0, n in _row_runs(spec):
        v = jnp.concatenate([l.reshape(lead + (-1,)) for l in leaves[i:j]],
                            axis=-1)
        pad = [(0, 0)] * len(lead) + [(0, n * spec.cols - v.shape[-1])]
        blocks.append(jnp.pad(v, pad).reshape(lead + (n, spec.cols)))
    return jnp.concatenate(blocks, axis=-2)


def pack(tree, spec: FlatSpec, dtype=jnp.float32) -> jnp.ndarray:
    """pytree -> (rows, cols) wire buffer (zero pad at the tail).

    Leaves are flattened via fp32 (the canonical wire precision) and
    the buffer is stored as ``dtype`` — fp32 by default, or a narrower
    resident format (bf16, fp8 e4m3/e5m2) when the caller keeps
    resident state per `CommConfig.state_dtype` / `moment_dtype` /
    `hessian_dtype` (a value-rounding, layout-preserving cast)."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    v = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return jnp.pad(v, (0, spec.padded - spec.total)).reshape(
        spec.rows, spec.cols).astype(dtype)


def unpack(flat: jnp.ndarray, spec: FlatSpec):
    """(rows, cols) buffer -> pytree with the original shapes/dtypes.

    The returned leaves are *views-then-casts* of ``flat``: for fp32
    models this is bit-exact round-tripping of `pack`; a narrower
    resident buffer (bf16, fp8) upcasts losslessly (every supported
    storage format ⊂ fp32)."""
    v = flat.reshape(-1)[:spec.total]
    out: List[jnp.ndarray] = []
    off = 0
    for sz, shp, dt in zip(spec.sizes, spec.shapes, spec.dtypes):
        out.append(v[off:off + sz].reshape(shp).astype(dt))
        off += sz
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def model_view(tree, spec: FlatSpec):
    """Each leaf of a leaf-layout tree (any resident dtype) cast to the
    model's own dtype; a no-op where the two agree."""
    return jax.tree_util.tree_unflatten(spec.treedef, [
        l.astype(dt) for l, dt in
        zip(jax.tree_util.tree_leaves(tree), spec.dtypes)])


def repack(flat: jnp.ndarray, from_spec: FlatSpec,
           to_spec: FlatSpec) -> jnp.ndarray:
    """Re-lay a packed buffer from one stream's (rows, cols) geometry
    into another's (same flattened coordinates, different quant_block;
    the pad tail is re-zeroed; the storage dtype is preserved).
    Matching geometries return the buffer — the SAME array object, not
    a copy — engine state keeps its pad tail at zero invariantly, so
    same-geometry repacks need no ops in the traced graph.  Callers
    must treat the result as aliasing the input (see the
    donation-safety contract in the module docstring)."""
    if from_spec.total != to_spec.total:
        raise ValueError(
            f"repack between incompatible specs: total "
            f"{from_spec.total} vs {to_spec.total}")
    if (from_spec.rows, from_spec.cols) == (to_spec.rows, to_spec.cols):
        return flat
    v = flat.reshape(-1)[:from_spec.total]
    return jnp.pad(v, (0, to_spec.padded - to_spec.total)).reshape(
        to_spec.rows, to_spec.cols)

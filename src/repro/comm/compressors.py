"""Stream compressors over the packed (rows, cols) fp32 wire buffer.

One compressor family serves every named stream of the round (uplink
model delta, downlink broadcast delta, hessian-EMA — `repro.configs.
base.COMM_STREAMS`): build one with `make_stream_compressor(comm,
stream, spec)`, which resolves the per-stream compressor choice via
``CommConfig.stream(name)``.

Each compressor is a pure function pair ``encode -> payload`` /
``decode -> reconstruction``, plus two fused engine entry points —
``roundtrip`` (decode(encode(x)) on an existing buffer) and
``encode_delta`` (the whole uplink chain over wire-layout state:
delta-code vs the received model, EF correction, round-trip, new
residual).  Both lower to the pure-JAX composition by default, or to
the fused Pallas kernels from `repro.kernels.quantize` when
``CommConfig.use_pallas`` is set; both paths consume the same
`jax.random` noise, so they agree to float rounding.  ``serialize``
renders a payload to its canonical little-endian wire bytes (the
normative layout in docs/wire-format.md, frozen by the golden tests).

Everything but ``serialize`` is vmap/scan-compatible: the engine calls
``roundtrip`` once per client under either execution strategy.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import accounting
from repro.comm import flat as cflat
from repro.comm.flat import FlatSpec
from repro.configs.base import CommConfig


Payload = Dict[str, jnp.ndarray]

# compressors whose reconstruction is a biased estimator of the input —
# these need error feedback to converge; the unbiased quantizers do not
BIASED = frozenset({"topk", "signsgd"})


def wants_error_feedback(comm: CommConfig) -> bool:
    """Whether the engine should materialise per-client EF residuals.

    ``error_feedback="auto"`` (the default) enables EF exactly for the
    biased compressors — unbiased int8/int4 would otherwise pay C full
    fp32 model copies of HBM for a variance reduction they don't need.
    """
    if comm.lossless:
        return False
    if comm.error_feedback == "auto":
        return comm.compressor in BIASED
    return bool(comm.error_feedback)


def participation_mask(key, num_clients: int,
                       num_participants: int) -> jnp.ndarray:
    """Seeded, jit-compatible uniform sample of S of C clients.

    permutation(arange(C)) assigns each client a distinct uniform rank;
    rank < S selects exactly S clients. Returns a float32 0/1 mask (C,).
    """
    ranks = jax.random.permutation(key, num_clients)
    return (ranks < num_participants).astype(jnp.float32)


def participation_indices(key, num_clients: int,
                          num_participants: int) -> jnp.ndarray:
    """The same sample as `participation_mask`, as S sorted client ids —
    the gather form, so the engine trains only the participants."""
    ranks = jax.random.permutation(key, num_clients)
    return jnp.sort(jnp.argsort(ranks)[:num_participants])


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base: lossless identity (the wire carries the raw fp32 delta)."""
    cfg: CommConfig
    spec: FlatSpec

    # -- wire format ----------------------------------------------------
    def encode(self, key, flat) -> Payload:
        del key
        return {"x": flat}

    def decode(self, payload: Payload) -> jnp.ndarray:
        return payload["x"]

    def header(self) -> cflat.Header:
        """The versioned 24-byte wire header of this stream's payloads
        (docs/wire-format.md): layout fingerprint a decoder validates
        before touching the body.  ``state_dtype`` records the storage
        dtype of resident state kept under this stream's layout (EF
        residuals, replicas) — the payload bytes themselves are always
        compressor-dtyped."""
        return cflat.Header(compressor=self.cfg.compressor,
                            total=self.spec.total,
                            quant_block=self.spec.cols,
                            state_dtype=self.cfg.state_dtype)

    def serialize(self, payload: Payload) -> bytes:
        """Canonical little-endian wire bytes of ONE payload (host-side,
        normative layout: docs/wire-format.md): the versioned header
        followed by the body.  The zero pad tail of the packed buffer
        is never transmitted; ``len(serialize(p))`` must equal
        `accounting.wire_bytes` for this compressor."""
        return self.header().pack() + self._body(payload)

    def _body(self, payload: Payload) -> bytes:
        x = np.asarray(payload["x"], dtype="<f4").reshape(-1)
        return x[: self.spec.total].tobytes()

    def stat(self, payload: Payload) -> jnp.ndarray:
        """Scalar the server aggregates alongside the decoded delta
        (signsgd majority vote needs the mean client scale)."""
        del payload
        return jnp.zeros((), jnp.float32)

    # -- engine entry points --------------------------------------------
    def roundtrip(self, key, flat) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """decode(encode(flat)) plus the aggregation stat, fused where a
        Pallas kernel exists."""
        payload = self.encode(key, flat)
        return self.decode(payload), self.stat(payload)

    def encode_delta(self, key, theta, start, ef):
        """One client's full uplink encode over wire-layout buffers:
        delta = (theta - start) [+ ef] -> round-trip -> new residual.

        The flat-resident engine's uplink entry point (`FedEngine.
        comm_client_step`): the delta never exists as a pytree.
        Returns ``(xhat, stat, new_ef)`` with ``new_ef=None`` when EF
        is off; `StochasticQuant` fuses the whole chain into one
        Pallas pass when ``use_pallas`` is set.
        """
        delta = theta - start
        if ef is not None:
            delta = delta + ef
        xhat, stat = self.roundtrip(key, delta)
        return xhat, stat, (None if ef is None else delta - xhat)

    def roundtrip_batched(self, keys, flat):
        """`roundtrip` over a packed (N, rows, cols) client stack;
        keys: the N per-client rng keys.  Returns ``(xhat, stat)``
        with a leading client axis.  Default: vmap of the per-client
        path (graph-identical to looping); the kernel-backed
        subclasses override with ONE client-batched Pallas launch,
        bitwise equal to the loop (tests/test_kernel_conformance.py).
        """
        return jax.vmap(self.roundtrip)(keys, flat)

    def encode_delta_batched(self, keys, theta, start, ef):
        """`encode_delta` over (N, rows, cols) client stacks in one
        pass.  ``start`` may stay (rows, cols) when every client
        trained from the same broadcast model (downlink replicas
        off); ``ef=None`` means EF is off for the whole cohort.
        Returns ``(xhat, stat, new_ef)`` stacked along clients."""
        start_ax = None if start.ndim == 2 else 0
        return jax.vmap(self.encode_delta,
                        in_axes=(0, 0, start_ax, 0))(keys, theta,
                                                     start, ef)

    def server_combine(self, agg, wstat):
        """Hook applied to the participation-weighted mean of decoded
        deltas (wstat = weighted mean of per-client stats)."""
        del wstat
        return agg


@dataclasses.dataclass(frozen=True)
class StochasticQuant(Compressor):
    """int8/int4 stochastic quantization, one fp32 scale per packed row.

    scale = max|row| / qmax, q = floor(x/scale + u), u ~ U[0,1):
    E[q * scale] = x, so the compressor is unbiased (up to the clip of
    the single max-magnitude coordinate).  int4 codes are simulated in
    an int8 container; byte accounting charges 4 bits (see
    repro.comm.accounting).
    """
    bits: int = 8

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1

    def _scales(self, flat):
        return jnp.max(jnp.abs(flat), axis=1, keepdims=True) / self.qmax

    def encode(self, key, flat) -> Payload:
        scale = self._scales(flat)
        safe = jnp.where(scale > 0, scale, 1.0)
        u = jax.random.uniform(key, flat.shape)
        q = jnp.clip(jnp.floor(flat / safe + u), -self.qmax, self.qmax)
        return {"q": q.astype(jnp.int8), "scale": scale}

    def decode(self, payload: Payload) -> jnp.ndarray:
        return payload["q"].astype(jnp.float32) * payload["scale"]

    def _body(self, payload: Payload) -> bytes:
        # [codes][group scales]; int4 packs two two's-complement
        # nibbles per byte (even coordinate in the low nibble)
        q = np.asarray(payload["q"], np.int8).reshape(-1)[: self.spec.total]
        scales = np.asarray(payload["scale"], dtype="<f4").reshape(-1)
        if self.bits == 8:
            codes = q.tobytes()
        else:
            nib = (q.astype(np.uint8) & 0xF)
            if nib.size % 2:
                nib = np.append(nib, np.uint8(0))
            codes = (nib[0::2] | (nib[1::2] << 4)).tobytes()
        return codes + scales.tobytes()

    def roundtrip(self, key, flat):
        if not self.cfg.use_pallas:
            return super().roundtrip(key, flat)
        from repro.kernels.quantize import quant_roundtrip_flat
        u = jax.random.uniform(key, flat.shape)
        xhat = quant_roundtrip_flat(flat, u, self._scales(flat),
                                    qmax=self.qmax)
        return xhat, jnp.zeros((), jnp.float32)

    def encode_delta(self, key, theta, start, ef):
        # EF off (the "auto" default for unbiased quantizers): the base
        # delta + `roundtrip` composition is already optimal — it
        # dispatches to the fused quant kernel under use_pallas without
        # streaming a zeros EF buffer or materializing a second delta
        if not self.cfg.use_pallas or ef is None:
            return super().encode_delta(key, theta, start, ef)
        # fused Pallas path: delta-code + EF + quant round-trip +
        # residual in one HBM pass (the scales need one reduction
        # over the corrected delta first) — the uplink twin of the
        # downlink `broadcast_roundtrip_flat`
        from repro.kernels.quantize import uplink_roundtrip_flat
        delta = theta - start + ef
        u = jax.random.uniform(key, delta.shape)
        xhat, resid = uplink_roundtrip_flat(
            theta, start, ef, u, self._scales(delta), qmax=self.qmax)
        return xhat, jnp.zeros((), jnp.float32), resid

    def roundtrip_batched(self, keys, flat):
        if not self.cfg.use_pallas:
            return super().roundtrip_batched(keys, flat)
        # ONE launch over the (N, R, C) stack; per-client noise/scales
        # match the vmapped per-client path exactly
        from repro.kernels.quantize import quant_roundtrip_batched
        u = jax.vmap(lambda k: jax.random.uniform(k, flat.shape[1:]))(keys)
        xhat = quant_roundtrip_batched(flat, u,
                                       jax.vmap(self._scales)(flat),
                                       qmax=self.qmax)
        return xhat, jnp.zeros((flat.shape[0],), jnp.float32)

    def encode_delta_batched(self, keys, theta, start, ef):
        if not self.cfg.use_pallas:
            return super().encode_delta_batched(keys, theta, start, ef)
        if ef is None:
            # EF off (the "auto" default for unbiased quantizers, and
            # the gated uplink-int8 bench regime): delta-code then the
            # batched quant kernel — a shared 2D start broadcasts
            delta = theta - start
            xhat, stat = self.roundtrip_batched(keys, delta)
            return xhat, stat, None
        # fused: delta + EF + quant round-trip + residual, one launch
        from repro.kernels.quantize import uplink_roundtrip_batched
        delta = theta - start + ef
        u = jax.vmap(lambda k: jax.random.uniform(k, theta.shape[1:]))(keys)
        xhat, resid = uplink_roundtrip_batched(
            theta, start, ef, u, jax.vmap(self._scales)(delta),
            qmax=self.qmax)
        return xhat, jnp.zeros((theta.shape[0],), jnp.float32), resid


@dataclasses.dataclass(frozen=True)
class TopK(Compressor):
    """Magnitude top-k sparsification (biased -> wants error feedback).

    Wire format: (int32 index, fp32 value) per surviving coordinate,
    k = ceil(topk_ratio * n_params).  The zero pad tail can never win a
    slot against any nonzero coordinate, but k is capped to the true
    element count anyway.
    """

    @property
    def k(self) -> int:
        return min(accounting.topk_k(self.cfg, self.spec.total),
                   self.spec.total)

    def encode(self, key, flat) -> Payload:
        del key
        v = flat.reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(v), self.k)
        return {"idx": idx.astype(jnp.int32), "val": v[idx]}

    def decode(self, payload: Payload) -> jnp.ndarray:
        n = self.spec.padded
        flat = jnp.zeros((n,), jnp.float32).at[payload["idx"]].set(
            payload["val"])
        return flat.reshape(self.spec.rows, self.spec.cols)

    def header(self) -> cflat.Header:
        return cflat.Header(compressor=self.cfg.compressor,
                            total=self.spec.total,
                            quant_block=self.spec.cols, aux=self.k,
                            state_dtype=self.cfg.state_dtype)

    def _body(self, payload: Payload) -> bytes:
        idx = np.asarray(payload["idx"], dtype="<i4")
        val = np.asarray(payload["val"], dtype="<f4")
        return idx.tobytes() + val.tobytes()

    def roundtrip(self, key, flat):
        if not self.cfg.use_pallas:
            return super().roundtrip(key, flat)
        from repro.kernels.quantize import topk_threshold_flat
        vals = jax.lax.top_k(jnp.abs(flat.reshape(-1)), self.k)[0]
        xhat = topk_threshold_flat(flat, vals[-1])
        return xhat, jnp.zeros((), jnp.float32)

    def roundtrip_batched(self, keys, flat):
        if not self.cfg.use_pallas:
            return super().roundtrip_batched(keys, flat)
        from repro.kernels.quantize import topk_threshold_batched
        vals = jax.vmap(
            lambda f: jax.lax.top_k(jnp.abs(f.reshape(-1)), self.k)[0]
        )(flat)
        xhat = topk_threshold_batched(flat, vals[:, -1])
        return xhat, jnp.zeros((flat.shape[0],), jnp.float32)


@dataclasses.dataclass(frozen=True)
class SignSGD(Compressor):
    """1-bit sign compression with a single fp32 magnitude scale.

    decode = scale * sign(x) with scale = mean|x| (EF-signSGD).  With
    ``sign_majority`` the server additionally takes the sign of the
    scale-weighted client vote and rescales by the mean client scale —
    the majority-vote rule of Bernstein et al., weighted by magnitude.
    """

    def _scale(self, flat):
        return jnp.sum(jnp.abs(flat)) / self.spec.total

    def encode(self, key, flat) -> Payload:
        del key
        return {"sign": jnp.sign(flat).astype(jnp.int8),
                "scale": self._scale(flat)}

    def decode(self, payload: Payload) -> jnp.ndarray:
        return (payload["sign"].astype(jnp.float32)
                * payload["scale"].astype(jnp.float32))

    def stat(self, payload: Payload) -> jnp.ndarray:
        return jnp.asarray(payload["scale"], jnp.float32)

    def _body(self, payload: Payload) -> bytes:
        # [packbits(x > 0), MSB-first][fp32 scale]; the wire bit cannot
        # carry sign(0) = 0, so exact zeros decode as -scale on a real
        # link (measure-zero for float deltas; the in-graph simulation
        # keeps them at 0 — see docs/wire-format.md)
        s = np.asarray(payload["sign"], np.int8).reshape(-1)[: self.spec.total]
        bits = np.packbits(s > 0).tobytes()
        scale = np.asarray(payload["scale"], dtype="<f4").reshape(1)
        return bits + scale.tobytes()

    def roundtrip(self, key, flat):
        if not self.cfg.use_pallas:
            return super().roundtrip(key, flat)
        from repro.kernels.quantize import sign_roundtrip_flat
        scale = self._scale(flat)
        xhat = sign_roundtrip_flat(flat, scale)
        return xhat, scale

    def roundtrip_batched(self, keys, flat):
        if not self.cfg.use_pallas:
            return super().roundtrip_batched(keys, flat)
        from repro.kernels.quantize import sign_roundtrip_batched
        scale = jax.vmap(self._scale)(flat)
        xhat = sign_roundtrip_batched(flat, scale)
        return xhat, scale

    def server_combine(self, agg, wstat):
        if not self.cfg.sign_majority:
            return agg
        return wstat * jnp.sign(agg)


def make_compressor(comm: CommConfig, spec: FlatSpec) -> Compressor:
    c = comm.compressor
    if c == "identity":
        return Compressor(comm, spec)
    if c in ("int8", "int4"):
        return StochasticQuant(comm, spec, bits=int(c[3:]))
    if c == "topk":
        return TopK(comm, spec)
    if c == "signsgd":
        return SignSGD(comm, spec)
    raise ValueError(f"unknown compressor {c!r}")


def make_stream_compressor(comm: CommConfig, stream: str,
                           spec: FlatSpec) -> Compressor:
    """Compressor for one named stream of the round (`COMM_STREAMS`)."""
    return make_compressor(comm.stream(stream), spec)

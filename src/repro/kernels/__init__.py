"""Fused Pallas TPU kernels for the engine's HBM-bound hot paths.

Every kernel consumes the packed ``(rows, cols)`` wire layout of
`repro.comm.flat` directly — the flat-resident engine hands them state
that is *already* in their layout, so the kernel path performs zero
pytree<->flat conversion (gated by ``make bench-engine-smoke``):

* `sophia_update.sophia_update_flat` — the fused Sophia local
  iteration (m-EMA, gated h-EMA, decay, clip, step) over theta/m/h/g;
  the engine's local loop launches it on each model leaf, viewed 2-D
  (`sophia_update_leaf`).
* `quantize.quant_roundtrip_flat` / `uplink_roundtrip_flat` /
  `broadcast_roundtrip_flat` / `sign_roundtrip_flat` /
  `topk_threshold_flat` — the wire round-trips of the comm streams
  (delta-code + EF + stochastic quant + residual in one VMEM pass).
* `stale_accum.stale_accum_flat` — the scheduler's staleness-weighted
  buffered aggregation.
* `robust_agg.robust_agg_flat` — the sort-free trimmed-mean/clip
  robust combine of `repro.robust` over the (K, rows, cols) stack.
* `ref` — pure-jnp oracles with identical per-coordinate semantics
  (the equivalence targets in tests/test_kernels.py).

Dtype contract: resident state may be stored bf16
(`CommConfig.state_dtype="bfloat16"`).  Kernels and refs upcast loads
to fp32, compute in fp32, and store each output in that output's
declared dtype; noise/scales/weights are always fp32.  With fp32
inputs all casts are no-ops — the default path is bit-identical to
the pre-dtype kernels.

Donation-safety: the kernels allocate fresh outputs; in-place update
of the resident buffers happens one level up, where the jitted round
donates its state (`FedEngine.round_fn`) and XLA aliases these
outputs onto the donated inputs.  Kernel callers never need to think
about aliasing; round callers do (docs/architecture.md "Memory
layout: the life of a round").

Client batching: every wire/optimizer kernel also has a ``*_batched``
entry point that takes the packed (C, rows, cols) client stack and
runs it as ONE launch with a leading client grid dimension, instead
of C vmapped (rows, cols) launches.  The batched launches reuse the
same kernel bodies over 3D blocks, so batched == per-client bitwise
(pinned by tests/test_kernel_conformance.py).  Block shapes — the
client block included — come from the committed ``tuning.json`` via
`repro.kernels.tuning` (autotuned by tools/autotune_kernels.py, safe
defaults when absent).

This layer is OPTIONAL: add <name>.py + a ref oracle ONLY for compute
hot-spots that are demonstrably HBM- or compute-bound; everything
else belongs in plain jnp.
"""
import jax

# Pallas kernels execute in interpret mode everywhere but real TPUs,
# where they lower through Mosaic.
INTERPRET = jax.default_backend() != "tpu"


def interpret_mode(interpret=None) -> bool:
    """The ``interpret`` flag of a kernel launch: an explicit value
    wins, None follows the platform (`INTERPRET`).  Every wrapper
    defaults to None, so a caller that omits the argument never runs
    the interpreter on a TPU."""
    return INTERPRET if interpret is None else bool(interpret)

# The kernel registry: one name per fused kernel family, used as the
# key space of kernels/tuning.json (validated by tools/check_docs.py
# and `make autotune-check`) and swept by tools/autotune_kernels.py.
KERNELS = (
    "quant_roundtrip",
    "broadcast_roundtrip",
    "uplink_roundtrip",
    "sign_roundtrip",
    "topk_threshold",
    "sophia_update",
    "stale_accum",
    "robust_agg",
)

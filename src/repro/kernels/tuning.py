"""Trace-time block-size resolution for the Pallas kernels.

The kernels tile their (rows, cols) — and, for the client-batched
entry points, (clients, rows, cols) — operands into VMEM blocks.  The
best block shape is hardware- and size-dependent: on a real TPU it is
a VMEM-budget question; in interpret mode (CPU, this container) the
dominant cost is per-grid-step dispatch overhead, so bigger blocks
(fewer grid steps) win outright.

`tools/autotune_kernels.py` sweeps candidate blocks at the committed
benchmark sizes and writes the winners to ``tuning.json`` next to
this module.  Kernels consult it AT TRACE TIME through `blocks_for` /
`blocks_2d`; block shape never changes kernel *values* (every entry
point is elementwise per coordinate — pinned bitwise across
geometries by tests/test_kernel_conformance.py), only launch
geometry, so a stale or missing file is always safe.  One caveat for
WHOLE-PROGRAM bitwise comparisons: in interpret mode a different
grid restructures the surrounding jitted program, which can move
XLA:CPU's per-fusion FMA contraction and shift last-ulp results of
*other* ops in the same jit — tests that pin two differently
structured programs bitwise (tests/test_flat_engine.py) therefore
fix the geometry first.  Fallback behaviour:

* no ``tuning.json`` / unreadable / malformed entry -> the safe
  defaults below (``DEFAULT_BLOCK_R x DEFAULT_BLOCK_C`` tiles, one
  client per grid step — exactly the pre-tuning launch geometry);
* an entry larger than the operand -> clamped to the operand;
* keys are validated against `repro.kernels.KERNELS` by
  ``tools/check_docs.py`` and ``make autotune-check``.

The file format (versioned, committed at the repo root of the
package)::

    {"version": 1,
     "backend": "<what the entries were chosen for>",
     "entries": {"<kernel>": {"block_n": 8, "block_r": 256,
                              "block_c": 1024}, ...}}

``block_n`` batches the client axis of the batched launches (and the
K wire axis of ``stale_accum``); ``block_r``/``block_c`` tile the
packed wire buffer.

Entry keys carry optional specificity suffixes::

    <kernel>                       the dtype-agnostic default
    <kernel>@<dtype>               per-dtype geometry (operand dtype
                                   name, e.g. "bfloat16",
                                   "float8_e4m3fn")
    <kernel>@<dtype>@n<chunk>      per-dtype AND per-client-chunk-size
                                   geometry (the chunked large-C
                                   dispatch of SchedConfig.dispatch_chunk)

`blocks_for` resolves most-specific-first and falls back to the bare
kernel key.  (Before the suffixed keys existed, lookups keyed on the
kernel name alone, so mixed-dtype runs in one process reused whatever
geometry was committed for fp32 — the per-dtype winners recorded by
``tools/autotune_kernels.py --dtype`` were unreachable.)
"""
from __future__ import annotations

import functools
import json
import os
from typing import Dict, Optional, Tuple

import numpy as np

#: safe fallback tile (the historical fixed BLOCK_R/BLOCK_C)
DEFAULT_BLOCK_R = 256
DEFAULT_BLOCK_C = 1024
#: safe fallback client-axis block: one client per grid step — the
#: geometry the vmapped per-client launches always had
DEFAULT_BLOCK_N = 1

#: the committed tuning table (next to this module)
TUNING_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tuning.json")

_FIELDS = ("block_n", "block_r", "block_c")


def _valid_entry(e) -> bool:
    return (isinstance(e, dict)
            and all(isinstance(e.get(f, 1), int) and e.get(f, 1) >= 1
                    for f in _FIELDS))


@functools.lru_cache(maxsize=8)
def load_tuning(path: Optional[str] = None) -> Dict[str, Dict[str, int]]:
    """The committed tuning entries, `{}` on any read/parse problem
    (missing file, bad JSON, wrong version) — the kernels then run on
    the safe defaults.  Cached per process; block resolution happens
    at trace time only."""
    p = path or TUNING_PATH
    try:
        with open(p) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict) or data.get("version") != 1:
        return {}
    entries = data.get("entries")
    if not isinstance(entries, dict):
        return {}
    return {k: v for k, v in entries.items() if _valid_entry(v)}


def _dtype_name(dtype) -> Optional[str]:
    """Canonical dtype-suffix name of a tuning key (None when no dtype
    was supplied).  Goes through numpy — ml_dtypes registers the fp8
    and bf16 formats with it, so this module stays jax-free."""
    if dtype is None:
        return None
    return np.dtype(dtype).name


def _lookup(kernel: str, dtype, n: int) -> Dict[str, int]:
    """Most-specific-first entry resolution:
    ``<kernel>@<dtype>@n<n>`` -> ``<kernel>@<dtype>`` -> ``<kernel>``.
    Keying on the kernel name alone (the pre-suffix behaviour) made
    mixed-dtype runs reuse one geometry for every dtype and chunk
    size."""
    table = load_tuning()
    name = _dtype_name(dtype)
    if name is not None:
        for key in (f"{kernel}@{name}@n{int(n)}", f"{kernel}@{name}"):
            if key in table:
                return table[key]
    return table.get(kernel, {})


def tile(kernel: str, n: int = 1, dtype=None) -> Tuple[int, int, int]:
    """The (bn, br, bc) block ``kernel`` is tuned to for ``n`` clients
    and ``dtype``, before any clamping to an operand (the most
    specific committed entry, else the safe defaults)."""
    e = _lookup(kernel, dtype, n)
    return (e.get("block_n", DEFAULT_BLOCK_N),
            e.get("block_r", DEFAULT_BLOCK_R),
            e.get("block_c", DEFAULT_BLOCK_C))


def blocks_for(kernel: str, n: int, r: int, c: int,
               override: Optional[Tuple[int, int, int]] = None,
               dtype=None) -> Tuple[int, int, int]:
    """Resolve the (bn, br, bc) block of a batched launch over an
    (n, r, c) stack: the explicit ``override`` (the autotuner's sweep
    hook) wins, then the most specific committed ``tuning.json`` entry
    for (``kernel``, ``dtype``, client count ``n``), then the safe
    defaults; always clamped to the operand dims.  ``dtype`` is the
    primary operand's storage dtype (the resident state the kernel
    loads) — omit it to resolve the dtype-agnostic entry."""
    bn, br, bc = (override if override is not None
                  else tile(kernel, n, dtype=dtype))
    return (max(1, min(int(bn), n)), max(1, min(int(br), r)),
            max(1, min(int(bc), c)))


def blocks_2d(kernel: str, r: int, c: int,
              override: Optional[Tuple[int, int]] = None,
              dtype=None) -> Tuple[int, int]:
    """(br, bc) for an unbatched (r, c) launch of ``kernel`` — the 2D
    slice of the same tuning entry (per-dtype when ``dtype`` is
    given)."""
    if override is not None:
        br, bc = override
        return max(1, min(int(br), r)), max(1, min(int(bc), c))
    _, br, bc = blocks_for(kernel, 1, r, c, dtype=dtype)
    return br, bc

"""One module per architecture, found by the configuration file's ``arch``.

``bench/models/<arch>.py``, with ``-`` and ``.`` of ``arch`` turned into
``_`` (``minicpm-2b`` -> ``minicpm_2b.py``), holds everything of the
benchmark that knows the architecture; the rest of the harness is the
generic Fed-Sophia round.  A module defines:

* ``program_config(cfg)``: the program's `ModelConfig` for a
  configuration file, refusing a file that states what the program
  does not run;
* ``Model``, a frozen dataclass with ``Model.from_config(cfg)`` and a
  ``compute`` field (None, or the dtype every matrix product of the
  lower-precision control reads its operands in);
* ``param_shapes(m)``: the program's parameter tree, with a shape tuple
  at each leaf, whose pytree order is the wire's;
* ``init_params(key, m)``: the initial parameters as float32 values,
  drawn with the program's key schedule;
* ``logits_and_aux(p, m, tokens)``: ``(B, S, Vp)`` logits with the
  padded vocabulary rows masked, and the auxiliary loss the program
  adds to the cross-entropy (0.0 where it has none);
* ``train_flops_per_token(cfg, seq)``: the model FLOPs of one token's
  forward and backward pass, the work ``mfu`` credits;
* ``tiny(cfg)``: the configuration cut to a size a CPU test run holds.

The reference part (``Model`` to ``logits_and_aux``) imports nothing of
the program; ``program_config`` imports it when called.
"""
from __future__ import annotations

import hashlib
import importlib.util
import os
import sys
from typing import Any, Dict

_LOADED: Dict[str, Any] = {}


def load(arch: str, root: str):
    """The module of ``arch`` under the checkout ``root``, loaded once
    per path."""
    ident = arch.replace("-", "_").replace(".", "_")
    p = os.path.abspath(os.path.join(root, "bench", "models", ident + ".py"))
    if p not in _LOADED:
        if not os.path.isfile(p):
            raise KeyError(f"no model module for arch {arch!r}: {p}")
        name = "bench_model_" + hashlib.sha1(p.encode()).hexdigest()[:12]
        spec = importlib.util.spec_from_file_location(name, p)
        mod = importlib.util.module_from_spec(spec)
        # dataclasses look their module up while the class is made
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _LOADED[p] = mod
    return _LOADED[p]

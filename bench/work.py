"""The work the algorithm does, counted from shapes: model FLOPs of a
round (from the model module's FLOPs per token), and the bytes each
kernel launch moves, read from the operand and result shapes of the
compiled round's custom calls."""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))

#: bytes per element of the HLO element types a kernel can carry
ELEMENT_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                 "s16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1,
                 "pred": 1}
#: kernel family -> substrings of the custom call's instruction name
FAMILIES = {
    "sophia_update": ("sophia_update",),
    "quantize": ("quant_roundtrip", "broadcast_roundtrip",
                 "uplink_roundtrip", "sign_roundtrip", "topk_threshold"),
    "stale_accum": ("stale_accum",),
    "robust_agg": ("robust_agg",),
}
_SHAPE = re.compile(r"\b(" + "|".join(ELEMENT_BYTES) + r")\[([0-9,]*)\]")


def round_flops(per_token: float, traffic, rounds: int = 1,
                first_round: int = 0) -> float:
    """Model FLOPs of ``rounds`` synchronous rounds, at ``per_token``
    FLOPs for one token's forward and backward pass (the model module's
    ``train_flops_per_token``): every client's local steps, plus a
    forward and backward pass for each curvature refresh (every
    ``tau``-th local step).  Recomputation is not counted."""
    J, tau = traffic["local_iters"], traffic["tau"]
    passes = 0
    for r in range(first_round, first_round + rounds):
        passes += J + sum((r * J + j) % tau == 0 for j in range(J))
    tokens = traffic["clients"] * traffic["batch"] * traffic["seq"]
    return passes * tokens * per_token


def shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ELEMENT_BYTES[dt]
    return total


def kernel_calls(hlo_text: str) -> List[Dict]:
    """Every Pallas custom call of a compiled module: its instruction
    name, family, and the bytes of its operands and results."""
    out = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(",
                     line)
        ops = re.search(r"operand_layout_constraints=\{(.*?)\}\s*,\s*\w+=",
                        line)
        if not m or not ops:
            continue
        name = m.group(1)
        family = next((f for f, keys in FAMILIES.items()
                       if any(k in name for k in keys)), None)
        out.append({"name": name, "family": family,
                    "bytes": shape_bytes(m.group(2))
                    + shape_bytes(ops.group(1))})
    return out


def peaks(device_kind: str) -> Dict:
    """The published peaks of a device kind; an unknown kind raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: "
                       f"{sorted(table['devices'])})") from None

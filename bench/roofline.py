"""The yardstick's arithmetic: published chip peaks, a kernel call's bytes
from its operand shapes, and the model's training FLOPs.

Peaks are keyed by ``jax.Device.device_kind``; a device that is not in the
table is an error, never a default.  No kernel is held to a roofline: the
compiler places the aggregation kernels' operands in on-chip memory
(layout ``S(1)``), and no bandwidth or vector rate of that memory is
published, so the only published bounds (HBM bandwidth, the bf16 matrix
peak) are not the ones these kernels meet.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Sequence, Tuple

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, TPU v5e",
        "flops_per_s": 197e12,          # bf16
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}

_DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8}
# dtype[dims]{layout}
_SHAPE = re.compile(
    r"\b(f32|s32|u32|bf16|f16|s8|u8|pred|f64|s64)\[([0-9,]*)\](\{[^}]*\})?")


def peaks(device_kind: str) -> Dict[str, object]:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


Shape = Tuple[str, str, str]


def _shape_bytes(shape: Shape) -> int:
    dtype, dims, _ = shape
    n = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
    return n * _DTYPE_BYTES[dtype]


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    raise ValueError("unbalanced parentheses in HLO text")


def call_shapes(hlo: str) -> Tuple[List[Shape], List[Shape]]:
    """(results, operands) as (dtype, dims, layout) of one HLO custom-call
    instruction.  Operand types are read from the call's argument list when
    it carries them, else from ``operand_layout_constraints``."""
    eq = hlo.index(" = ")
    call = hlo.index("custom-call(", eq)
    results = _SHAPE.findall(hlo[eq:call])
    close = _balanced(hlo, call + len("custom-call"))
    operands = _SHAPE.findall(hlo[call:close])
    if not operands:
        m = re.search(r"operand_layout_constraints=\{", hlo)
        if m is None:
            raise ValueError("custom-call carries no operand shapes")
        end = hlo.index("}}", m.end()) + 1
        operands = _SHAPE.findall(hlo[m.end():end])
    return results, operands


def call_bytes(hlo: str) -> int:
    """Bytes of every operand and result of one kernel call, at the padded
    shapes the call was given, wherever the compiler placed them."""
    results, operands = call_shapes(hlo)
    return sum(_shape_bytes(s) for s in results + operands)


def calls_gb_per_s(calls: Sequence[Tuple[float, str]]) -> float:
    """GB/s over kernel calls given as (device ns, instruction text): their
    summed bytes over their summed time."""
    return (sum(call_bytes(hlo) for _, hlo in calls)
            / sum(d for d, _ in calls))


def mlp_train_flops_per_sample(layers: Sequence[int]) -> int:
    """Matrix-multiply FLOPs of one sample's forward and backward pass
    through a dense stack with widths ``layers`` (input first).  Forward:
    2 in*out per layer.  Backward: 2 in*out for each weight gradient, plus
    2 in*out for each activation gradient except the input layer's, which
    no one needs.  Elementwise work is not counted."""
    pairs = list(zip(layers[:-1], layers[1:]))
    fwd = sum(2 * i * o for i, o in pairs)
    wgrad = fwd
    agrad = sum(2 * i * o for i, o in pairs[1:])
    return fwd + wgrad + agrad

"""Bytes a kernel must move, from the shapes of its operands and result.

A Pallas kernel appears in the device trace as one HLO custom call,
``%name = u32[16384,128]{1,0:T(8,128)} custom-call(u32[16384,128]{...}
%x, u32[507904,128]{...} %masks), custom_call_target="tpu_custom_call"``.
Its least HBM traffic is each operand read once and its result written
once, counting only the arrays that live in HBM: a layout marked
``S(1)`` places the array in the core's on-chip memory (VMEM), which
the HBM roofline does not bound.
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\](\{[^{}]*\})?")
_CALL = re.compile(r"^%?[\w.-]+ = (?P<result>.+?) custom-call\((?P<operands>.*?)\), "
                   r'.*custom_call_target="tpu_custom_call"')


def _arrays(text: str):
    """``(bytes, in_hbm)`` of every array type in ``text``."""
    for dtype, dims, layout in _ARRAY.findall(text):
        count = 1
        for d in filter(None, dims.split(",")):
            count *= int(d)
        yield count * _DTYPE_BYTES[dtype], "S(" not in (layout or "")


def pallas_call(hlo: str):
    """``(result elements, HBM bytes)`` of a Pallas kernel's instruction,
    or None for any other instruction."""
    m = _CALL.match(hlo)
    if not m:
        return None
    result = list(_arrays(m.group("result")))
    elements = sum(b for b, _ in result)
    dtype_bytes = _DTYPE_BYTES[_ARRAY.search(m.group("result")).group(1)]
    moved = sum(b for b, hbm in result + list(_arrays(m.group("operands"))) if hbm)
    return elements // dtype_bytes, moved


def roofline_share(events, peak_bytes_per_s: float) -> float | None:
    """Percent of the HBM roofline: the least time of the kernels' bytes at
    peak bandwidth over their measured device time.  ``events`` are
    ``(hlo, seconds)`` of one kernel's calls; None when there are none."""
    moved = seconds = 0.0
    for hlo, dt in events:
        moved += pallas_call(hlo)[1]
        seconds += dt
    if seconds <= 0:
        return None
    return 100.0 * moved / peak_bytes_per_s / seconds

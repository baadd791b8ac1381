"""Counts from the compiled step's HLO text: exact, the same on every run."""

import re

_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_OP = re.compile(r"=\s+(\(.*?\)|\S+)\s+all-reduce(?:-start)?\(")
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")


def all_reduces(hlo_text):
    """[(bytes, shape string)] of every all-reduce the compiler kept."""
    found = []
    for line in hlo_text.splitlines():
        m = _OP.search(line)
        if not m:
            continue
        total = 0
        for dtype, dims in _SHAPE.findall(m.group(1)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * _BYTES[dtype]
        found.append((total, m.group(1)))
    return found

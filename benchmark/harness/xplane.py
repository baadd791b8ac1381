"""The reduction from the profiler's ``.xplane.pb`` to numbers, with
nothing but jax (``jax.profiler.ProfileData``).

What a v5e trace holds (read by hand from a recorded one, PR 23): one
plane ``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA
Modules``, ``XLA Ops`` (every HLO instruction the TensorCore ran, named by
its HLO text ``%name = shape opcode(...)``; a ``while`` spans the ops of
its body, so events nest) and ``Async XLA Ops`` (copies and slices in
flight); and ``/host:CPU`` with one line per thread, where the benchmark
loop's ``bench_feed`` / ``bench_dispatch`` / ``bench_wait`` annotations
sit on the same clock.  Pallas kernels are the events whose text has
``custom_call_target="tpu_custom_call"`` (they carry no name of their
own today).

An event carries no ``op_name`` (its stats are ``device_offset_ps`` and
``device_duration_ps`` only): the program's scopes are in the compiled
text, under the same instruction names, and ``harness/scopes`` joins the
two.  Names are unique within a module, not across the programs of a
run, so ``Reduced.instructions`` is kept by the module that the event
lies in on ``XLA Modules`` (``jit_step(<fingerprint>)``: ``jit_step``).
"""

import bisect
import glob
import os
import re

from jax.profiler import ProfileData

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_CONTAINERS = ("while", "conditional", "call")
HOST_SPANS = ("bench_feed", "bench_dispatch", "bench_wait")


def _instruction(event_name):
    """'%fusion.12 = bf16[..] fusion(...)' -> ('fusion.12', 'fusion')."""
    head, _, rest = event_name.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-_]*)\(", " " + rest)
    return head.lstrip("%"), (m.group(1) if m else "")


def _union(intervals):
    """Sorted, merged (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(merged):
    return sum(e - s for s, e in merged)


def _overlap(a, b):
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _self_times(events):
    """[(name, opcode, self_ns)]: an event's time less what its children
    on the same line cover."""
    out, stack = [], []            # stack of [end, index into out]
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= dur
        inst, opcode = _instruction(name)
        out.append([inst, opcode, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def _is_all_reduce(name):
    return _instruction(name)[1].startswith("all-reduce")


class Reduced:
    """Seconds, averaged over the chips used."""

    def __init__(self):
        self.busy_s = self.window_s = 0.0
        self.kernel_s = 0.0
        self.collective_s = self.collective_exposed_s = None
        self.device_ops, self.idle_gaps = [], []
        # first chip: {module: {instruction: [opcode, self seconds, calls]}}
        self.instructions = {}

    def breakdown(self):
        return {"device_ops": self.device_ops[:10], "idle_gaps": self.idle_gaps[:10]}


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return [(e.start_ns, e.duration_ns, e.name) for e in line.events]
    return []


def reduce_profile(profile, chips):
    planes = sorted(((int(_DEVICE.match(p.name).group(1)), p)
                     for p in profile.planes if _DEVICE.match(p.name)),
                    key=lambda t: t[0])[:chips]
    if not planes:
        return None
    host = [(s, s + d, n) for p in profile.planes if p.name == "/host:CPU"
            for line in p.lines for s, d, n in
            ((e.start_ns, e.duration_ns, e.name) for e in line.events)
            if n in HOST_SPANS]
    r = Reduced()
    collective = exposed = 0.0
    saw_collective = False
    by_kind, gaps = {}, {}
    for index, (_, plane) in enumerate(planes):
        ops = _line(plane, "XLA Ops")
        if not ops:
            return None
        modules = _line(plane, "XLA Modules") or ops
        lo = min(s for s, _, _ in modules)
        hi = max(s + d for s, d, _ in modules)
        busy = _union((s, s + d) for s, d, _ in ops)
        r.busy_s += _length(busy) / 1e9
        r.window_s += (hi - lo) / 1e9
        r.kernel_s += sum(d for _, d, n in ops if "tpu_custom_call" in n) / 1e9
        reduces = _union(
            (s, s + d) for s, d, n in ops + _line(plane, "Async XLA Ops")
            if _is_all_reduce(n))
        if reduces:
            saw_collective = True
            others = _union(
                (s, s + d) for s, d, n in ops if not _is_all_reduce(n)
                and _instruction(n)[1] not in _CONTAINERS)
            collective += _length(reduces) / 1e9
            exposed += (_length(reduces) - _overlap(reduces, others)) / 1e9
        if index:
            continue               # the breakdown is of the first chip
        spans = sorted((s, s + d, re.sub(r"\(\d+\)$", "", n))
                       for s, d, n in _line(plane, "XLA Modules"))
        ops.sort(key=lambda e: (e[0], -e[1]))       # as _self_times orders them
        for (start, _, _), (inst, opcode, self_ns) in zip(ops, _self_times(ops)):
            kind = re.sub(r"[.\d]+$", "", inst)
            if "tpu_custom_call" in opcode or opcode == "custom-call":
                kind += " (custom-call)"
            by_kind[kind] = by_kind.get(kind, 0.0) + self_ns / 1e9
            i = bisect.bisect_right(spans, (start, float("inf"))) - 1
            module = spans[i][2] if i >= 0 and start < spans[i][1] else ""
            kept = r.instructions.setdefault(module, {}).setdefault(
                inst, [opcode, 0.0, 0])
            kept[1] += self_ns / 1e9
            kept[2] += 1
        edges = [lo] + [t for pair in busy for t in pair] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0.0
            for h0, h1, name in host:
                part = min(g1, h1) - max(g0, h0)
                if part > 0:
                    gaps[name] = gaps.get(name, 0.0) + part / 1e9
                    covered += part
            rest = (g1 - g0) - covered
            if rest > 0:
                gaps["between spans"] = gaps.get("between spans", 0.0) + rest / 1e9
    n = len(planes)
    r.busy_s, r.window_s, r.kernel_s = r.busy_s / n, r.window_s / n, r.kernel_s / n
    if saw_collective:
        r.collective_s, r.collective_exposed_s = collective / n, exposed / n
    r.device_ops = [[k, v] for k, v in sorted(by_kind.items(), key=lambda kv: -kv[1])]
    r.idle_gaps = [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])]
    return r


def reduce_dir(path, chips):
    """The newest trace under ``path`` (where jax.profiler wrote it)."""
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return None
    return reduce_profile(ProfileData.from_file(files[-1]), chips)

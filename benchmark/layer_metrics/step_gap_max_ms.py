"""Longest interval between the starts of two consecutive ``step`` spans
of the main window.  The five longest go on an earlier line, each with the
later call's ``n``, how long the earlier call's jitted call held the host
(``held``: a gap spent inside the call is the runtime's, one spent outside
it the loop's), the steps in flight when the later call began and the
fewest over the ``window.IN_FLIGHT`` calls after it (``in_flight 5 then
1``: the device kept its queue through the gap and worked it off before
the host was back; ``0``: it had run dry), the collector's milliseconds
inside the gap and the functions jax compiled inside it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Train-step assembly", "step_ms.p95", "program_span"

import heapq

from harness import steploop, window

LONGEST = 5


def read(ctx):
    w = steploop.window(ctx)
    if w is None or len(w.steps) < 2:
        return None
    gaps = [(w.steps[i + 1]["t0"] - w.steps[i]["t0"], i)
            for i in range(len(w.steps) - 1)]
    compiled = steploop.compiles()
    parts = []
    for gap, i in heapq.nlargest(LONGEST, gaps):
        a, b = w.steps[i], w.steps[i + 1]
        lo, hi = a["t0"], b["t0"]
        after = [s["args"]["in_flight"]
                 for s in w.steps[i + 1:i + 2 + window.IN_FLIGHT]]
        names = sorted({s["name"] for s in compiled
                        if s["t1"] > lo and s["t0"] < hi})
        parts.append(
            f"n={b['args']['n']} {gap * 1e3:.3f} ms held "
            f"{(a['t1'] - lo) * 1e3:.3f} in_flight {after[0]} then "
            f"{min(after)} gc {steploop.inside(w.gc, lo, hi) * 1e3:.3f} ms "
            "compiled " + (",".join(names) or "nothing"))
    ctx.say(f"step gaps, longest of {len(gaps)}: " + "; ".join(parts))
    return max(gaps)[0] * 1e3

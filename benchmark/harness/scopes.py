"""Device time by the program's own scopes: every instruction's self time
in the traced stretch (``xplane.Reduced.instructions``, the step's module)
joined with the (scope, pass) that the compiled text gives the same
instruction (``hlo.scopes``).  Readers ask ``ms(ctx, scope, passes)``; the
table is made once a run and says itself on two lines, which are what
builders made by hand one instruction at a time before PR 35."""

import re
import time

from harness import hlo

_MODULE = re.compile(r"HloModule\s+([\w.\-]+)")
SHOWN = 0.001                   # a row under this share of the step is not said
LARGEST = 20


class Table:
    """Seconds of the traced stretch on the first chip.  ``rows`` is
    ``{(scope, pass): seconds}`` with the unnamed instructions under
    ``("", "")``; it adds up to ``total_s``.  ``mixed`` is the part of the
    named rows that fusions spanning several (scope, pass) took, by name,
    and ``spans`` the same by what they span."""

    def __init__(self, instructions, where, steps):
        self.steps, self.rows, self.mixed, self.spans = steps, {}, {}, {}
        self.largest = []       # (seconds, name, opcode, scope, pass, mixed, shape, calls)
        for name, (opcode, seconds, calls) in instructions.items():
            scope, pass_, mixed, shape = where.get(name, ("", "", "", ""))
            self.rows[scope, pass_] = self.rows.get((scope, pass_), 0.0) + seconds
            if mixed and scope:
                self.mixed[name] = seconds
                self.spans[mixed] = self.spans.get(mixed, 0.0) + seconds
            self.largest.append((seconds, name, opcode, scope, pass_, mixed,
                                 shape, calls))
        self.largest.sort(reverse=True)
        self.total_s = sum(self.rows.values())
        self.unattributed_s = self.rows.get(("", ""), 0.0)
        self.mixed_s = sum(self.mixed.values())

    def seconds(self, scope=None, passes=None):
        """Time under ``scope`` (a component of the row's scope, so
        ``hvd_moe_experts`` holds its kernels' too; any named scope where
        None) in ``passes`` (all where None)."""
        return sum(s for (sc, p), s in self.rows.items()
                   if sc and (scope is None or scope in sc.split("/"))
                   and (passes is None or p in passes))

    def lines(self):
        ms = 1e3 / self.steps
        shown = lambda d: sorted(((s, k) for k, s in d.items()
                                  if s >= SHOWN * self.total_s), reverse=True)
        rows = {f"{sc} {p}": s for (sc, p), s in self.rows.items() if sc}
        by_pass = {p: self.seconds(passes=(p,)) for p in hlo.PASSES}
        said = lambda pairs: ", ".join(f"{k} {s * ms:.3f}" for s, k in pairs)
        yield ("scopes, ms a step: "
               + "; ".join(f"{k} {s * ms:.3f}" for s, k in shown(rows))
               + f"; unattributed {self.unattributed_s * ms:.3f}; by pass: "
               + ", ".join(f"{p} {s * ms:.3f}" for p, s in by_pass.items() if s)
               + f"; all {self.total_s * ms:.3f}; of the named, in mixed "
               f"fusions {self.mixed_s * ms:.3f}"
               + "".join(f", {w}" for w in [said(shown(self.spans)[:4])] if w)
               + "".join(f": {w}" for w in [said(shown(self.mixed)[:8])] if w))
        yield (f"instructions, ms a step, the {LARGEST} largest: " + "; ".join(
            f"{name} {opcode} {scope or '-'}/{pass_ or '-'}"
            f"{' mixed' if mixed else ''} {s * ms:.3f} in "
            f"{calls / self.steps:g} "
            f"{shape if len(shape) <= 64 else shape[:61] + '...'}"
            for s, name, opcode, scope, pass_, mixed, shape, calls
            in self.largest[:LARGEST]))


def table(ctx):
    """The run's table, made at the first asking and kept on ``ctx``; None
    without a trace, or where the trace holds no module of the text's
    name."""
    if not hasattr(ctx, "scope_table"):
        ctx.scope_table = None
        kept = getattr(ctx.trace, "instructions", None)
        t0 = time.perf_counter()
        if kept:
            text = ctx.hlo_text()
            module = _MODULE.match(text)
            kept = kept.get(module.group(1)) if module else None
        if kept:
            ctx.scope_table = Table(kept, hlo.scopes(text),
                                    len(ctx.traced.stamps))
            for line in ctx.scope_table.lines():
                ctx.say(line)
            ctx.say(f"scope table: {len(kept)} instructions against "
                    f"{len(text)} bytes of text in "
                    f"{time.perf_counter() - t0:.2f} s")
    return ctx.scope_table


def ms(ctx, scope=None, passes=None):
    """Device ms a step under ``scope`` in ``passes``; None where the
    trace has nothing there (a parent without the scope, a CPU rehearsal)."""
    t = table(ctx)
    if t is None:
        return None
    seconds = t.seconds(scope, passes)
    return seconds / t.steps * 1e3 if seconds else None

"""Counts from the compiled step's HLO text: exact, the same on every run."""

import collections
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


# ---- the program's scopes, from each instruction's op_name ---------------
# What the compiled text of a v5e step holds (read off the SDAR, Phi and
# ResNet steps compiled for a described v5e, and BERT's on the chip, PR
# 35): every instruction jax emitted carries
# ``metadata={op_name="jit(step)/<name stack>/<primitive>"}``.  A
# ``jax.named_scope`` is a component of the stack (``hvd_forward``), and so
# is a Pallas kernel's ``name=`` (``.../hvd_flash_dkv/pallas_call``).
# Autodiff wraps the stack, ``jvp(hvd_forward)`` and
# ``transpose(jvp(hvd_forward))`` where the scope was opened inside the
# differentiated function, ``hvd_forward/jvp()`` and
# ``hvd_forward/transpose(jvp())`` where outside.  ``jax.checkpoint`` adds
# ``checkpoint`` in the forward pass and in the backward, and
# ``checkpoint/rematted_computation`` around the forward run again there.
# XLA's passes glue names: ``a;b`` where it merged two instructions, and
# ``<call site>/<callee's whole name>`` where it inlined a call, so the
# stack's first component can come again in the middle; the first stretch
# is then where the instruction runs.  Copies, buffers and some fusions
# that XLA made itself carry no name.

PASSES = ("forward", "recompute", "backward", "optimizer", "reduce")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(\(.*?\)|\S+)\s+([a-z][a-z0-9\-_]*)\(")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_SCOPE = re.compile(r"\bhvd_\w+")
# what moves, casts or names data and computes nothing of its own
_MOVES = frozenset((
    "parameter", "constant", "iota", "broadcast", "bitcast", "reshape",
    "transpose", "copy", "convert", "tuple", "get-tuple-element", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad", "reverse",
    "bitcast-convert"))
# what a fusion's time is for, beside the writing of its outputs
_HEAVY = frozenset((
    "convolution", "dot", "reduce", "reduce-window", "select-and-scatter",
    "scatter", "gather", "sort", "custom-call"))
_OPERAND = re.compile(r"%([\w.\-]+)")
_OWN_PASS = {"hvd_optimizer": "optimizer", "hvd_reduce": "reduce"}
# one parsed line: ``where`` is classify()'s pair, None without an op_name
_Parsed = collections.namedtuple("_Parsed", "where shape opcode operands")


def classify(op_name):
    """``(scope, pass)`` of one op_name: ``scope`` the ``hvd_`` components
    from the outermost in, joined by ``/`` (``hvd_forward/hvd_moe_experts``;
    ``""`` without any), ``pass`` one of PASSES."""
    parts = op_name.split(";")[0].split("/")
    if parts[0] in parts[1:]:                   # an inlined call: its site
        parts = parts[:parts.index(parts[0], 1)]
    here = "/".join(parts)
    chain = list(dict.fromkeys(_SCOPE.findall(here)))
    if not chain:
        return "", ""
    if chain[0] in _OWN_PASS:
        return "/".join(chain), _OWN_PASS[chain[0]]
    again = here.rfind("rematted_computation")
    if "transpose(" in here[max(again, 0):]:
        return "/".join(chain), "backward"
    return "/".join(chain), "recompute" if again >= 0 else "forward"


def scopes(hlo_text):
    """``{instruction: (scope, pass, mixed, shape)}`` for every instruction
    of every computation of the module.  A fusion takes its own op_name
    (without one, what most of the instructions it calls carry).  ``mixed``
    is ``""`` or, where what decides its time lies in more than one (scope,
    pass), their names (``"hvd_forward backward + hvd_optimizer
    optimizer"``): its products, reductions, scatters, sorts and kernels
    (``_HEAVY``) and whatever computes each of its outputs.  ResNet's
    ``multiply_add_fusion`` is: a weight gradient's convolution (backward)
    whose outputs are the momentum and the parameter (optimizer).  An
    elementwise producer that XLA pulled in from another pass (remat's
    ``exp`` before a backward product) rides on the fusion's own reads and
    does not make it so, nor does a constant or a cast."""
    found, members, calls, roots = {}, {}, {}, {}
    inside = computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
                inside = members.setdefault(computation, [])
            continue
        name, shape, opcode = m.groups()
        op_name = _OP_NAME.search(line)
        found[name] = _Parsed(
            classify(op_name.group(1)) if op_name else None, shape, opcode,
            _OPERAND.findall(line[m.end():].split(")", 1)[0]))
        if inside is not None:
            inside.append(name)
            if line.lstrip().startswith("ROOT"):
                roots[computation] = name
        if opcode == "fusion":
            calls[name] = _CALLS.search(line).group(1)

    def anchors(computation, seen, root=None):
        """The (scope, pass) of what decides a fused computation's time."""
        outputs = [root] if root in found else []
        if outputs and found[root].opcode == "tuple":
            outputs = found[root].operands
        for name in outputs:        # back through casts to what computes it
            while (name in found and found[name].opcode in _MOVES
                   and found[name].operands):
                at = found[name]
                # a buffer updated in place: what is written into it
                name = at.operands[at.opcode == "dynamic-update-slice"
                                   and len(at.operands) > 1]
            if name in found and found[name].opcode not in _MOVES:
                seen.add(found[name].where)
        for name in members.get(computation, ()):
            if name in calls:
                anchors(calls[name], seen)
            elif found[name].opcode in _HEAVY:
                seen.add(found[name].where)
        return seen - {None}

    def named(computation, works, moves):
        """The named (scope, pass) under a fused computation, counted: of
        the instructions that compute, and of those that only move."""
        for name in members.get(computation, ()):
            if name in calls:
                named(calls[name], works, moves)
            elif found[name].where is not None:
                seen = moves if found[name].opcode in _MOVES else works
                seen[found[name].where] = seen.get(found[name].where, 0) + 1
        return works or moves

    out = {}
    for name, at in found.items():
        own, mixed = at.where, ""
        if name in calls:
            seen = anchors(calls[name], set(), roots.get(calls[name]))
            if len(seen) > 1:
                mixed = " + ".join(sorted(" ".join(w).strip() or "unnamed"
                                          for w in seen))
            if own is None:
                seen = named(calls[name], {}, {})
                own = max(seen, key=seen.get) if seen else None
        out[name] = (own or ("", "")) + (mixed, at.shape)
    return out

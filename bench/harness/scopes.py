"""The phases of a round, read from the named scopes the program sets.

The program wraps the parts of its fused round in ``jax.named_scope``s
(``SCOPE_GRAD``, ``SCOPE_LOCAL_STEP`` and ``SCOPE_GOSSIP`` in
``repro.core.pdsgdm``) and its attention sub-layers in others
(``ATTENTION_SCOPES`` in ``repro.models.transformer``); ``vocabulary``
reads those names from the program, so a program without them (an older
build) gets no table and the readers built on it read nothing.  The names
reach the compiled HLO's ``metadata={op_name=...}``, which the reduced
trace does not hold: it has only op names.  ``table(cell)`` compiles the
cell's ``train_round`` once more (the compile cache that the run enabled
hands back the executable that ran) and maps each of its instructions to
its opcode and op_name.  ``phase`` puts one op in exactly one phase, and
``ms_per_round`` sums the in-window time of the ops a predicate picks.  An
op whose op_name holds no scope (the round loop's own copies and
bookkeeping) is ``other``.  The window also holds the feed program's few
ops: those the table lacks are ``other``; one whose name the round's
program also uses is read as the round's (tens of microseconds per round
on the chip).

Autodiff wraps scope names in the name stack: a forward op reads
``.../grad/vmap(jvp())/.../attn/...``, a backward op
``.../grad/vmap(transpose(jvp()))/.../checkpoint/attn/...``, the remat
policy's recompute ``.../checkpoint/rematted_computation/attn/...``.  A
scope is therefore matched as a path segment once such wrappers are taken
off.
"""
from __future__ import annotations

import collections
import functools
import json
import re
import time

from . import common, readers, trace

PHASES = ("xla_remat", "recompute", "bwd", "fwd", "update", "collective",
          "mix", "other")
# clones that XLA's rematerialization pass made to fit memory
XLA_REMAT = re.compile(r"\.remat\d*(\.\d+)?$")
COLLECTIVE_OPCODES = re.compile(
    r"^(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-broadcast|send|recv)(-start|-done)?$")

Vocabulary = collections.namedtuple(
    "Vocabulary", "grad local_step gossip attention")

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=(%?[\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=(%?[\w.\-]+)")
_NAME = re.compile(r"%[\w.\-]+")
_METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")
_STACK_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")

_TABLES: dict = {}


@functools.lru_cache(maxsize=None)
def vocabulary() -> Vocabulary | None:
    """The scope names the program sets; None where it sets none."""
    try:
        from repro.core.pdsgdm import (SCOPE_GOSSIP, SCOPE_GRAD,
                                       SCOPE_LOCAL_STEP)
        from repro.models.transformer import ATTENTION_SCOPES
    except ImportError:
        return None
    return Vocabulary(SCOPE_GRAD, SCOPE_LOCAL_STEP, SCOPE_GOSSIP,
                      frozenset(ATTENTION_SCOPES))


def instr_name(trace_name: str) -> str:
    """``%fusion.12`` from a trace op name (``%fusion.12 = f32[...] ...``)."""
    name = trace_name.split(" = ", 1)[0].strip()
    return name if name.startswith("%") else "%" + name


@functools.lru_cache(maxsize=None)
def segments(op_name: str) -> frozenset:
    """The scope names on an op_name's path, AD and call wrappers taken off:
    ``grad/vmap(transpose(jvp(attn)))`` gives ``grad`` and ``attn``."""
    out = set()
    for seg in op_name.split("/"):
        while True:
            m = _WRAPPED.match(seg)
            if not m:
                break
            seg = m.group(1)
        out.add(seg)
    return frozenset(out)


def _is_collective(trace_name: str, opcode: str) -> bool:
    return bool(COLLECTIVE_OPCODES.match(opcode)
                or re.search(readers.COLLECTIVE, trace_name))


def phase(trace_name: str, opcode: str, op_name: str) -> str:
    """The one phase of an op, by the first rule that holds."""
    if XLA_REMAT.search(instr_name(trace_name)):
        return "xla_remat"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "bwd"
    names, segs = vocabulary(), segments(op_name)
    # an attention op outside ``grad`` is work on no weight that tracing
    # hoisted out of the loss (the causal mask, the RoPE tables)
    if names.grad in segs or segs & names.attention:
        return "fwd"
    if names.local_step in segs:
        return "update"
    if names.gossip in segs:
        return "collective" if _is_collective(trace_name, opcode) else "mix"
    return "other"


def is_attention(trace_name: str, opcode: str, op_name: str) -> bool:
    """An op of an attention sub-layer (its pre-norm, projections, RoPE,
    softmax), in any pass; collectives left out."""
    return (not _is_collective(trace_name, opcode)
            and bool(segments(op_name) & vocabulary().attention))


def _scoped(op_name: str) -> bool:
    names = vocabulary()
    return bool(segments(op_name) & ({names.grad, names.local_step,
                                      names.gossip} | names.attention))


# ------------------------------------------------------------ the table
def parse(text: str) -> dict:
    """``{"%instr": (opcode, op_name)}`` for every instruction of an
    optimized HLO module that can run as an op (those of fused computations
    and reducers left out).  A fusion whose own op_name holds no scope takes
    its fused computation's: the root's, else the last fused instruction's
    that holds one."""
    comps: dict = {}   # computation -> [(name, opcode, op_name, rest)]
    comp = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            comp = None
            if line.rstrip().endswith("{") and (
                    line.startswith("%") or line.startswith("ENTRY ")):
                head = line[6:] if line.startswith("ENTRY ") else line
                comp = _pct(head.split(None, 1)[0])
                comps[comp] = []
            continue
        m = comp is not None and _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(" " + rest)
        meta = _OP_NAME.search(rest)
        comps[comp].append((_pct(m.group(1)), op.group(1) if op else "",
                            meta.group(1) if meta else "", rest))

    inner = set()
    for instrs in comps.values():
        for _n, opcode, _o, rest in instrs:
            for rx in (_TO_APPLY, _CALLS if opcode == "fusion" else None):
                m = rx.search(rest) if rx else None
                if m:
                    inner.add(_pct(m.group(1)))

    @functools.lru_cache(maxsize=None)
    def fused(comp_name: str) -> str:
        names = []
        for _n, opcode, op_name, rest in reversed(comps.get(comp_name, [])):
            m = _CALLS.search(rest) if opcode == "fusion" else None
            names.append(op_name or (fused(_pct(m.group(1))) if m else ""))
        return next((n for n in names if _scoped(n)), "")

    table = {}
    for comp_name, instrs in comps.items():
        if comp_name in inner:
            continue
        for n, opcode, op_name, rest in instrs:
            m = _CALLS.search(rest) if opcode == "fusion" else None
            if m and not _scoped(op_name):
                op_name = fused(_pct(m.group(1))) or op_name
            table[n] = (opcode, op_name)
    return table


def _pct(name: str) -> str:
    return name if name.startswith("%") else "%" + name


def stripped(text: str) -> str:
    """An optimized HLO module without its metadata and its stack-frame
    tables: what the chip runs."""
    out, skip = [], False
    for line in text.splitlines():
        if line in _STACK_TABLES:
            skip = True
        elif skip and (line.startswith("%") or line.startswith("ENTRY ")):
            skip = False
        if not skip:
            out.append(_METADATA.sub("", line))
    return "\n".join(out)


def renaming(src: str, dst: str) -> dict | None:
    """``{name in src: name in dst}`` where two optimized HLO modules differ,
    metadata aside, only in the names of their instructions and
    computations, one for one; None where they differ in more."""
    a, b = stripped(src), stripped(dst)
    if _NAME.split(a) != _NAME.split(b):
        return None
    out = {}
    for x, y in zip(_NAME.findall(a), _NAME.findall(b)):
        if out.setdefault(x, y) != y:
            return None
    return out if len(set(out.values())) == len(out) else None


def _struct(tree, shardings):
    import jax
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def lower(cell: dict, devices=None):
    """The cell's ``train_round`` lowered from the pack rebuilt on the
    cell's devices (the first of ``jax.devices()`` unless given), on shapes
    with the pack's shardings."""
    import jax

    from . import sharded
    if devices is None:
        devices = jax.devices()
    _run, _mesh, pack = sharded.build(cell, devices[:int(cell["chips"])])
    return pack.train_round.lower(
        _struct(pack.params_struct, pack.params_sharding),
        _struct(pack.state_struct, pack.state_sharding),
        _struct(pack.round_batch_struct, pack.round_batch_sharding))


def _compile_uncached(cell: dict) -> str:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lower(cell).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def build(cell: dict) -> dict | None:
    """The instruction table of the executable the run ran; None where the
    program sets no scopes, or where that executable cannot be matched.

    The compile cache's key leaves op metadata out, so a cache shared with
    a build of the program without scopes can hand back that build's
    executable, and the run then ran it.  Its names are what the trace
    holds, but its metadata names no scope: the round is then compiled
    again with the cache off, and the fresh table is used only where the
    two modules are the same computation up to the names of their
    instructions, under the names of the one that ran."""
    if vocabulary() is None:
        return None
    ran = lower(cell).compile().as_text()
    if any(_scoped(o) for o in _OP_NAME.findall(ran)):
        return parse(ran)
    common.log(f"{cell['name']}: the cached executable holds no scopes; "
               "compiling the round again with the cache off")
    fresh = _compile_uncached(cell)
    names = renaming(fresh, ran)
    if names is None:
        common.log(f"{cell['name']}: the executable that ran and the "
                   "scoped one differ beyond their names: no table")
        return None
    return {names.get(n, n): row for n, row in parse(fresh).items()}


def table(cell: dict):
    """The cell's instruction table, built once per process (``build``)."""
    key = json.dumps([cell["name"], cell["config_spec"],
                      cell["traffic_spec"]], sort_keys=True)
    if key not in _TABLES:
        t0 = time.perf_counter()
        _TABLES[key] = build(cell)
        common.log(f"{cell['name']}: scope table in "
                   f"{time.perf_counter() - t0:.2f} s: " + (
                       "none" if _TABLES[key] is None
                       else f"{len(_TABLES[key])} instructions"))
    return _TABLES[key]


def classify(ctx) -> list | None:
    """Per device, ``[(start, end, phase, attention)]`` of the ops inside
    the window (loops and calls left out); None without a table.  Ops the
    table lacks are ``other``, and logged with their time.  Kept in
    ``ctx``, since every reader of a run asks for the same."""
    if "scopes" in ctx:
        return ctx["scopes"]
    rows = table(ctx["cell"])
    if rows is None:
        ctx["scopes"] = None
        return None
    rec = ctx["trace"]
    lo, hi = rec["window"]
    out, missing, known = [], {}, {}
    for dev in rec["devices"]:
        ops = []
        for name, s, d in dev["ops"]:
            if trace.is_container(name):
                continue
            c = trace.clip([(s, s + d)], lo, hi)
            if not c:
                continue
            key = instr_name(name)
            if key not in known:
                opcode, op_name = rows.get(key, ("", ""))
                known[key] = (phase(name, opcode, op_name),
                              is_attention(name, opcode, op_name))
            if key not in rows:
                missing[key] = missing.get(key, 0.0) + c[0][1] - c[0][0]
            ops.append((c[0][0], c[0][1]) + known[key])
        out.append(ops)
    if missing:
        worst = sorted(missing.items(), key=lambda kv: -kv[1])[:5]
        common.log(f"{ctx['cell']['name']}: {len(missing)} trace ops not in "
                   f"the scope table, {sum(missing.values()) * 1e-6:.3f} ms "
                   f"in all; largest {worst}")
    ctx["scopes"] = out
    return out


def ms_per_round(ctx, pred) -> float | None:
    """The summed in-window time of the ops for which ``pred(phase,
    attention)`` holds, averaged over the chips, per round; None where the
    program sets no scopes."""
    per_dev = classify(ctx)
    if per_dev is None:
        return None
    total = sum(e - s for ops in per_dev for s, e, ph, att in ops
                if pred(ph, att))
    return readers.per_round_ms(total / len(per_dev), ctx)


def phase_ms(ctx, name: str) -> float | None:
    return ms_per_round(ctx, lambda ph, _att: ph == name)

"""Device time per round of the Mamba-2 mixer (ops under the model's
``ssm`` scope: its pre-norm, projections, conv and SSD core), in every
pass, collectives left out, averaged over the chips, in milliseconds;
nothing where the program sets no such scope."""
import re

from harness import readers, scopes, trace

UNIT = "ms"


def _ssm_scope():
    try:
        from repro.models.transformer import SSM_SCOPE
    except ImportError:
        return None
    return SSM_SCOPE


def read(ctx):
    name = _ssm_scope()
    rows = scopes.table(ctx["cell"]) if name else None
    if rows is None:
        return None
    rec = ctx["trace"]
    lo, hi = rec["window"]
    total = 0.0
    for dev in rec["devices"]:
        for op, s, d in dev["ops"]:
            if trace.is_container(op):
                continue
            opcode, op_name = rows.get(scopes.instr_name(op), ("", ""))
            collective = (scopes.COLLECTIVE_OPCODES.match(opcode)
                          or re.search(readers.COLLECTIVE, op))
            if name in scopes.segments(op_name) and not collective:
                total += sum(e - b for b, e in trace.clip([(s, s + d)],
                                                          lo, hi))
    return readers.per_round_ms(total / len(rec["devices"]), ctx)

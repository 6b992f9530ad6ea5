"""Which source scope each instruction of a compiled program came from, and
the share of a program's device time that a scope holds.

The trace names a device op by its HLO instruction alone; the compiled
program's text carries each instruction's ``metadata={op_name="..."}``,
the ``jax.named_scope`` path it was traced under (backward ops as
``transpose(jvp(<scope>))``).  A fusion that carries no metadata takes the
``op_name`` of its fused computation's ROOT instruction, or where that has
none either (a ROOT ``convert`` or ``tuple``) the last ``op_name`` inside
the fused computation."""

from __future__ import annotations

import re

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+)\s*=\s*\S+\s+([a-z][\w-]*)\(")
_TUPLE_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+)\s*=\s*\(.*?\)\s+([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} over every computation of a compiled
    program's text; ``""`` where neither the instruction nor (for a fusion)
    its fused computation carries one."""
    own: dict[str, str] = {}
    fused: dict[str, str] = {}  # fusion instruction -> called computation
    roots: dict[str, str] = {}  # computation -> its ROOT instruction
    last: dict[str, str] = {}  # computation -> its last op_name
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line) or _TUPLE_INSTR.match(line)
        if m is None or comp is None:
            continue
        root, name, opcode = m.groups()
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        if own[name]:
            last[comp] = own[name]
        if root:
            roots[comp] = name
        if opcode == "fusion":
            called = _CALLS.search(line)
            if called:
                fused[name] = called.group(1)

    def resolve(name: str, depth: int = 0) -> str:
        if own.get(name) or name not in fused or depth > 8:
            return own.get(name, "")
        comp = fused[name]
        root = roots.get(comp)
        return (resolve(root, depth + 1) if root else "") or last.get(comp, "")

    return {name: resolve(name) for name in own}


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` lies under ``scope`` (a ``/``-separated path
    prefix such as ``quant/``), at any depth of the path."""
    return re.search(r"(?:^|[/(])" + re.escape(scope), op_name) is not None


def share(ops: dict[str, list], scopes: dict[str, str],
          scope: str) -> tuple[float, float] | None:
    """(percent of the ops' device time under ``scope``, percent with no
    scope) from one program's ``{instruction: [calls, seconds]}`` (the
    trace's ``ops_by_kind``), or None where no instruction of the program
    lies under ``scope`` or the ops took no time.  Ops with no scope stay
    in the denominator."""
    if not any(in_scope(s, scope) for s in scopes.values()):
        return None
    total = inside = bare = 0.0
    for name, (_, secs) in ops.items():
        s = scopes.get(name, "")
        total += secs
        if not s:
            bare += secs
        elif in_scope(s, scope):
            inside += secs
    if total <= 0.0:
        return None
    return 100.0 * inside / total, 100.0 * bare / total


def train_step_text(cfg: dict, mix: dict) -> str:
    """The compiled text of the training loop's step program at the
    window's shapes (``bench.loops.train.make_step`` on one device): the
    same program the window ran, compiled again (a cache hit where the
    compile cache is on)."""
    import jax

    from bench.harness import program, traffic
    from bench.loops import train

    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    state = jax.eval_shape(lambda: program.train_state(0, cfg))
    batch = next(traffic.train_batches(mix, 0, cfg["vocab_size"]))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev),
        (state, batch))
    return train.make_step(cfg).lower(*shapes).compile().as_text()

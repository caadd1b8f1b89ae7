"""Device time of each named layer of the EF-BV step, from a profiler trace
and the compiled step's HLO text.

The program names the layers of its step with ``jax.named_scope``
(``efbv.fwd_bwd``, ``efbv.compress``, ``efbv.exchange``, ``efbv.decode``,
``efbv.optimizer``, ``efbv.step_metrics``, ``efbv.downlink``; see
``src/repro/train/trainer.py``).  XLA keeps the scope in each instruction's
``op_name``, but a TPU trace's op events carry only the instruction's name
and text, without its metadata; so the layer of a traced op is looked up by
its instruction name in the compiled step's ``as_text()``.

Attribution, in :func:`scope_map`:

- an instruction belongs to the innermost ``efbv.*`` segment of its
  ``op_name``;
- a fusion carries the ``op_name`` of its root instruction (XLA sets it so),
  and counts whole for that scope;
- an instruction with no scope inside a computation that a ``while``,
  ``conditional`` or ``call`` runs takes its caller's scope: it runs inside
  the caller's interval;
- an instruction with no ``op_name`` at all was made by the compiler (a
  layout copy, the TPU's sort-and-scatter expansion of a scatter-add): it
  takes the scope of its users where those that have one agree, so a chain
  of such instructions takes the scope of the layer that consumes it;
- any other instruction, and any traced op the map does not name (another
  program's), goes to ``other``.

A layer's time on a device (:func:`layer_seconds`) is the union of its ops'
intervals inside ``bench.window``, averaged over devices: a ``while``
encloses its body's ops, which carry the same scope and must not count
twice.
"""

from __future__ import annotations

import base64
import collections
import hashlib
import re
from typing import Dict, List, Optional, Tuple

from chipbench import trace as tr

UNSCOPED = "other"

_SCOPE = re.compile(r"efbv\.[A-Za-z_]+")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CONTROL = re.compile(r"\s(?:while|conditional|call)\(")
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)|"
                     r"\bbranch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_KERNEL = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name (without the ``%``) -> its layer's scope, for every
    instruction of a compiled program's ``as_text()`` that has one."""
    own: Dict[str, Optional[str]] = {}
    made: List[str] = []            # instructions with no op_name
    members: Dict[str, List[str]] = collections.defaultdict(list)
    callers: List[Tuple[str, List[str]]] = []
    refs: Dict[str, List[str]] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        body = _METADATA.sub("", line)
        op = _OP_NAME.search(line)
        found = _SCOPE.findall(op.group(1)) if op else []
        own[name] = found[-1] if found else None
        if op is None:
            made.append(name)
        members[comp].append(name)
        refs[name] = _REF.findall(body[m.end():])
        if _CONTROL.search(body):
            called = []
            for single, branches in _CALLED.findall(body):
                called += [single] if single else [
                    b.strip().lstrip("%") for b in branches.split(",")]
            callers.append((name, called))
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, operands in refs.items():
        for operand in operands:
            if operand in own and operand != name:
                users[operand].append(name)
    # repeat until nothing changes: nested loops' bodies and chains of
    # compiler-made instructions resolve one link per pass
    changed = True
    while changed:
        changed = False
        for name, called in callers:
            if own[name] is None:
                continue
            for c in called:
                for member in members.get(c, ()):
                    if own[member] is None:
                        own[member] = own[name]
                        changed = True
        for name in made:
            if own[name] is None:
                seen = {own[u] for u in users[name]} - {None}
                if len(seen) == 1:
                    own[name] = seen.pop()
                    changed = True
    return {k: v for k, v in own.items() if v is not None}


def layer_seconds(ops: Dict[str, List[tr.Event]], spans: List[tr.Event],
                  scopes: Dict[str, str]) -> Dict[str, float]:
    """Scope -> seconds inside the window, the union of its ops' intervals
    on each device, averaged over devices."""
    windows = [s for s in spans if s.name == tr.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW} span, found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    total: Dict[str, float] = collections.defaultdict(float)
    for dev in ops:
        by_scope: Dict[str, List[Tuple[float, float]]] = \
            collections.defaultdict(list)
        for a, b, e in tr._clip(ops[dev], lo, hi):
            by_scope[scopes.get(e.name.lstrip("%"), UNSCOPED)].append((a, b))
        for k, iv in by_scope.items():
            total[k] += sum(b - a for a, b in tr._union(iv))
    return {k: v / len(ops) for k, v in total.items()}


def _kernel_body(match: re.Match) -> str:
    """A Pallas kernel's body is a serialized Mosaic module that carries its
    own source locations (file paths, lines): stand in the digest of the
    module without them."""
    from jax._src.lib.mlir import ir, passmanager

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        passmanager.PassManager.parse(
            "builtin.module(strip-debuginfo)").run(module.operation)
        text = str(module)
    return f'"body":"sha256:{hashlib.sha256(text.encode()).hexdigest()}"'


def program_text(hlo_text: str) -> str:
    """A compiled program's text without its debug information: each
    instruction's ``metadata={...}``, the tables of source locations that
    the metadata points into, and the source locations inside each Pallas
    kernel's body.  Two programs that differ only in named scopes, source
    lines or the checkout's path give the same text."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in _DEBUG_TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(_KERNEL.sub(_kernel_body, _METADATA.sub("", line)))
    return "\n".join(out) + "\n"


def program_digest(hlo_text: str) -> str:
    return hashlib.sha256(program_text(hlo_text).encode()).hexdigest()

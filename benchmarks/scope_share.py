#!/usr/bin/env python3
"""Device time per ``engine.*`` / ``kernel.*`` named scope, from a device
trace's op self times and the compiled HLO text of the programs it ran.

A TPU trace's op events carry neither the scope nor the HLO module: each
event is named by its instruction's text, operands printed with their
types (``%fusion.8 = s32[65536]{...} fusion(s32[...] %bitcast.73, ...),
kind=kCustom, calls=%fused_computation.49``).  The scope lives in the
instruction's ``metadata={op_name=...}`` in the compiled HLO text
(``fn.lower(*args).compile().as_text()``).  An event is matched to an
instruction by its name and the ordered ``%`` references of its text
(operands, called computations), which tells apart instructions that
share a name across the programs of one run.  Where several programs hold
that key with different scopes the event's time is counted as ambiguous,
never guessed.

    python benchmarks/scope_share.py OPS.json PROGRAM.hlo.txt [...]

``OPS.json`` is a record with ``extra.all_ops``, a list of ``[[module,
hlo_op, event_name, ...], self_seconds]`` pairs, and ``extra.busy_s``.
Prints one JSON object: seconds and share of busy time per scope, and the
seconds matched, ambiguous and unmatched.
"""
from __future__ import annotations

import json
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

SCOPES = ("kernel.", "engine.push", "engine.pull", "engine.msg",
          "engine.update")
_REF = re.compile(r"%[\w.\-]+")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def _key(text: str) -> Tuple[str, ...]:
    """(name, references...) of one instruction's text, HLO line or event."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    name, _, rest = text.partition(" = ")
    for cut in (", metadata=", ", backend_config="):
        rest = rest.split(cut)[0]
    return (name,) + tuple(_REF.findall(rest))


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` on an op_name path, or ``other``."""
    found = "other"
    for part in op_name.split("/"):
        for sc in SCOPES:
            if part.startswith(sc):
                found = sc
    return found


def instruction_scopes(hlo_texts: Iterable[str]) -> Dict[Tuple[str, ...], set]:
    """{instruction key: {scope, ...}} over every program's HLO text."""
    out: Dict[Tuple[str, ...], set] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            s = line.strip()
            if not s.startswith(("%", "ROOT %")) or " = " not in s:
                continue
            m = _OP_NAME.search(s)
            out.setdefault(_key(s), set()).add(
                scope_of(m.group(1)) if m else "other")
    return out


def scope_share(ops: List[Tuple[str, float]], hlo_texts: Iterable[str],
                busy_s: Optional[float] = None) -> dict:
    """Seconds per scope of ``(event_name, self_seconds)`` ops, and their
    share of ``busy_s`` (default: the ops' summed self time)."""
    table = instruction_scopes(hlo_texts)
    per: Dict[str, float] = {}
    ambiguous = unmatched = 0.0
    for name, secs in ops:
        scopes = table.get(_key(name))
        if scopes is None:
            unmatched += secs
        elif len(scopes) > 1:
            ambiguous += secs
        else:
            sc, = scopes
            per[sc] = per.get(sc, 0.0) + secs
    busy = busy_s if busy_s is not None else sum(s for _, s in ops)
    return {
        "busy_s": busy,
        "scope_s": per,
        "scope_share_pct": {k: 100.0 * v / busy for k, v in per.items()}
        if busy else {},
        "matched_s": sum(per.values()),
        "ambiguous_s": ambiguous,
        "unmatched_s": unmatched,
    }


def _load_ops(path: str) -> Tuple[List[Tuple[str, float]], float]:
    with open(path) as f:
        extra = json.load(f)["extra"]
    return ([(str(k[2]), float(s)) for k, s in extra["all_ops"]],
            extra["busy_s"])


def main(argv: List[str]) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    ops, busy = _load_ops(argv[0])
    texts = []
    for p in argv[1:]:
        with open(p) as f:
            texts.append(f.read())
    print(json.dumps(scope_share(ops, texts, busy), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

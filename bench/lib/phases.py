"""Device time by phase of the program: the ``sim.*`` named scopes
(``repro.core.engine.PHASES``) that the compiled program's ``op_name``
metadata carries. ``phase_map`` maps each HLO instruction to its phase
and ``split`` divides a traced window's busy time, the one
``xtrace.reduce`` finds, by phase. ``bench/phase_split.py`` applies them
to one cell's traced call.
"""
from __future__ import annotations

import re

import xtrace

# the program's phase scopes, a segment of an instruction's op_name
SCOPE = re.compile(r"(?<![\w.])sim\.\w+")
HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
HLO_FUSION = re.compile(r"\sfusion\(.*\bcalls=%([^\s,]+)")
HLO_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%[^\s,]+|\bbranch_computations=\{[^}]*\}")
HLO_NAME = re.compile(r"%([^\s,}]+)")


def phase_map(hlo_text: str) -> dict:
    """{instruction name: phase} of a compiled program's HLO text.

    An instruction's phase is the innermost ``sim.*`` segment of the
    ``op_name`` in its metadata; XLA gives a fusion its root's, so a
    fusion that crosses a phase boundary counts in its root's phase.
    Where XLA left an instruction without one, it takes the phase of the
    instruction that calls its computation (all that a phase's loop runs
    is that phase), and else, for a fusion, the one phase its own
    instructions name. Instructions with no phase are left out."""
    own, where, fused, members, caller = {}, {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = HLO_COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name, text = m.groups()
        op = HLO_OP_NAME.search(text)
        scopes = SCOPE.findall(op.group(1)) if op else []
        own[name] = scopes[-1] if scopes else None
        where[name] = computation
        members.setdefault(computation, []).append(name)
        call = HLO_FUSION.search(text)
        if call:
            fused[name] = call.group(1)
        for called in HLO_CALLED.finditer(text):
            for c in HLO_NAME.findall(called.group(0)):
                caller.setdefault(c, name)

    def inner_phases(comp) -> set:
        out = set()
        for n in members.get(comp, ()):
            if own[n]:
                out.add(own[n])
            elif n in fused:
                out |= inner_phases(fused[n])
        return out

    memo = {}

    def phase(name):
        if name in memo:
            return memo[name]
        memo[name] = None                    # a call cycle finds nothing
        p = own[name]
        if p is None and where[name] in caller:
            p = phase(caller[where[name]])
        if p is None and name in fused:
            inner = inner_phases(fused[name])
            p = inner.pop() if len(inner) == 1 else None
        memo[name] = p
        return p

    return {n: p for n in own if (p := phase(n))}


def covered(intervals) -> float:
    """Seconds covered by the union of (start_ns, end_ns) intervals."""
    return sum(e - s for s, e in xtrace.union(
        intervals, float("-inf"), float("inf"))) / 1e9


def split(events: dict, window: str, phases: dict, top: int = 10) -> dict:
    """Busy time of the traced window (the host span named ``window``,
    taken as ``xtrace.reduce`` takes it) by phase. Each chip gets
    ``busy_s``, ``phases`` ({phase: seconds} for every phase of the map,
    each the union of its ops' intervals in the window) and
    ``unscoped_s`` (the same for the ops of no phase); ``unscoped_ops``
    are the ops of no phase that took most time, mean over chips. Where
    ops of two phases overlap, the phases add up to more than busy."""
    wins = [(s, e) for n, s, e in events["spans"] if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    lo, hi = wins[-1]
    chips, unscoped = [], {}
    for ops in events["chips"]:
        by_phase = {p: [] for p in set(phases.values())}
        rest, busy = [], []
        for name, s, e in ops:
            if not lo <= s < hi:
                continue
            p = phases.get(name)
            (by_phase[p] if p else rest).append((s, min(e, hi)))
            busy.append((s, min(e, hi)))
            if not p:
                unscoped[name] = unscoped.get(name, 0) + min(e, hi) - s
        chips.append({"busy_s": covered(busy),
                      "phases": {p: covered(iv)
                                 for p, iv in sorted(by_phase.items())},
                      "unscoped_s": covered(rest)})
    n = max(len(chips), 1)
    return {"chips": chips,
            "unscoped_ops": [[k, v / n / 1e9] for k, v in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:top]]}

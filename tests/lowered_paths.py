"""Shared by ``test_scopes.py`` and ``test_dsl_scopes.py``: where the ops of a
lowered module sit in the name stack."""

import re


def op_paths(text, ops):
    """The name-stack path of every op of the kinds ``ops`` (substrings of a
    line: ``"stablehlo.dot_general"``) in a lowered module
    (``as_text(debug_info=True)``). A function lowered once and called
    (``closed_call``: a scan's body, an inner jit) names its ops relative to
    itself, and XLA joins the call's name on when it inlines: so does this,
    once for each place the function is called from."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls, found, func = {}, [], None
    for line in text.splitlines():
        m = re.search(r"func\.func (?:\w+ )?@([\w.]+)\(", line)
        if m:
            func = m.group(1)
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        name = locs.get(at.group(1), "") if at else ""
        m = re.search(r"\bcall @([\w.]+)\(", line)
        if m:
            calls.setdefault(m.group(1), []).append((func, name))
        elif any(op in line for op in ops):
            found.append((func, name))

    def paths(func, name):
        sites = calls.get(func)
        if not sites:
            return [name]
        return [p + "/" + name for f, n in sites for p in paths(f, n)]

    return [p for func, name in found for p in paths(func, name)]

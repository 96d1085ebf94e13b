"""The benchmark's own reader and rewriter of ``Section { key = value }``
input files (the subset the ex4 inputs use). The plain reference reads
its sizes through this, so it shares no parser with the program."""

from __future__ import annotations

import re

_SECTION = re.compile(r"(?ms)^\s*(\w+)\s*\{(.*?)^\s*\}")
_KEYVAL = re.compile(r"(?m)^\s*(\w+)\s*=\s*(.*?)\s*$")


def _strip_comments(text: str) -> str:
    return re.sub(r"//[^\n]*", "", text)


def _value(raw: str):
    parts = [p.strip() for p in raw.split(",")]
    out = []
    for p in parts:
        if p.startswith('"') and p.endswith('"'):
            out.append(p[1:-1])
        elif p in ("TRUE", "FALSE"):
            out.append(p == "TRUE")
        else:
            try:
                out.append(int(p))
            except ValueError:
                out.append(float(p))
    return out[0] if len(out) == 1 else out


def parse(text: str) -> dict:
    """``{section: {key: value}}``; arrays become lists."""
    db = {}
    for name, body in _SECTION.findall(_strip_comments(text)):
        db[name] = {k: _value(v) for k, v in _KEYVAL.findall(body)}
    return db


def _format(val) -> str:
    if isinstance(val, bool):
        return "TRUE" if val else "FALSE"
    if isinstance(val, str):
        return f'"{val}"'
    if isinstance(val, (list, tuple)):
        return ", ".join(_format(v) for v in val)
    return repr(val)


def set_keys(text: str, keys: dict) -> str:
    """Return ``text`` with ``keys = {section: {key: value}}`` set: a key
    that is there is rewritten in place, one that is not is added to its
    section, and a section that is not there is appended."""
    for section, kv in keys.items():
        m = re.search(rf"(?ms)^(\s*{section}\s*\{{)(.*?)(^\s*\}})", text)
        if m is None:
            body = "".join(f"   {k} = {_format(v)}\n" for k, v in kv.items())
            text += f"\n{section} {{\n{body}}}\n"
            continue
        body = m.group(2)
        for k, v in kv.items():
            line = rf"(?m)^(\s*{k}\s*=).*$"
            if re.search(line, body):
                body = re.sub(line, lambda mm: f"{mm.group(1)} {_format(v)}",
                              body)
            else:
                body = body.rstrip("\n") + f"\n   {k} = {_format(v)}\n"
        text = text[:m.start(2)] + body + text[m.end(2):]
    return text

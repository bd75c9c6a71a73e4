#!/usr/bin/env python
"""Doc-consistency gate: README / DESIGN_* must not drift from the code.

The serving CLI and the design docs are maintained by hand, in
different files, by different PRs — the classic recipe for a README
that advertises a flag ``det_serve`` no longer has.  This gate makes
two narrow promises, checked statically on every CI run (the ``lint``
job, next to reprolint):

1. **Every ``--flag`` the docs attribute to ``det_serve`` exists** in
   ``src/repro/launch/det_serve.py``'s argparse.  "Attribute to" means
   the flag appears in a code span that also mentions ``det_serve`` —
   an inline backtick span, or one logical shell command inside a
   fenced block (backslash continuations joined).  Flags of *other*
   tools (``bench/run.py --workload``, reprolint's ``--json``,
   ``perf_serve --smoke``) live in spans without ``det_serve`` and are
   deliberately out of scope: this is a drift gate, not a universal
   flag registry.
2. **Every ``[[NAME]]`` cross-reference resolves** to ``NAME.md`` at
   the repo root.  The docs link each other with this wiki-style form
   (see README's architecture map); a rename that orphans a reference
   fails here instead of 404ing a reader.

Design constraints, same as reprolint (DESIGN_LINT.md): stdlib only
(the lint job runs before any wheel install), pure static analysis
(``ast`` for the argparse surface — never importing det_serve, which
would drag in jax), findings rendered ``file:line: message`` with a
non-zero exit.

Usage: ``python tools/check_docs.py [--root DIR]``
(``--root`` exists so the negative-path tests can point the gate at a
fixture tree instead of the live repo).
"""

import argparse
import ast
import re
import sys
from pathlib import Path

_FLAG_RE = re.compile(r"--[a-z][a-z0-9-]*")
_XREF_RE = re.compile(r"\[\[([A-Za-z0-9_]+)\]\]")
_SPAN_RE = re.compile(r"`([^`]+)`")

DET_SERVE_REL = Path("src") / "repro" / "launch" / "det_serve.py"


def argparse_flags(path: Path) -> set:
    """All ``--flag`` names det_serve's argparse accepts, via pure AST.

    Collects string constants starting with ``--`` in positional args
    of any ``*.add_argument(...)`` call — no import, no jax.
    """
    tree = ast.parse(path.read_text(), filename=str(path))
    flags = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        for arg in node.args:
            if (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value.startswith("--")):
                flags.add(arg.value)
    return flags


def iter_code_spans(text: str):
    """Yield ``(lineno, span_text)`` for every checkable code span.

    Outside fenced blocks: each inline ``\\`...`\\``` span, one per
    match.  Inside fenced blocks: one span per *logical command* —
    consecutive lines joined while they end with a backslash — so a
    wrapped ``det_serve`` invocation is judged as a whole and a
    ``pytest`` line sharing the block is not dragged into scope.
    """
    in_fence = False
    pending = []
    pending_line = 0
    for lineno, raw in enumerate(text.splitlines(), 1):
        stripped = raw.strip()
        if stripped.startswith("```"):
            if in_fence and pending:       # unterminated continuation
                yield pending_line, " ".join(pending)
                pending = []
            in_fence = not in_fence
            continue
        if in_fence:
            if not pending:
                pending_line = lineno
            pending.append(stripped.rstrip("\\").strip())
            if not stripped.endswith("\\"):
                yield pending_line, " ".join(pending)
                pending = []
        else:
            for m in _SPAN_RE.finditer(raw):
                yield lineno, m.group(1)
    if pending:                            # file ended mid-continuation
        yield pending_line, " ".join(pending)


def check_docs(root: Path) -> tuple:
    """Return ``(findings, stats)`` for the doc tree under ``root``."""
    findings = []
    det_serve = root / DET_SERVE_REL
    if not det_serve.exists():
        return [f"{DET_SERVE_REL}: missing (cannot check doc flags)"], {}
    flags = argparse_flags(det_serve)

    docs = sorted(root.glob("DESIGN_*.md"))
    readme = root / "README.md"
    if readme.exists():
        docs.insert(0, readme)
    else:
        findings.append("README.md: missing at repo root")

    n_spans = n_flags = n_refs = 0
    for doc in docs:
        text = doc.read_text()
        for lineno, span in iter_code_spans(text):
            if "det_serve" not in span:
                continue
            n_spans += 1
            for m in _FLAG_RE.finditer(span):
                n_flags += 1
                if m.group(0) not in flags:
                    findings.append(
                        f"{doc.name}:{lineno}: doc names det_serve flag "
                        f"{m.group(0)!r} but det_serve.py has no such "
                        f"argparse option")
        for lineno, raw in enumerate(text.splitlines(), 1):
            for m in _XREF_RE.finditer(raw):
                n_refs += 1
                target = root / (m.group(1) + ".md")
                if not target.exists():
                    findings.append(
                        f"{doc.name}:{lineno}: cross-reference "
                        f"[[{m.group(1)}]] does not resolve to "
                        f"{m.group(1)}.md at the repo root")
    stats = {"docs": len(docs), "det_serve_spans": n_spans,
             "flags_checked": n_flags, "xrefs_checked": n_refs,
             "argparse_flags": len(flags)}
    return findings, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="check README/DESIGN_* docs against det_serve's CLI")
    ap.add_argument("--root", default=None,
                    help="repo root to check (default: this file's repo)")
    args = ap.parse_args(argv)
    root = (Path(args.root) if args.root
            else Path(__file__).resolve().parent.parent)
    findings, stats = check_docs(root)
    for f in findings:
        print(f, file=sys.stderr)
    if stats:
        print("check_docs: {docs} docs, {det_serve_spans} det_serve "
              "spans, {flags_checked} flags vs {argparse_flags} argparse "
              "options, {xrefs_checked} cross-refs".format(**stats))
    if findings:
        print(f"check_docs: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("check_docs: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

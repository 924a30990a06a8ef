"""graft-lint core: file walking, suppressions, the check driver.

Stdlib-only by design — ``tools/lint.py`` loads this package standalone
(no ``mxnet_tpu`` import, no jax) so a lint run costs milliseconds and
works on a machine with no accelerator stack.  The rules themselves
live in ``checkers.py``; the manifests they consult in ``manifest.py``;
the human catalog in ``docs/architecture/static_analysis.md``.

Suppression syntax (one per line, reason REQUIRED)::

    something_flagged()  # graft-lint: disable=<rule>[,<rule>] — reason

``--`` is accepted in place of the em-dash.  A suppression on a line of
its own also covers the next line.  A ``graft-lint: disable`` that
omits the reason (or names an unknown rule) is itself reported as a
``bad-suppression`` violation — ``make lint`` stays green only with
zero unexplained suppressions.
"""
from __future__ import annotations

import ast
import io
import os
import re
import tokenize

from .checkers import ALL_CHECKERS, RULES

__all__ = ["Violation", "LintContext", "lint_source", "lint_file",
           "lint_paths", "main", "RULES"]

_BASE_RELPATH = "mxnet_tpu/base.py"
_DOC_RELPATH = "docs/env_vars.md"

# matches comments of the form "disable=rule-a,rule-b — reason text"
_SUPPRESS_ANY_RE = re.compile(r"#\s*graft-lint\s*:\s*disable")
_SUPPRESS_RE = re.compile(
    r"#\s*graft-lint\s*:\s*disable=([a-z][a-z0-9\-]*(?:\s*,\s*"
    r"[a-z][a-z0-9\-]*)*)\s*(?:—|--)\s*(\S.*)$")


class Violation:
    """One finding: ``path:line: [rule] message``."""

    __slots__ = ("rule", "path", "line", "msg")

    def __init__(self, rule, path, line, msg):
        self.rule = rule
        self.path = path
        self.line = line
        self.msg = msg

    def key(self):
        return (self.path, self.line, self.rule, self.msg)

    def __repr__(self):
        return "%s:%d: [%s] %s" % (self.path, self.line, self.rule, self.msg)


class LintContext:
    """Repo-level facts the checkers consult: the env-knob registry
    parsed out of ``base.py`` (by AST, not import), the knob rows of
    ``docs/env_vars.md``, and the rule manifests.  Tests inject small
    fixture registries/manifests through the keyword overrides."""

    def __init__(self, root=None, registry=None, documented=None,
                 hot_paths=None, span_entry_points=None,
                 atomic_publish=None):
        from . import manifest as _m
        self.root = root
        self.base_relpath = _BASE_RELPATH
        self.doc_relpath = _DOC_RELPATH
        self.hot_paths = _m.HOT_PATHS if hot_paths is None else \
            tuple(hot_paths)
        self.span_entry_points = _m.SPAN_ENTRY_POINTS \
            if span_entry_points is None else tuple(span_entry_points)
        self.atomic_publish = _m.ATOMIC_PUBLISH \
            if atomic_publish is None else tuple(atomic_publish)
        if registry is not None:
            self.registry = dict(registry)
        elif root is not None:
            self.registry = _parse_registry(os.path.join(root, _BASE_RELPATH))
        else:
            self.registry = {}
        if documented is not None:
            self.documented = dict(documented)
        elif root is not None:
            self.documented = _parse_doc_rows(
                os.path.join(root, _DOC_RELPATH))
        else:
            self.documented = {}


def _parse_registry(base_path):
    """name -> line of every ``register_env("NAME", ...)`` in base.py."""
    with open(base_path) as f:
        tree = ast.parse(f.read(), filename=base_path)
    out = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "register_env" and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            out[node.args[0].value] = node.lineno
    return out

def _parse_doc_rows(doc_path):
    """name -> line of its OWN env_vars.md table row.  Only the first
    (name) column counts — another row's description mentioning a knob
    must not satisfy doc-sync for it."""
    out = {}
    if not os.path.exists(doc_path):
        return out
    with open(doc_path) as f:
        for i, line in enumerate(f, 1):
            if not line.lstrip().startswith("|"):
                continue
            name_cell = line.lstrip().split("|")[1] if "|" in line else ""
            for m in re.finditer(r"MXNET_[A-Z0-9_]+", name_cell):
                out.setdefault(m.group(0), i)
    return out


def _comment_tokens(src):
    """(line, comment_text, is_own_line) for every real COMMENT token —
    docstrings and string literals that merely *mention* the suppression
    syntax never match."""
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return
    for tok in toks:
        if tok.type == tokenize.COMMENT:
            own_line = tok.line[:tok.start[1]].strip() == ""
            yield tok.start[0], tok.string, own_line


def _suppressions(src):
    """line -> set(rules) suppressed there; plus [Violation] for
    malformed suppressions (missing reason / unknown rule)."""
    table = {}
    bad = []
    for i, comment, own_line in _comment_tokens(src):
        if not _SUPPRESS_ANY_RE.search(comment):
            continue
        m = _SUPPRESS_RE.search(comment)
        if not m:
            bad.append(Violation(
                "bad-suppression", None, i,
                "malformed graft-lint suppression: expected "
                "'# graft-lint: disable=<rule>[,<rule>] — reason' "
                "(the reason is required)"))
            continue
        rules = {r.strip() for r in m.group(1).split(",")}
        unknown = rules - set(RULES)
        if unknown:
            bad.append(Violation(
                "bad-suppression", None, i,
                "unknown rule%s in suppression: %s (known: %s)"
                % ("s" if len(unknown) > 1 else "",
                   ", ".join(sorted(unknown)), ", ".join(RULES))))
            rules -= unknown
        table.setdefault(i, set()).update(rules)
        # a comment-only line covers the statement below it
        if own_line:
            table.setdefault(i + 1, set()).update(rules)
    return table, bad


def lint_source(ctx, src, relpath, rules=None):
    """Lint one python source string known as ``relpath``."""
    try:
        tree = ast.parse(src, filename=relpath)
    except SyntaxError as e:
        return [Violation("syntax", relpath, e.lineno or 1, str(e))]
    lines = src.splitlines()
    suppressed, out = _suppressions(src)
    for v in out:
        v.path = relpath
    for checker in ALL_CHECKERS:
        if rules is not None and checker.rule not in rules:
            continue
        out.extend(checker().check(ctx, relpath, tree, lines))
    return [v for v in out
            if v.rule not in suppressed.get(v.line, ())]


def lint_file(ctx, path, rules=None):
    relpath = os.path.relpath(path, ctx.root) if ctx.root else path
    relpath = relpath.replace(os.sep, "/")
    with open(path) as f:
        src = f.read()
    return lint_source(ctx, src, relpath, rules=rules)


def repo_checks(ctx, rules=None):
    """Cross-file checks: registry <-> docs/env_vars.md sync."""
    if rules is not None and "env-knob" not in rules:
        return []
    out = []
    for name in sorted(ctx.registry):
        if name.startswith("MXNET_") and name not in ctx.documented:
            out.append(Violation(
                "env-knob", ctx.base_relpath, ctx.registry[name],
                "registered knob %s has no docs/env_vars.md row" % name))
    for name in sorted(ctx.documented):
        if name.startswith("MXNET_") and name not in ctx.registry:
            out.append(Violation(
                "env-knob", ctx.doc_relpath, ctx.documented[name],
                "documented knob %s is not registered in base.py "
                "(register_env)" % name))
    return out


class MissingPathError(ValueError):
    """A lint target does not exist — fail loudly rather than letting a
    typo'd/renamed path make the zero-violation gate pass vacuously."""


def _expand(root, paths):
    files = []
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                files.extend(os.path.join(dirpath, f)
                             for f in sorted(filenames)
                             if f.endswith(".py"))
        elif os.path.isfile(full) and full.endswith(".py"):
            files.append(full)
        else:
            raise MissingPathError(
                "lint target %r does not exist (or is not a directory "
                "or .py file) — refusing to report a vacuously clean "
                "tree" % p)
    return sorted(set(files))


def lint_paths(root, paths, rules=None):
    """Lint every .py under ``paths`` (files or directories, relative
    to ``root``) plus the repo-level registry/doc sync checks."""
    ctx = LintContext(root=root)
    out = repo_checks(ctx, rules=rules)
    for f in _expand(root, paths):
        out.extend(lint_file(ctx, f, rules=rules))
    return sorted(out, key=Violation.key)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="graft-lint",
        description="Project-specific static analysis "
                    "(docs/architecture/static_analysis.md).")
    ap.add_argument("paths", nargs="*", default=["mxnet_tpu", "tools"],
                    help="files/directories to lint (default: "
                         "mxnet_tpu tools)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: auto-detected from this "
                         "file's location)")
    ap.add_argument("--rule", action="append", dest="rules", default=None,
                    metavar="RULE", help="run only this rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        from .checkers import ALL_CHECKERS as cs
        for c in cs:
            doc = (c.__doc__ or "").strip().splitlines()[0]
            print("%-18s %s" % (c.rule, doc))
        return 0
    root = args.root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        violations = lint_paths(root, args.paths, rules=args.rules)
    except MissingPathError as e:
        print("graft-lint: error: %s" % e)
        return 2
    for v in violations:
        print(v)
    if violations:
        print("graft-lint: %d violation%s" %
              (len(violations), "s" if len(violations) != 1 else ""))
        return 1
    print("graft-lint: clean")
    return 0

"""mxlint core: findings, suppressions, config, and the rule engine.

This package is a *static* analysis library: it reads source text and
``ast`` trees, never imports the modules it checks, and depends only on
the stdlib. That is a hard design constraint — ``tools/mxlint.py``
loads this package standalone (without importing ``mxnet_tpu`` and its
jax dependency), so a full-tree lint costs ~1s of CPU and nothing
against the tier-1 test clock.

Vocabulary:

- A **rule** encodes one repo invariant (see ``rules/``). File-scope
  rules see one :class:`FileCtx` at a time; project-scope rules (the
  catalog-drift family) see every file plus the repo root, because
  they diff code against the docs catalogs.
- A **finding** is one violation at ``path:line``. Findings are
  suppressed inline (``# mxlint: disable=RULE  reason``) or
  grandfathered in the committed baseline file (see ``baseline.py``);
  everything else fails ``tools/mxlint.py --check``.
"""
from __future__ import annotations

import ast
import os
import re
import time

__all__ = ["Finding", "FileCtx", "Rule", "RunResult", "run",
           "load_config", "collect_files", "DEFAULT_CONFIG",
           "parent_map", "enclosing", "lint_source"]


# --------------------------------------------------------------------------
# findings
# --------------------------------------------------------------------------

class Finding:
    """One rule violation at a source location."""

    __slots__ = ("rule", "path", "line", "col", "message")

    def __init__(self, rule, path, line, col, message):
        self.rule = rule
        self.path = path
        self.line = int(line)
        self.col = int(col)
        self.message = message

    def key(self):
        """Identity used by suppressions and the baseline: rule + the
        exact file:line, so baseline entries burn down honestly."""
        return (self.rule, self.path, self.line)

    def to_dict(self):
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message}

    def sort_key(self):
        return (self.path, self.line, self.col, self.rule)

    def __repr__(self):
        return (f"Finding({self.path}:{self.line}:{self.col} "
                f"{self.rule}: {self.message!r})")


# --------------------------------------------------------------------------
# per-file context + inline suppressions
# --------------------------------------------------------------------------

_DISABLE_RE = re.compile(
    r"#\s*mxlint:\s*disable(?P<file>-file)?\s*=\s*"
    r"(?P<rules>[A-Za-z0-9_\-*]+(?:\s*,\s*[A-Za-z0-9_\-*]+)*)"
    r"(?:\s+(?P<reason>\S.*))?")

_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")


class FileCtx:
    """One parsed source file: path (repo-relative, POSIX separators),
    text, ``ast`` tree, and the inline-suppression map."""

    def __init__(self, path, source, tree):
        self.path = path
        self.source = source
        self.tree = tree
        self.lines = source.splitlines()
        # shared per-file analysis caches: parent_map and anything
        # rules memoize via ``memo`` (e.g. the compiled-functions
        # fixpoint) are computed once per file, not once per rule
        self._parents = None
        self.memo = {}
        # lineno -> set of rule ids ('*' = all); trailing comments bind
        # to their own line, comment-only lines to the next line.
        self.line_disables = {}
        self.file_disables = set()
        self.guarded_by = {}          # lineno -> lock/waiver name
        self._scan_comments()

    def _scan_comments(self):
        for i, text in enumerate(self.lines, start=1):
            g = _GUARDED_BY_RE.search(text)
            if g:
                self.guarded_by[i] = g.group(1)
            m = _DISABLE_RE.search(text)
            if not m:
                continue
            rules = {r.strip() for r in m.group("rules").split(",")}
            if m.group("file"):
                self.file_disables |= rules
            elif text.lstrip().startswith("#"):
                self.line_disables.setdefault(i + 1, set()).update(rules)
            else:
                self.line_disables.setdefault(i, set()).update(rules)

    def suppressed(self, rule, line):
        if rule in self.file_disables or "*" in self.file_disables:
            return True
        rules = self.line_disables.get(line, ())
        return rule in rules or "*" in rules

    def parents(self):
        """Cached ``parent_map(self.tree)``."""
        if self._parents is None:
            self._parents = parent_map(self.tree)
        return self._parents

    def segment(self, node):
        """Source text of ``node`` (best effort)."""
        try:
            return ast.get_source_segment(self.source, node) or ""
        except Exception:
            return ""


def parent_map(tree):
    """child node -> parent node, for ancestor walks."""
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def enclosing(node, parents, kinds):
    """Nearest ancestor of ``node`` matching ``kinds`` (a type tuple)."""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, kinds):
            return cur
        cur = parents.get(cur)
    return None


# --------------------------------------------------------------------------
# rules
# --------------------------------------------------------------------------

class Rule:
    """Base class. Subclasses set ``id`` (the suppression/baseline
    name), ``scope`` (``file`` | ``project``) and implement one of the
    check methods, yielding :class:`Finding` objects."""

    id = ""
    scope = "file"
    description = ""

    def check_file(self, ctx):
        return []

    def check_project(self, ctxs, root, config):
        return []

    def finding(self, path, node_or_line, message, col=0):
        if isinstance(node_or_line, int):
            line = node_or_line
        else:
            line = getattr(node_or_line, "lineno", 1)
            col = getattr(node_or_line, "col_offset", 0)
        return Finding(self.id, path, line, col, message)


# --------------------------------------------------------------------------
# configuration ([tool.mxlint] in pyproject.toml)
# --------------------------------------------------------------------------

DEFAULT_CONFIG = {
    "paths": ["mxnet_tpu", "tools"],
    "exclude": ["__pycache__", "native/_build", ".git", "build",
                "dist", ".eggs"],
    "baseline": "tools/mxlint_baseline.json",
    # catalog rules only treat THESE paths as declaration sites
    "catalog_paths": ["mxnet_tpu"],
    "metric_docs": "docs/OBSERVABILITY.md",
    "env_docs": "docs/ENV_VARS.md",
    "fault_docs": "docs/RESILIENCE.md",
}


def _strip_toml_comment(line):
    """Drop a ``#`` comment, respecting quoted strings (a ``#`` inside
    quotes is data). This runs BEFORE value parsing on every line —
    on Python 3.10 (no tomllib) this parser is the production path,
    so an ordinary trailing comment must not corrupt the value."""
    out, quote = [], None
    for ch in line:
        if quote:
            out.append(ch)
            if ch == quote:
                quote = None
        elif ch in "\"'":
            quote = ch
            out.append(ch)
        elif ch == "#":
            break
        else:
            out.append(ch)
    return "".join(out).strip()


def _parse_toml_minimal(text):
    """Tiny TOML-subset reader for Python < 3.11 (no tomllib): dotted
    ``[section]`` headers, string / bool / int scalars, and string
    lists (single- or multi-line). Enough for ``[tool.mxlint]``."""
    out = {}
    cur = out
    buf_key, buf = None, None
    for raw in text.splitlines():
        line = _strip_toml_comment(raw)
        if buf_key is not None:
            buf.append(line)
            if line.endswith("]"):
                cur[buf_key] = _parse_toml_value(" ".join(buf))
                buf_key, buf = None, None
            continue
        if not line or line.startswith("#"):
            continue
        if line.startswith("[") and line.endswith("]"):
            cur = out
            for part in line[1:-1].strip().split("."):
                cur = cur.setdefault(part.strip(), {})
            continue
        if "=" not in line:
            continue
        key, _, val = line.partition("=")
        key, val = key.strip().strip('"'), val.strip()
        if val.startswith("[") and not val.endswith("]"):
            buf_key, buf = key, [val]
            continue
        cur[key] = _parse_toml_value(val)
    return out


def _parse_toml_value(val):
    # comments were stripped line-by-line before buffering/dispatch
    val = val.strip()
    if val.startswith("[") and val.endswith("]"):
        inner = val[1:-1].strip().rstrip(",")
        if not inner:
            return []
        return [_parse_toml_value(v.strip())
                for v in inner.split(",") if v.strip()]
    if val.startswith('"') and val.endswith('"'):
        return val[1:-1]
    if val.startswith("'") and val.endswith("'"):
        return val[1:-1]
    if val in ("true", "false"):
        return val == "true"
    try:
        return int(val)
    except ValueError:
        return val


def load_config(root):
    """DEFAULT_CONFIG overridden by ``[tool.mxlint]`` in
    ``<root>/pyproject.toml`` (when present)."""
    config = dict(DEFAULT_CONFIG)
    pyproject = os.path.join(root, "pyproject.toml")
    if os.path.isfile(pyproject):
        try:
            import tomllib
            with open(pyproject, "rb") as f:
                data = tomllib.load(f)
        except ImportError:
            with open(pyproject, encoding="utf-8") as f:
                data = _parse_toml_minimal(f.read())
        table = data.get("tool", {}).get("mxlint", {})
        if isinstance(table, dict):
            config.update(table)
    return config


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------

def collect_files(root, paths, exclude):
    """Repo-relative POSIX paths of every ``.py`` file under the
    configured paths, excluded dirs pruned."""
    out = []
    exclude = tuple(exclude)

    def excluded(rel):
        # exact path-segment match only (single- or multi-segment
        # patterns like "__pycache__" / "native/_build") — a substring
        # test would silently drop e.g. distill.py for pattern "dist"
        rel = "/" + rel.replace(os.sep, "/").strip("/") + "/"
        return any("/" + part.strip("/") + "/" in rel
                   for part in exclude)

    for p in paths:
        full = os.path.join(root, p)
        if os.path.isfile(full):
            if p.endswith(".py") and not excluded(p):
                out.append(p.replace(os.sep, "/"))
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            rel_dir = os.path.relpath(dirpath, root)
            dirnames[:] = sorted(
                d for d in dirnames
                if not excluded(os.path.join(rel_dir, d)))
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                if not excluded(rel):
                    out.append(rel.replace(os.sep, "/"))
    return sorted(set(out))


class RunResult:
    """Everything one engine pass produced."""

    def __init__(self, findings, files, elapsed_s, suppressed_count,
                 parse_errors):
        self.findings = sorted(findings, key=Finding.sort_key)
        self.files = files
        self.elapsed_s = elapsed_s
        self.suppressed_count = suppressed_count
        self.parse_errors = parse_errors

    def by_rule(self):
        out = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return dict(sorted(out.items()))


def _default_rules():
    from .rules import ALL_RULES
    return [cls() for cls in ALL_RULES]


def run(root, config=None, rules=None, files=None):
    """Lint the tree under ``root``. Returns a :class:`RunResult` of
    unsuppressed findings (baseline filtering is the caller's business
    — see ``baseline.diff``)."""
    t0 = time.monotonic()
    config = config or load_config(root)
    rules = _default_rules() if rules is None else rules
    if files is None:
        files = collect_files(root, config["paths"], config["exclude"])

    ctxs, parse_errors, findings = [], [], []
    for rel in files:
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source, filename=rel)
        except (SyntaxError, UnicodeDecodeError, OSError) as exc:
            line = getattr(exc, "lineno", 1) or 1
            parse_errors.append((rel, str(exc)))
            findings.append(Finding("parse-error", rel, line, 0,
                                    f"file does not parse: {exc}"))
            continue
        ctxs.append(FileCtx(rel, source, tree))

    suppressed = 0
    file_rules = [r for r in rules if r.scope == "file"]
    project_rules = [r for r in rules if r.scope == "project"]
    for ctx in ctxs:
        for rule in file_rules:
            for f in rule.check_file(ctx):
                if ctx.suppressed(f.rule, f.line):
                    suppressed += 1
                else:
                    findings.append(f)
    ctx_by_path = {c.path: c for c in ctxs}
    for rule in project_rules:
        for f in rule.check_project(ctxs, root, config):
            ctx = ctx_by_path.get(f.path)
            if ctx is not None and ctx.suppressed(f.rule, f.line):
                suppressed += 1
            else:
                findings.append(f)
    return RunResult(findings, files, time.monotonic() - t0,
                     suppressed, parse_errors)


def lint_source(source, rules=None, path="<snippet>"):
    """Run file-scope rules over a source string — the fixture-test
    entry point. Returns the (unsuppressed) findings."""
    tree = ast.parse(source, filename=path)
    ctx = FileCtx(path, source, tree)
    rules = _default_rules() if rules is None else rules
    out = []
    for rule in rules:
        if rule.scope != "file":
            continue
        for f in rule.check_file(ctx):
            if not ctx.suppressed(f.rule, f.line):
                out.append(f)
    return sorted(out, key=Finding.sort_key)

"""ptlint core: findings, file context, rule registry, suppressions.

One parse per file: :class:`FileContext` owns the source, the AST and
the path taxonomy; every registered rule whose ``applies`` predicate
accepts the context runs over it and returns :class:`Finding`\\ s.
Suppression handling is central (rules never see comments):

- ``# noqa`` on the finding line suppresses everything there (the
  legacy escape hatch, kept so old call sites stay valid);
- ``# ptlint: disable=PT013`` (comma-separated codes) suppresses the
  listed codes on that line, and MUST carry a justification after the
  code list (``# ptlint: disable=PT014 -- probe RPC is deadline-bounded``)
  or it is itself a finding (PTL002);
- a disable comment whose codes produced no finding on that line is an
  unused suppression (PTL001) — suppressions rot when the code under
  them changes, and a stale one silently disables the NEXT real
  finding on the line.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import sys
import tokenize

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: The directive comment shape (justification separator is free-form:
#: anything after the code list counts). Anchored at the start of the
#: COMMENT token, so a comment QUOTING a directive is prose.
_DISABLE_RE = re.compile(
    r"^#\s*ptlint:\s*disable=([A-Za-z0-9_,]+)(.*)$")


class Finding:
    """One diagnostic: ``path:line: code message``."""

    __slots__ = ("path", "line", "code", "message")

    def __init__(self, path: str, line: int, code: str, message: str):
        self.path = path
        self.line = int(line)
        self.code = code
        self.message = message

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"

    def to_json(self) -> dict:
        return {"path": self.path, "line": self.line,
                "code": self.code, "message": self.message}

    def __repr__(self) -> str:
        return f"Finding({self.format()!r})"


class FileContext:
    """Everything a rule needs about one file, parsed once."""

    def __init__(self, path: str, src: str, tree: ast.AST):
        self.path = path
        self.src = src
        self.tree = tree
        self.lines = src.splitlines()
        norm = os.path.normpath(path)
        self.parts = norm.split(os.sep)
        self.basename = os.path.basename(path)
        self.is_init = self.basename == "__init__.py"

    # -- path taxonomy helpers (the old checker's dispatch, named)

    def in_dir(self, name: str) -> bool:
        return name in self.parts

    @property
    def in_pkg(self) -> bool:
        return "ptype_tpu" in self.parts

    def finding(self, node_or_line, code: str, message: str) -> Finding:
        line = (node_or_line if isinstance(node_or_line, int)
                else getattr(node_or_line, "lineno", 0))
        return Finding(self.path, line, code, message)


class Rule:
    """One registered pass: stable code, doc line, gate, check."""

    __slots__ = ("code", "summary", "applies", "check")

    def __init__(self, code, summary, applies, check):
        self.code = code
        self.summary = summary
        self.applies = applies
        self.check = check


#: code -> Rule. Codes are stable IDs: docs/LINTING.md catalogues
#: them, suppressions name them, and tests pin them.
RULES: dict[str, Rule] = {}


def rule(code: str, summary: str, applies=None):
    """Decorator: register ``check(ctx) -> list[Finding]`` under a
    stable code. ``applies(ctx) -> bool`` gates by path (default:
    every file)."""

    def wrap(fn):
        if code in RULES:
            raise ValueError(f"duplicate ptlint rule code {code!r}")
        RULES[code] = Rule(code, summary, applies or (lambda ctx: True),
                           fn)
        return fn

    return wrap


# ------------------------------------------------------------ suppression


def _parse_suppressions(ctx: FileContext) -> dict[int, tuple[set, bool]]:
    """lineno -> (codes, justified) for every ``ptlint: disable``
    comment. Real COMMENT tokens only (tokenize): a directive QUOTED
    in a docstring — this docstring, the rule catalogue, a test
    fixture string — is prose, not a suppression."""
    out: dict[int, tuple[set, bool]] = {}
    try:
        tokens = tokenize.generate_tokens(
            io.StringIO(ctx.src).readline)
        comments = [(t.start[0], t.string) for t in tokens
                    if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, IndentationError):
        return out
    for lineno, text in comments:
        m = _DISABLE_RE.search(text)
        if m is None:
            continue
        codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
        justification = m.group(2).strip(" -—:\t")
        out[lineno] = (codes, bool(justification))
    return out


def _apply_suppressions(ctx: FileContext,
                        raw: list[Finding]) -> list[Finding]:
    """Drop suppressed findings; add PTL001 (unused suppression) and
    PTL002 (suppression without justification) findings."""
    disables = _parse_suppressions(ctx)
    used: dict[int, set] = {i: set() for i in disables}
    kept: list[Finding] = []
    for f in raw:
        line = ctx.lines[f.line - 1] if 0 < f.line <= len(ctx.lines) \
            else ""
        if "noqa" in line:
            continue
        codes, _ = disables.get(f.line, (set(), True))
        if f.code in codes:
            used[f.line].add(f.code)
            continue
        kept.append(f)
    for lineno, (codes, justified) in disables.items():
        unused = codes - used.get(lineno, set())
        # Meta-codes can't be pre-suppressed by themselves; a disable
        # line may legitimately pre-arm a code for a finding the rule
        # only raises on SOME configurations — no: unused is unused.
        if unused:
            kept.append(Finding(
                ctx.path, lineno, "PTL001",
                f"unused suppression for "
                f"{', '.join(sorted(unused))} — no such finding on "
                f"this line; a stale disable silently eats the next "
                f"real one (delete it)"))
        if not justified:
            kept.append(Finding(
                ctx.path, lineno, "PTL002",
                f"suppression for {', '.join(sorted(codes))} carries "
                f"no justification — write WHY after the code list "
                f"(`# ptlint: disable=PTxxx -- reason`)"))
    return kept


# --------------------------------------------------------------- checking


def check_file_findings(path: str) -> list[Finding]:
    """Run every applicable rule over one file; suppressions applied."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "E999", str(e.msg))]
    ctx = FileContext(path, src, tree)
    raw: list[Finding] = []
    for r in RULES.values():
        if r.applies(ctx):
            raw.extend(r.check(ctx))
    out = _apply_suppressions(ctx, raw)
    # De-duplicate (identical finding from overlapping walks), keep
    # first-seen order, then sort by line for stable output.
    seen: set[str] = set()
    uniq = []
    for f in out:
        key = f.format()
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    uniq.sort(key=lambda f: (f.line, f.code))
    return uniq


def check_file(path: str, findings: list[str]) -> None:
    """The tools/lint.py-compatible surface: append formatted
    ``path:line: code message`` strings."""
    findings.extend(f.format() for f in check_file_findings(path))


def iter_py(paths: list[str]):
    for root in paths:
        if os.path.isfile(root):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def run_paths(paths: list[str]) -> tuple[list[Finding], int]:
    """(findings, files checked) over files/directories."""
    findings: list[Finding] = []
    n = 0
    for path in iter_py(paths):
        n += 1
        findings.extend(check_file_findings(path))
    return findings, n


def main(argv: list[str]) -> int:
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    paths = argv or [os.path.join(REPO, "ptype_tpu"),
                     os.path.join(REPO, "tests"),
                     os.path.join(REPO, "examples"),
                     os.path.join(REPO, "chip_smoke.py"),
                     os.path.join(REPO, "__graft_entry__.py"),
                     os.path.join(REPO, "tools")]
    findings, n = run_paths(paths)
    if as_json:
        print(json.dumps([f.to_json() for f in findings], indent=2))
    else:
        for f in findings:
            print(f.format())
    print(f"ptlint: {n} files, {len(findings)} findings, "
          f"{len(RULES)} rules", file=sys.stderr)
    return 1 if findings else 0

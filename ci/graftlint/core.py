"""graftlint core — shared AST infrastructure for every lint pass.

The seven historical ``ci/check_*.py`` scripts each carried their own
file walker, their own suppression comment, and their own output format;
none could express a dataflow property (PyGraph makes the case that a
*static* side-effect/compatibility analysis is what decides what may
enter a captured/compiled region — the same argument applies to our
jit-traced code, donated buffers, and threaded modules).  This package
gives every pass one:

* :class:`Source` — parse a file ONCE (text, line table, AST, suppression
  table) and share it across passes;
* :class:`Finding` — one diagnostic with a stable, line-independent
  ``key`` so baselines survive unrelated edits;
* :class:`Pass` — the plugin contract (per-file ``check_source`` or
  whole-project ``run``);
* the **suppression grammar** ``# lint: ok[pass-id] <reason>`` (comma
  lists and ``*`` allowed) honored uniformly, with each migrated pass's
  legacy tag (``# noqa``, ``# host-sync: ok``) still respected so no
  existing annotation breaks.
"""

from __future__ import annotations

import ast
import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parent.parent.parent

#: the unified suppression grammar: ``# lint: ok[pass-id] reason`` — the
#: bracket takes one id, a comma list, or ``*`` (all passes); everything
#: after the bracket is the human reason (recommended, not enforced)
SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*ok\[([A-Za-z0-9_*,\- ]+)\]\s*(.*)")


class Finding:
    """One diagnostic.

    ``detail`` is the pass-chosen *stable symbol* for the finding (an
    attribute name, a variable, an env var) — together with the pass id,
    file and code it forms the baseline ``key``, which deliberately
    excludes the line number so a baseline entry survives unrelated
    edits above it."""

    __slots__ = ("pass_id", "path", "line", "code", "message", "detail",
                 "suppressed", "baselined")

    def __init__(self, pass_id, path, line, code, message, detail=""):
        self.pass_id = pass_id
        self.path = str(path)
        self.line = int(line)
        self.code = code
        self.message = message
        self.detail = detail
        self.suppressed = None   # reason string when suppressed
        self.baselined = False

    def key(self):
        return (self.pass_id, self.path, self.code, self.detail)

    def location(self):
        return "%s:%d" % (self.path, self.line)

    def to_dict(self):
        d = {"pass": self.pass_id, "path": self.path, "line": self.line,
             "code": self.code, "message": self.message}
        if self.detail:
            d["detail"] = self.detail
        if self.suppressed is not None:
            d["suppressed"] = self.suppressed
        if self.baselined:
            d["baselined"] = True
        return d

    def __repr__(self):
        return "Finding(%s %s [%s] %s)" % (self.pass_id, self.location(),
                                           self.code, self.detail)


class Source:
    """One parsed file, shared by every pass that looks at it."""

    def __init__(self, path, rel, text):
        self.path = pathlib.Path(path)
        self.rel = str(rel)          # what findings/baselines report
        self.text = text
        self.lines = text.splitlines()
        self.syntax_error = None
        try:
            self.tree = ast.parse(text, filename=str(path))
        except SyntaxError as e:
            self.tree = None
            self.syntax_error = e
        # lineno -> (set of pass ids or {'*'}, reason)
        self.suppressions = {}
        for i, line in enumerate(self.lines, 1):
            m = SUPPRESS_RE.search(line)
            if m:
                ids = {p.strip() for p in m.group(1).split(",") if p.strip()}
                self.suppressions[i] = (ids, m.group(2).strip())
        self._tag_lines = {}

    @classmethod
    def load(cls, path, rel=None):
        path = pathlib.Path(path)
        if rel is None:
            try:
                rel = path.resolve().relative_to(REPO).as_posix()
            except ValueError:
                rel = str(path)
        return cls(path, rel, path.read_text())

    def tag_lines(self, tag):
        """Line numbers carrying a legacy suppression ``tag`` verbatim
        (``# noqa``, ``# host-sync: ok``) — the pre-graftlint grammar,
        still honored by the migrated passes."""
        if tag not in self._tag_lines:
            self._tag_lines[tag] = {
                i for i, line in enumerate(self.lines, 1) if tag in line}
        return self._tag_lines[tag]

    def suppression_for(self, pass_id, lineno, legacy_tags=()):
        """The suppression reason covering ``(pass_id, lineno)``, or None.

        Honors the unified grammar on the finding line or on a
        comment-only line directly above it (for statements too long to
        carry a trailing comment), and each legacy tag on the finding
        line (exactly the old scripts' behavior)."""
        for ln in (lineno, lineno - 1):
            entry = self.suppressions.get(ln)
            if entry is None:
                continue
            ids, reason = entry
            if ln == lineno - 1 and self.lines[ln - 1].strip() \
                    and not self.lines[ln - 1].lstrip().startswith("#"):
                continue  # above-line form must be a comment-only line
            if "*" in ids or pass_id in ids:
                return reason or "suppressed"
        for tag in legacy_tags:
            if lineno in self.tag_lines(tag):
                return "legacy tag %r" % tag
        return None


class Pass:
    """Base class for one lint pass.

    Subclasses set ``id`` (kebab-case, what the suppression grammar and
    baseline refer to), ``title``, ``default_roots`` (repo-relative
    paths scanned when the caller gives none), optional
    ``excluded_files`` (basenames skipped wholesale), optional
    ``legacy_tags`` (pre-graftlint suppression comments still honored),
    and implement either ``check_source`` (per-file) or ``run``
    (whole-project: gets every collected :class:`Source` at once)."""

    id = "abstract"
    title = "abstract pass"
    #: repo-relative default scan roots
    default_roots = ("mxnet_tpu",)
    #: basenames skipped entirely (allowed-by-design files)
    excluded_files = frozenset()
    #: legacy suppression comments (exact substrings) still honored
    legacy_tags = ()
    #: orchestrated passes run an external workload (subprocess
    #: cache probes) instead of analyzing sources — opt-in only
    orchestrated = False
    #: interprocedural passes analyze the whole collected tree at once
    #: (project call graph); in ``--changed`` runs they still see every
    #: source but only findings in changed files are reported
    interprocedural = False

    def run(self, sources, ctx):
        findings = []
        for src in sources:
            if src.syntax_error is not None:
                e = src.syntax_error
                findings.append(Finding(
                    self.id, src.rel, e.lineno or 0, "syntax-error",
                    "syntax error: %s" % e.msg))
                continue
            findings.extend(self.check_source(src, ctx))
        return findings

    def check_source(self, src, ctx):
        raise NotImplementedError

    def find(self, src, node_or_line, code, message, detail=""):
        line = getattr(node_or_line, "lineno", node_or_line)
        return Finding(self.id, src.rel, line, code, message, detail)


class RunContext:
    """Options shared by one runner invocation (overridable in tests):
    ``repo`` root, explicit ``roots`` (None -> per-pass defaults), and
    ``env_doc_path`` for the env-docs pass."""

    def __init__(self, repo=REPO, roots=None, env_doc_path=None,
                 literal_paths=False, changed=None):
        self.repo = pathlib.Path(repo)
        self.roots = [pathlib.Path(r) for r in roots] if roots else None
        self.env_doc_path = pathlib.Path(env_doc_path) \
            if env_doc_path else self.repo / "docs" / "how_to" / "env_var.md"
        #: report paths exactly as walked (absolute for default roots,
        #: as-given for CLI args) instead of repo-relative
        self.literal_paths = literal_paths
        #: diff-scoped lane (``--changed [REV]``): the set of
        #: repo-relative paths to REPORT on.  Per-file passes skip
        #: unchanged sources entirely; interprocedural passes still
        #: analyze the whole tree (the call graph needs it) but only
        #: findings in changed files surface.  None = full run.
        self.changed = set(changed) if changed is not None else None
        self._cache = {}

    def collect(self, lint_pass):
        """The :class:`Source` list ``lint_pass`` should analyze: the
        explicit roots when given (files or directories), else the
        pass's defaults; parsed files are cached so N passes share one
        AST per file."""
        roots = self.roots if self.roots is not None \
            else [self.repo / r for r in lint_pass.default_roots]
        sources = []
        for root in roots:
            files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
            for f in files:
                if f.name in lint_pass.excluded_files:
                    continue
                key = str(f)
                if key not in self._cache:
                    if self.literal_paths:
                        rel = str(f)
                    else:
                        try:
                            rel = f.resolve().relative_to(
                                self.repo.resolve()).as_posix()
                        except ValueError:
                            rel = str(f)
                    self._cache[key] = Source.load(f, rel)
                sources.append(self._cache[key])
        return sources


def apply_suppressions(findings, sources_by_rel, legacy_tags):
    """Mark each finding whose line carries a matching suppression;
    returns the (still-complete) list — callers filter on
    ``f.suppressed``."""
    for f in findings:
        src = sources_by_rel.get(f.path)
        if src is None:
            continue
        reason = src.suppression_for(f.pass_id, f.line, legacy_tags)
        if reason is not None:
            f.suppressed = reason
    return findings

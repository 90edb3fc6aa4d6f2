"""replint — the repo-specific static-analysis suite (DESIGN.md §10).

The codebase rests on invariants that used to exist only as convention:
every kernel op has a ref contract and an oracle backend, every ``REPRO_*``
knob is registered and documented, jitted cores never coerce tracers,
Pallas scratch fits VMEM for every autotune candidate, ``-1`` is the one
sentinel. ``python -m repro.lint`` turns each into a checked rule:

  ====  =====================================================================
  R1    knob-registry: all ``REPRO_*`` env access flows through
        ``core/knobs.py``; ``docs/KNOBS.md`` matches the generated table
  R2    dispatch-contract: every op in ``kernels/ops.py`` has a ``ref.py``
        contract, an oracle impl token, ``_check_impl`` validation, a
        registered override knob, and a test module naming it
  R3    jit-discipline: no tracer coercions (``float()``/``int()``/
        ``bool()``/``.item()``/``np.asarray``) and no unhashable static
        args inside the jitted ``_*_jit`` cores
  R4    vmem-budget: every Pallas kernel's BlockSpec/scratch shapes,
        evaluated over the full ``kernels/autotune.py`` CANDIDATES grid,
        fit the 16 MiB/core VMEM budget DESIGN.md claims
  R5    sentinel-discipline: only ``-1`` sentinels in storage/kernel code —
        no dtype-max comparisons or stray magic sentinels
  R6    import-reachability: no code unreachable from the public entry
        points except the allowlisted seed-vestigial packages
  ====  =====================================================================

Workflow: findings not in the committed baseline (``lint_baseline.json``,
entries carry a one-line reason) fail the run; ``--strict`` additionally
fails on *stale* baseline entries so the baseline only ever shrinks
(``benchmarks/ci_gate.py`` hard-fails growth). Point suppressions use an
inline ``# replint: allow[R5] reason`` comment on the flagged line.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os
import re

__all__ = [
    "Finding", "Context", "run", "load_baseline", "save_baseline",
    "suppressed", "DEFAULT_BASELINE", "repo_root",
]

DEFAULT_BASELINE = "lint_baseline.json"

_ALLOW_RE = re.compile(r"#\s*replint:\s*allow\[([A-Za-z0-9*,\s]+)\]")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation.

    ``key`` is the *stable identity* used for baselining — rule + path +
    a slug chosen by the rule (a knob/op/module name, never a line number),
    so baseline entries survive unrelated edits to the file.
    """

    rule: str       # "R1".."R6"
    path: str       # repo-relative, '/'-separated
    line: int       # 1-based; 0 = whole-file finding
    message: str
    slug: str       # stable identity fragment

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.slug}"

    def render(self, tag: str = "") -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        suffix = f"  [{tag}]" if tag else ""
        return f"{self.rule} {loc}: {self.message}{suffix}"


def repo_root(start: str | None = None) -> str:
    """Walk up from ``start`` (default: this file) to the pyproject root."""
    p = os.path.abspath(start or os.path.dirname(__file__))
    while True:
        if os.path.exists(os.path.join(p, "pyproject.toml")):
            return p
        parent = os.path.dirname(p)
        if parent == p:
            raise FileNotFoundError(
                "repro.lint: no pyproject.toml above " + str(start)
            )
        p = parent


class Context:
    """Everything a rule needs to see, injectable for fixture tests.

    The defaults describe *this* repo's layout; ``tests/test_lint.py``
    builds Contexts over tmp fixture trees by overriding the relevant
    paths (``ops_path``, ``src_dir``, ...), which is how each rule's
    violating/clean fixtures run without a full repo copy.
    """

    def __init__(
        self,
        root: str | None = None,
        *,
        src_dir: str | None = None,        # the repro package dir
        extra_dirs: tuple[str, ...] | None = None,  # benchmarks etc. (R1)
        tests_dir: str | None = None,
        knobs_path: str | None = None,     # core/knobs.py (R1/R2)
        knobs_md_path: str | None = None,  # docs/KNOBS.md (R1)
        ops_path: str | None = None,       # kernels/ops.py (R2)
        ref_path: str | None = None,       # kernels/ref.py (R2)
        autotune_path: str | None = None,  # kernels/autotune.py (R4)
        kernels_dir: str | None = None,    # kernels/ (R4)
        sentinel_paths: tuple[str, ...] | None = None,  # R5 scope
        entry_points: tuple[str, ...] | None = None,    # R6 roots
    ):
        self.root = os.path.abspath(root or repo_root())
        j = os.path.join
        self.src_dir = src_dir or j(self.root, "src", "repro")
        self.extra_dirs = (
            extra_dirs if extra_dirs is not None
            else (j(self.root, "benchmarks"),)
        )
        self.tests_dir = tests_dir or j(self.root, "tests")
        self.knobs_path = knobs_path or j(self.src_dir, "core", "knobs.py")
        self.knobs_md_path = (
            knobs_md_path or j(self.root, "docs", "KNOBS.md")
        )
        self.ops_path = ops_path or j(self.src_dir, "kernels", "ops.py")
        self.ref_path = ref_path or j(self.src_dir, "kernels", "ref.py")
        self.autotune_path = (
            autotune_path or j(self.src_dir, "kernels", "autotune.py")
        )
        self.kernels_dir = kernels_dir or j(self.src_dir, "kernels")
        if sentinel_paths is not None:
            self.sentinel_paths = sentinel_paths
        else:
            core = j(self.src_dir, "core")
            self.sentinel_paths = tuple(
                sorted(self.py_files(self.kernels_dir))
            ) + tuple(
                j(core, f) for f in (
                    "storage.py", "bitset.py", "search.py", "edge_select.py",
                    "rng.py", "build.py", "index.py", "distributed.py",
                )
            )
        # the paper-system public surface: the index/search API, the
        # serving stack, the baselines/multiattr/distributed workloads, the
        # compile-cache placement every entry script calls, and the linter
        # itself. Deliberately NOT the dryrun/train harness —
        # that is the fence around the seed-vestigial model zoo (R6).
        self.entry_points = entry_points or (
            "repro.core", "repro.core.index", "repro.core.baselines",
            "repro.core.multiattr", "repro.core.distributed",
            "repro.serve.engine", "repro.serve.loop", "repro.serve.executor",
            "repro.kernels.ops", "repro.compressio", "repro.compile_cache",
            "repro.lint", "repro.lint.__main__",
        )
        self._source: dict[str, str] = {}
        self._tree: dict[str, ast.Module] = {}

    # -- cached IO ----------------------------------------------------------
    def source(self, path: str) -> str:
        path = os.path.abspath(path)
        if path not in self._source:
            with open(path, encoding="utf-8") as f:
                self._source[path] = f.read()
        return self._source[path]

    def tree(self, path: str) -> ast.Module:
        path = os.path.abspath(path)
        if path not in self._tree:
            self._tree[path] = ast.parse(self.source(path), filename=path)
        return self._tree[path]

    def relpath(self, path: str) -> str:
        return os.path.relpath(os.path.abspath(path), self.root).replace(
            os.sep, "/"
        )

    def py_files(self, *dirs: str) -> list[str]:
        out = []
        for d in dirs:
            if not os.path.isdir(d):
                continue
            for base, _dirnames, names in os.walk(d):
                out.extend(
                    os.path.join(base, f) for f in names
                    if f.endswith(".py")
                )
        return sorted(out)

    def finding(self, rule, path, node_or_line, message, slug) -> Finding:
        line = (
            node_or_line if isinstance(node_or_line, int)
            else getattr(node_or_line, "lineno", 0)
        )
        return Finding(rule, self.relpath(path), line, message, slug)


def suppressed(ctx: Context, f: Finding) -> bool:
    """True when the flagged source line carries ``# replint: allow[Rn]``."""
    if not f.line:
        return False
    try:
        lines = ctx.source(os.path.join(ctx.root, f.path)).splitlines()
        text = lines[f.line - 1]
    except (OSError, IndexError):
        return False
    m = _ALLOW_RE.search(text)
    if not m:
        return False
    rules = {t.strip() for t in m.group(1).split(",")}
    return "*" in rules or f.rule in rules


def run(ctx: Context, rule_ids=None) -> list[Finding]:
    """Run the requested rules (default: all) and drop inline-suppressed
    findings. Baseline handling is the caller's (``__main__``) job."""
    from repro.lint import rules as rules_pkg

    out: list[Finding] = []
    for mod in rules_pkg.ALL_RULES:
        if rule_ids and mod.RULE_ID not in rule_ids:
            continue
        out.extend(f for f in mod.check(ctx) if not suppressed(ctx, f))
    return sorted(out, key=lambda f: (f.rule, f.path, f.line, f.slug))


def load_baseline(path: str) -> dict[str, str]:
    """``{finding key: one-line reason}`` from the committed baseline."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    out = {}
    for entry in data.get("entries", []):
        key, reason = entry["key"], entry.get("reason", "")
        if not reason.strip():
            raise ValueError(
                f"lint baseline {path}: entry {key!r} has no reason — "
                f"every baselined finding must carry a one-line "
                f"justification"
            )
        out[key] = reason
    return out


def save_baseline(path: str, entries: dict[str, str]) -> None:
    data = {
        "_comment": (
            "replint findings baseline (DESIGN.md §10). Every entry is a "
            "known, justified violation; python -m repro.lint fails on "
            "findings not listed here, --strict also fails on stale "
            "entries, and benchmarks/ci_gate.py hard-fails if this file "
            "grows. Shrink it by fixing findings, never grow it casually."
        ),
        "entries": [
            {"key": k, "reason": entries[k]} for k in sorted(entries)
        ],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2)
        f.write("\n")

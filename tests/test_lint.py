"""replint (src/repro/lint) — DESIGN.md §10.

Per rule: one *violating* fixture asserting the rule demonstrably fires
(with the expected stable finding key) and one *clean* fixture asserting
it stays quiet. Plus the framework pieces (inline suppression, baseline
reasons, stable keys) and the self-run: the repo itself must be clean
under ``python -m repro.lint --strict``.

Fixtures are tmp trees handed to :class:`repro.lint.Context` via its
path overrides — no repo copying, and each rule runs against exactly the
files it claims to check.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.lint import (
    Context, Finding, load_baseline, run, save_baseline, suppressed,
)
from repro.lint.rules import (
    ALL_RULES, r1_knob_registry, r2_dispatch_contract, r3_jit_discipline,
    r4_vmem_budget, r5_sentinel_discipline, r6_reachability,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KNOBS_STUB = textwrap.dedent(
    '''
    class _K:
        def __init__(self, name):
            self.name = name
    REGISTRY = (_K("REPRO_GOOD"), _K("REPRO_MY_IMPL"))
    def generate_markdown():
        return "# knobs\\n"
    '''
)


def write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(textwrap.dedent(content))
    return path


def slugs(findings):
    return {f.slug for f in findings}


# ---------------------------------------------------------------------------
# R1 knob-registry
# ---------------------------------------------------------------------------

def _r1_ctx(tmp_path, module_src, knobs_md="# knobs\n"):
    root = str(tmp_path)
    write(root, "pyproject.toml", "")
    knobs = write(root, "pkg/knobs.py", KNOBS_STUB)
    write(root, "pkg/mod.py", module_src)
    md = write(root, "KNOBS.md", knobs_md)
    return Context(
        root=root, src_dir=os.path.join(root, "pkg"), extra_dirs=(),
        tests_dir=os.path.join(root, "tests"), knobs_path=knobs,
        knobs_md_path=md, sentinel_paths=(),
    )


def test_r1_fires_on_raw_env_and_unregistered_knob(tmp_path):
    ctx = _r1_ctx(
        tmp_path,
        """
        import os
        TOKEN = os.environ.get("REPRO_MYSTERY")
        OTHER = os.environ["REPRO_GOOD"]
        """,
    )
    got = slugs(r1_knob_registry.check(ctx))
    assert "raw-env:REPRO_MYSTERY" in got
    assert "raw-env:REPRO_GOOD" in got  # registered but read raw: still R1
    assert "unregistered:REPRO_MYSTERY" in got
    assert "knobs-md-drift" not in got


def test_r1_fires_on_knobs_md_drift(tmp_path):
    ctx = _r1_ctx(tmp_path, "X = 1\n", knobs_md="# stale, hand-edited\n")
    assert "knobs-md-drift" in slugs(r1_knob_registry.check(ctx))


def test_r1_clean(tmp_path):
    ctx = _r1_ctx(
        tmp_path,
        """
        from pkg import knobs
        LEVEL = knobs.get_int("REPRO_GOOD")
        """,
    )
    assert not list(r1_knob_registry.check(ctx))


# ---------------------------------------------------------------------------
# R2 dispatch-contract
# ---------------------------------------------------------------------------

def _r2_ctx(tmp_path, ops_src, ref_src="", test_src=None):
    root = str(tmp_path)
    write(root, "pyproject.toml", "")
    ops = write(root, "pkg/ops.py", ops_src)
    ref = write(root, "pkg/ref.py", ref_src)
    knobs = write(root, "pkg/knobs.py", KNOBS_STUB)
    if test_src is not None:
        write(root, "tests/test_myop.py", test_src)
    return Context(
        root=root, src_dir=os.path.join(root, "pkg"), extra_dirs=(),
        tests_dir=os.path.join(root, "tests"), ops_path=ops, ref_path=ref,
        knobs_path=knobs, sentinel_paths=(),
    )


def test_r2_fires_on_missing_contract(tmp_path):
    ctx = _r2_ctx(
        tmp_path,
        # exported op with no ref contract, pallas-only tokens, an
        # unregistered knob, and no test naming it
        """
        __all__ = ["myop"]
        def _check_impl(op, impl, allowed):
            if impl not in allowed:
                raise ValueError(impl)
        def myop(x, impl="auto"):
            if impl == "auto":
                impl = "pallas" if x else "REPRO_SECRET_IMPL"
            _check_impl("myop", impl, {"pallas"})
            return x
        """,
    )
    got = slugs(r2_dispatch_contract.check(ctx))
    assert "myop:no-oracle" in got
    assert "myop:no-ref-contract" in got
    assert "myop:unregistered-knob:REPRO_SECRET_IMPL" in got
    assert "myop:no-test" in got


def test_r2_fires_on_missing_check_impl(tmp_path):
    ctx = _r2_ctx(
        tmp_path,
        """
        __all__ = ["myop"]
        def myop(x, impl="auto"):
            return x
        """,
    )
    assert "myop:no-check-impl" in slugs(r2_dispatch_contract.check(ctx))


def test_r2_clean(tmp_path):
    ctx = _r2_ctx(
        tmp_path,
        """
        __all__ = ["myop", "default_impl"]
        from pkg import ref as _ref
        def _check_impl(op, impl, allowed):
            if impl not in allowed:
                raise ValueError(impl)
        def default_impl(kind=None):
            return "xla"
        def myop(x, impl="auto"):
            if impl == "auto":
                impl = default_impl("my")
            _check_impl("myop", impl, {"pallas", "xla"})
            return _ref.myop(x)
        """,
        ref_src="def myop(x):\n    return x\n",
        test_src="def test_myop():\n    assert 'myop'\n",
    )
    assert not list(r2_dispatch_contract.check(ctx))


# ---------------------------------------------------------------------------
# R3 jit-discipline
# ---------------------------------------------------------------------------

def _r3_ctx(tmp_path, src):
    root = str(tmp_path)
    write(root, "pyproject.toml", "")
    write(root, "pkg/core.py", src)
    return Context(
        root=root, src_dir=os.path.join(root, "pkg"), extra_dirs=(),
        sentinel_paths=(),
    )


def test_r3_fires_on_tracer_coercion_and_mutable_static(tmp_path):
    ctx = _r3_ctx(
        tmp_path,
        """
        import functools
        import jax

        @jax.jit
        def _bad_jit(x):
            return float(x) + x.sum().item()

        @functools.partial(jax.jit, static_argnames=("cfg", "ghost"))
        def _bad2_jit(x, cfg=[1]):
            import numpy as np
            return np.asarray(x)
        """,
    )
    got = slugs(r3_jit_discipline.check(ctx))
    assert any(s.startswith("_bad_jit:coerce-float") for s in got)
    assert any(s.startswith("_bad_jit:item") for s in got)
    assert "_bad2_jit:static-mutable:cfg" in got
    assert "_bad2_jit:static-unknown:ghost" in got
    assert any(s.startswith("_bad2_jit:np-asarray") for s in got)


def test_r3_clean_shapes_and_statics(tmp_path):
    ctx = _r3_ctx(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("m",))
        def _ok_jit(x, m=4):
            k = int(x.shape[0])          # shape-routed: fine
            scale = float(m)             # static arg: fine
            return x * scale + k

        _also_ok = functools.partial(jax.jit, static_argnames=("m",))(_ok_jit)
        """,
    )
    assert not list(r3_jit_discipline.check(ctx))


# ---------------------------------------------------------------------------
# R4 vmem-budget
# ---------------------------------------------------------------------------

def test_r4_fires_when_budget_shrinks(monkeypatch):
    # the real kernels, the real CANDIDATES grid, a 256 KiB budget: the
    # evaluator must produce totals big enough to trip it — proof the
    # rule genuinely evaluates shapes rather than skipping them
    monkeypatch.setattr(r4_vmem_budget, "BUDGET_BYTES", 256 << 10)
    got = list(r4_vmem_budget.check(Context(root=REPO)))
    assert got, "shrunken budget must trip candidates"
    assert any("hop_kernel_call" in f.slug for f in got)
    assert all("uneval" not in f.slug for f in got)
    # finding keys name the exact candidate so the baseline stays stable
    assert any("block_b=" in f.slug for f in got)


def test_r4_covers_every_candidate_grid():
    from repro.lint import astutil

    ctx = Context(root=REPO)
    grid = astutil.eval_module_constant(
        ctx.tree(ctx.autotune_path), "CANDIDATES", ctx.autotune_path
    )
    mapped = {
        k for _, _, kinds, _, _ in r4_vmem_budget.KERNELS for k in kinds
    }
    assert set(grid) <= mapped


def test_r4_fires_on_unmapped_kind_and_uncovered_kernel(tmp_path,
                                                       monkeypatch):
    root = str(tmp_path)
    write(root, "pyproject.toml", "")
    autotune = write(
        root, "pkg/kernels/autotune.py",
        'CANDIDATES = {"toy": [{"block": 8}]}\n',
    )
    write(
        root, "pkg/kernels/rogue.py",
        """
        import jax.experimental.pallas as pl
        def rogue_kernel_call(x):
            return pl.pallas_call(None)(x)
        """,
    )
    ctx = Context(
        root=root, src_dir=os.path.join(root, "pkg"), extra_dirs=(),
        autotune_path=autotune,
        kernels_dir=os.path.join(root, "pkg/kernels"), sentinel_paths=(),
    )
    got = slugs(r4_vmem_budget.check(ctx))
    assert "unmapped-kind:toy" in got
    assert "uncovered:rogue.py" in got
    # and the rule's own kernel table is missing from this tree
    assert any(s.startswith("missing-module:") for s in got)


def test_r4_prices_each_codec_at_its_vmem_tile():
    """A gathered 8-row block costs its dtype's whole sublane tile, so a
    per-slot block of a bf16 or int8 table is priced 8x a one-row f32
    slot — the blow-up that kept 16/8-bit tables from fitting VMEM."""
    import ast

    env = r4_vmem_budget._gather_env(
        r4_vmem_budget.PROBES["deployment"],
        {"block_b": 8, "block_m": 128, "window": 8}, "bf16")
    assert env["tbl"].shape[1:] == (8, 128) and env["tbl"].dtype == 2

    def cost(expr, dtype):
        return r4_vmem_budget._nbytes(
            ast.parse(expr, mode="eval").body,
            ast.parse(dtype, mode="eval").body, env)

    row = cost("(bb * bm, tbl.shape[-1])", "jnp.float32")
    block = cost("(bb * bm, _rows.ROWS, tbl.shape[-1])", "tbl.dtype")
    assert row == 8 * 128 * 128 * 4
    assert block == 8 * row
    for codec in ("f32", "bf16", "int8", "pq"):
        assert codec in r4_vmem_budget.TABLES["hop"]


def test_r4_clean_on_repo():
    got = [
        f for f in r4_vmem_budget.check(Context(root=REPO))
    ]
    assert not got, [f.render() for f in got]


# ---------------------------------------------------------------------------
# R5 sentinel-discipline
# ---------------------------------------------------------------------------

def _r5_ctx(tmp_path, src):
    root = str(tmp_path)
    write(root, "pyproject.toml", "")
    path = write(root, "pkg/store.py", src)
    return Context(
        root=root, src_dir=os.path.join(root, "pkg"), extra_dirs=(),
        sentinel_paths=(path,),
    )


def test_r5_fires_on_dtype_max_and_magic(tmp_path):
    ctx = _r5_ctx(
        tmp_path,
        """
        import numpy as np

        def invalid(ids):
            lim = ids == np.iinfo(np.int16).max
            magic = ids == 32767
            filled = np.where(ids < 0, 2147483647, ids)
            return lim | magic, filled
        """,
    )
    got = slugs(r5_sentinel_discipline.check(ctx))
    assert "iinfo-max" in got
    assert "magic:32767" in got
    assert "magic-fill:2147483647" in got


def test_r5_clean_minus_one_and_iinfo_min(tmp_path):
    ctx = _r5_ctx(
        tmp_path,
        """
        import numpy as np

        def invalid(ids):
            # -1 is THE sentinel; iinfo(...).min priority masking is fine
            mask = ids == -1
            prio = np.where(mask, np.iinfo(np.int32).min, ids)
            return mask, prio
        """,
    )
    assert not list(r5_sentinel_discipline.check(ctx))


def test_r5_inline_allow_suppresses(tmp_path):
    ctx = _r5_ctx(
        tmp_path,
        """
        import numpy as np

        def fits(n):
            return n <= np.iinfo(np.int16).max  # replint: allow[R5] capacity
        """,
    )
    found = list(r5_sentinel_discipline.check(ctx))
    assert found and all(suppressed(ctx, f) for f in found)


# ---------------------------------------------------------------------------
# R6 import-reachability
# ---------------------------------------------------------------------------

def _r6_ctx(tmp_path, files, entry_points=("pkg",)):
    root = str(tmp_path)
    write(root, "pyproject.toml", "")
    for rel, content in files.items():
        write(root, rel, content)
    return Context(
        root=root, src_dir=os.path.join(root, "pkg"), extra_dirs=(),
        entry_points=entry_points, sentinel_paths=(),
    )


def test_r6_fires_on_dead_module(tmp_path):
    ctx = _r6_ctx(tmp_path, {
        "pkg/__init__.py": "from pkg import used\n",
        "pkg/used.py": "X = 1\n",
        "pkg/dead.py": "Y = 2\n",
    })
    assert slugs(r6_reachability.check(ctx)) == {"pkg.dead"}


def test_r6_clean_when_wired(tmp_path):
    ctx = _r6_ctx(tmp_path, {
        "pkg/__init__.py": "from pkg import used\n",
        "pkg/used.py": "from . import dead\nX = 1\n",  # relative import
        "pkg/dead.py": "Y = 2\n",
    })
    assert not list(r6_reachability.check(ctx))


def test_r6_fires_on_missing_entry_point(tmp_path):
    ctx = _r6_ctx(
        tmp_path, {"pkg/__init__.py": "X = 1\n"},
        entry_points=("pkg", "pkg.ghost"),
    )
    assert "missing-entry:pkg.ghost" in slugs(r6_reachability.check(ctx))


# ---------------------------------------------------------------------------
# framework: keys, baseline, suppression
# ---------------------------------------------------------------------------

def test_finding_keys_are_line_independent():
    a = Finding("R6", "src/x.py", 10, "msg", "pkg.dead")
    b = Finding("R6", "src/x.py", 99, "other msg", "pkg.dead")
    assert a.key == b.key == "R6:src/x.py:pkg.dead"


def test_baseline_requires_reasons(tmp_path):
    path = os.path.join(str(tmp_path), "b.json")
    with open(path, "w") as f:
        json.dump({"entries": [{"key": "R6:x:y", "reason": ""}]}, f)
    with pytest.raises(ValueError, match="no reason"):
        load_baseline(path)
    save_baseline(path, {"R6:x:y": "because"})
    assert load_baseline(path) == {"R6:x:y": "because"}


def test_every_rule_declares_metadata():
    ids = [m.RULE_ID for m in ALL_RULES]
    assert ids == ["R1", "R2", "R3", "R4", "R5", "R6"]
    for mod in ALL_RULES:
        assert mod.TITLE and mod.SUMMARY and callable(mod.check)


# ---------------------------------------------------------------------------
# the self-run: this repo is clean under --strict
# ---------------------------------------------------------------------------

def test_repo_is_clean_in_process():
    ctx = Context(root=REPO)
    baseline = load_baseline(os.path.join(REPO, "lint_baseline.json"))
    findings = run(ctx)
    new = [f for f in findings if f.key not in baseline]
    stale = set(baseline) - {f.key for f in findings}
    assert not new, "new findings:\n" + "\n".join(f.render() for f in new)
    assert not stale, f"stale baseline entries: {sorted(stale)}"


def test_repo_is_clean_strict_cli():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.lint", "--strict"],
        cwd=REPO,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new" in proc.stdout

"""R4 vmem-budget: every Pallas kernel's VMEM footprint — declared
scratch (``pltpu.VMEM``) plus double-buffered in/out blocks
(``pl.BlockSpec``) — must fit the ~16 MiB/core VMEM budget DESIGN.md
claims, for **every** candidate in the ``kernels/autotune.py``
``CANDIDATES`` grid, at both the autotune probe shape and a
production-scale shape. A tile that compiles at the bench probe but
OOMs VMEM at 100k rows is exactly the failure this rule front-runs.

How it works (see ``astutil.eval_shape``): the rule lifts the *actual*
shape expressions out of each ``*_kernel_call`` body — no parallel
bookkeeping of shapes that could drift — and evaluates them against a
symbol environment computed from the probe shape and the candidate
params using the kernels' own tiling formulas. ``SMEM``/``ANY`` specs
and DMA semaphores don't occupy VMEM blocks and are skipped; block
elements are costed at 4 bytes (f32/int32 worst case) and in/out blocks
are doubled for pipelining double-buffering. Every array is priced at
its VMEM tile footprint: the minor dim rounds up to 128 lanes and the
second-minor to the dtype's sublane tile (8 rows of 32-bit, 16 of
16-bit, 32 of 8-bit). A kernel that gathers table rows is checked once
per table codec its autotune kind serves (``TABLES``), each with the
shape, itemsize and 8-row blocking ``kernels/rowdma.py::row_table``
gives that codec. Compiler temporaries are not priced;
``tests/test_tpu_compile.py`` compiles the defaults for a real v5e. An
expression the evaluator cannot reduce is itself a finding, so a new
shape idiom in a kernel forces this rule (and its env) to be taught
about it rather than silently passing.

Completeness is checked both ways: every ``CANDIDATES`` kind must map
to a kernel, and every module under ``kernels/`` that calls
``pl.pallas_call`` must be covered by this rule's kernel table.
"""
from __future__ import annotations

import ast
import os

from repro.lint import astutil
from repro.lint.astutil import SimpleNamespace as NS

RULE_ID = "R4"
TITLE = "vmem-budget"
SUMMARY = "Pallas scratch+blocks fit 16 MiB VMEM for every autotune candidate"

# DESIGN.md's stated per-core VMEM budget (TPU VMEM is ~16 MB/core).
BUDGET_BYTES = 16 << 20

# itemsizes standing in for jnp dtypes in shape/dtype expressions
_DTYPES = NS(
    int32=4, uint32=4, float32=4, int16=2, uint16=2, bfloat16=2,
    float16=2, int8=1, uint8=1, bool_=1,
)

# Probe shapes. "autotune" mirrors benchmarks/hotpath.py::bench_autotune;
# "production" is the acceptance-scale workload (100k rows, d=128) with
# generous beam/candidate widths so the check documents headroom.
PROBES = {
    "autotune": dict(
        B=8, n=4096, d=32, m=8, W=4, m_out=8, C=64, M=64,
        Sq=128, Skv=128, Dh=64,
    ),
    "production": dict(
        B=64, n=100_000, d=128, m=16, W=32, m_out=16, C=256, M=512,
        Sq=2048, Skv=2048, Dh=128,
    ),
    # deployment scale, n = 2^20, where the visited bitset rows (128 KiB
    # per query) dominate the hop's blocks; beam width as generous as the
    # production probe's, C = m + ef_construction as a build level prunes
    "deployment": dict(
        B=64, n=1 << 20, d=128, m=16, W=32, m_out=16, C=80, M=512,
        Sq=2048, Skv=2048, Dh=128,
    ),
}

# Table codecs each row-gathering autotune kind serves: the f32/bf16
# float tables share "gather_dist", the int8/PQ codecs "gather_dist_codec"
# (``kernels/ops.py::gather_dist``); hop and prune take every codec.
TABLES = {
    "hop": ("f32", "bf16", "int8", "pq"),
    "gather_dist": ("f32", "bf16"),
    "gather_dist_codec": ("int8", "pq"),
    "prune": ("f32", "bf16", "int8", "pq"),
}


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _layers(n: int) -> int:
    # logn = ceil(log2 n) (the index depth), layers = logn + 1
    return max(1, (max(int(n), 2) - 1).bit_length()) + 1


def _pq(d: int) -> tuple[int, int]:
    # worst-case PQ geometry for the aux codebook input: dsub=8 lanes
    dsub = 8
    return max(1, d // dsub), dsub


def _row_table(p, codec):
    """The table as ``rowdma.row_table`` hands it to a kernel: lanes
    padded to 128; a table that is not one 32-bit lane tile wide viewed as
    ``[n/8, 8, w]`` 8-row blocks."""
    itemsize = {"f32": 4, "bf16": 2, "int8": 1, "pq": 1}[codec]
    w = 128 if codec == "pq" else _ceil_to(p["d"], 128)
    if itemsize == 4 and w == 128:
        return NS(shape=(p["n"], w), dtype=itemsize)
    return NS(shape=(-(-p["n"] // 8), 8, w), dtype=itemsize)


def _table_env(p, codec):
    pq_m, dsub = _pq(p["d"])
    tbl = _row_table(p, codec)
    return {
        "tp": tbl, "tbl": tbl, "aux": NS(shape=(pq_m * 256, dsub)),
        "_rows": NS(ROWS=8), "jnp": _DTYPES,
    }


def _hop_env(p, c, codec):
    layers = _layers(p["n"])
    K = layers * p["m"]
    bb = min(c["block_b"], max(8, p["B"]))
    return {
        "bb": bb, "W": p["W"], "K": K, "WM": p["W"] * p["m_out"],
        "dp": _ceil_to(p["d"], 128),
        "wp": _ceil_to(-(-p["n"] // 32), 128), "Rw": -(-K // 128),
        "win": max(1, min(c["window"], bb * p["W"])),
        "m_out": p["m_out"], "window": c["window"],
        **_table_env(p, codec),
    }


def _gather_env(p, c, codec):
    bb = min(c["block_b"], max(8, p["B"]))
    bm = -(-p["M"] // 8) * 8 if p["M"] <= 128 else min(c["block_m"], p["M"])
    return {
        "bb": bb, "bm": bm, "dp": _ceil_to(p["d"], 128),
        "window": c["window"], "win": min(c["window"], bb * bm),
        **_table_env(p, codec),
    }


def _edge_env(p, c, codec=None):
    K = _layers(p["n"]) * p["m"]
    return {
        "bf": c["block_f"], "K": K, "edges": NS(R=-(-K // 128)),
        "m_out": p["m_out"], "window": c["window"], "jnp": _DTYPES,
    }


def _prune_env(p, c, codec):
    bb = min(c["block_b"], max(8, p["B"]))
    C = _ceil_to(p["C"], 8)
    return {
        "bb": bb, "C": C, "m": p["m"], "window": c["window"],
        "win": min(c["window"], bb * C), **_table_env(p, codec),
    }


def _dist_env(p, c, codec=None):
    return {
        "bq": min(c["block_q"], max(8, p["B"])),
        "bn": min(c["block_n"], max(8, p["n"])),
        "bk": min(c["block_k"], _ceil_to(p["d"], 128)),
        "jnp": _DTYPES,
    }


def _flash_env(p, c, codec=None):
    return {
        "bq": min(c["block_q"], max(8, p["Sq"])),
        "bk": min(c["block_k"], max(8, p["Skv"])),
        "Dh": p["Dh"], "jnp": _DTYPES,
    }


# kernel table: module -> call fn, autotune kinds (None = no grid entry,
# checked at its wrapper-default candidate), env builder
KERNELS = (
    ("hop.py", "hop_kernel_call", ("hop",), _hop_env, None),
    ("gather_distance.py", "gather_distance_kernel_call",
     ("gather_dist", "gather_dist_codec"), _gather_env, None),
    ("edge_select.py", "edge_select_kernel_call", ("edge_select",),
     _edge_env, None),
    ("prune.py", "prune_kernel_call", ("prune",), _prune_env, None),
    ("distance.py", "pairwise_dist_kernel_call", (None,), _dist_env,
     [{"block_q": 128, "block_n": 128, "block_k": 512}]),
    ("flash_attention.py", "flash_attention_kernel_call", (None,),
     _flash_env, [{"block_q": 128, "block_k": 128}]),
)


def _spec_kind(call: ast.Call) -> str | None:
    """'scratch' | 'block' | None(skip) for a Call node inside the fn."""
    name = astutil.dotted(call.func)
    if "SemaphoreType" in name:
        return None
    if name.endswith(".VMEM") or name == "VMEM":
        return "scratch"
    if name.endswith(".BlockSpec") or name == "BlockSpec":
        for kw in call.keywords:
            if kw.arg == "memory_space":
                space = astutil.dotted(kw.value)
                if space.endswith(("SMEM", "ANY")):
                    return None
        if not call.args:
            return None  # memory_space-only spec
        return "block"
    return None


def _extract(fn: ast.AST):
    """(kind, shape_expr, dtype_expr|None) for every VMEM-occupying
    declaration in the kernel-call body, across all codec branches
    (the union is a conservative superset of any one branch)."""
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        kind = _spec_kind(node)
        if kind == "scratch":
            dtype = node.args[1] if len(node.args) > 1 else None
            out.append((kind, node.args[0], dtype))
        elif kind == "block":
            out.append((kind, node.args[0], None))
    return out


def _nbytes(shape_expr, dtype_expr, env) -> int:
    shape = astutil.eval_shape(shape_expr, env)
    if isinstance(shape, (int, float)):
        shape = (shape,)
    shape = list(shape)
    for e in shape:
        if not isinstance(e, int) or e <= 0:
            raise astutil.EvalError(
                f"non-positive/non-int dim {e!r} in "
                f"{ast.unparse(shape_expr)}"
            )
    itemsize = 4
    if dtype_expr is not None:
        itemsize = astutil.eval_shape(dtype_expr, env)
        if not isinstance(itemsize, int) or itemsize not in (1, 2, 4):
            raise astutil.EvalError(
                f"dtype {ast.unparse(dtype_expr)} -> {itemsize!r}"
            )
    # the VMEM tile: 128 lanes by 8 rows of 32 bits (16 of 16-bit, ...)
    shape[-1] = _ceil_to(shape[-1], 128)
    if len(shape) > 1:
        shape[-2] = _ceil_to(shape[-2], 8 * 4 // itemsize)
    total = itemsize
    for e in shape:
        total *= e
    return total


def check(ctx):
    try:
        candidates = astutil.eval_module_constant(
            ctx.tree(ctx.autotune_path), "CANDIDATES", ctx.autotune_path
        )
    except astutil.EvalError as e:
        yield ctx.finding(
            RULE_ID, ctx.autotune_path, 0,
            f"cannot read the CANDIDATES grid statically: {e}",
            "no-candidates",
        )
        return

    covered_kinds, covered_files = set(), set()
    for fname, call_name, kinds, env_fn, defaults in KERNELS:
        path = os.path.join(ctx.kernels_dir, fname)
        covered_files.add(os.path.abspath(path))
        if not os.path.exists(path):
            yield ctx.finding(
                RULE_ID, ctx.kernels_dir, 0,
                f"R4 kernel table names {fname} but kernels/ has no such "
                f"module — update KERNELS in this rule",
                f"missing-module:{fname}",
            )
            continue
        fn = astutil.top_level_functions(ctx.tree(path)).get(call_name)
        if fn is None:
            yield ctx.finding(
                RULE_ID, path, 0,
                f"expected Pallas entry point {call_name}() not found — "
                f"update KERNELS in this rule",
                f"missing-call:{call_name}",
            )
            continue
        decls = _extract(fn)
        if not decls:
            yield ctx.finding(
                RULE_ID, path, fn,
                f"{call_name} declares no VMEM blocks or scratch — "
                f"extraction found nothing to budget (rule out of sync?)",
                f"{call_name}:no-decls",
            )
            continue

        for kind in kinds:
            grid = defaults if kind is None else candidates.get(kind)
            label = kind or fname[:-3]
            covered_kinds.add(kind)
            if grid is None:
                yield ctx.finding(
                    RULE_ID, ctx.autotune_path, 0,
                    f"R4 kernel table maps {fname} to autotune kind "
                    f"{kind!r} but CANDIDATES has no such kind",
                    f"unknown-kind:{kind}",
                )
                continue
            runs = [
                (probe_name, probe, codec, cand)
                for probe_name, probe in PROBES.items()
                for codec in TABLES.get(kind, (None,))
                for cand in grid
            ]
            for probe_name, probe, codec, cand in runs:
                where = probe_name + (f"/{codec}" if codec else "")
                try:
                    env = env_fn(probe, cand, codec)
                    total = sum(
                        _nbytes(s, d, env) * (2 if k == "block" else 1)
                        for k, s, d in decls
                    )
                except astutil.EvalError as e:
                    yield ctx.finding(
                        RULE_ID, path, fn,
                        f"{call_name}: cannot evaluate a VMEM shape "
                        f"for {label}/{where} {cand}: {e} — "
                        f"teach r4_vmem_budget the new idiom",
                        f"{call_name}:uneval:{e}",
                    )
                    break
                if total > BUDGET_BYTES:
                    cd = ",".join(f"{k}={cand[k]}" for k in sorted(cand))
                    yield ctx.finding(
                        RULE_ID, path, fn,
                        f"{call_name}: candidate {{{cd}}} needs "
                        f"{total / 2**20:.2f} MiB VMEM at the {where} "
                        f"shape — over the {BUDGET_BYTES >> 20} MiB budget "
                        f"DESIGN.md claims; shrink the tile or drop it "
                        f"from CANDIDATES[{label!r}]",
                        f"{call_name}:{label}:{where}:{cd}",
                    )

    for kind in candidates:
        if kind not in covered_kinds:
            yield ctx.finding(
                RULE_ID, ctx.autotune_path, 0,
                f"CANDIDATES kind {kind!r} is not mapped to any kernel in "
                f"r4_vmem_budget.KERNELS — its grid is unchecked",
                f"unmapped-kind:{kind}",
            )

    for path in ctx.py_files(ctx.kernels_dir):
        if os.path.abspath(path) in covered_files:
            continue
        if any(
            isinstance(n, ast.Attribute) and n.attr == "pallas_call"
            for n in ast.walk(ctx.tree(path))
        ):
            yield ctx.finding(
                RULE_ID, path, 0,
                f"{os.path.basename(path)} calls pl.pallas_call but is "
                f"not covered by r4_vmem_budget.KERNELS — its VMEM "
                f"footprint is unchecked",
                f"uncovered:{os.path.basename(path)}",
            )

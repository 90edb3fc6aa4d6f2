"""Block-size / pipeline-depth autotuner for the Pallas kernels.

Every Pallas kernel in this repo exposes two knobs the compiler cannot pick
for us: the row-tile size (``block_b`` / ``block_f`` / ``block_m``: how many
queries or frontier nodes one grid program owns, bounded by VMEM residency)
and the DMA pipeline depth (``window``: how many row copies stay in flight).
The right values depend on the host — interpret-mode CPU wants small tiles,
a real TPU wants the MXU fed — so, like the build-path chunk auto-tuner
(``core/build.py::auto_chunk``), picks are *measured*, not hardcoded:

  * ``CANDIDATES[kind]`` is the search space per kernel
    ("hop" | "gather_dist" | "gather_dist_codec" | "edge_select" |
    "prune" — the codec kind retunes the decode tile for quantized
    tables, DESIGN.md §9);
  * ``autotune(kind, run)`` times ``run(**params)`` for every candidate
    (min over ``iters`` after a warmup call that also pays the compile)
    and returns a record ``{kind, best, best_ms, candidates: [...]}``;
  * ``benchmarks/hotpath.py`` drives it on representative probe shapes,
    installs the winners via ``set_pick``, and persists the records in
    ``artifacts/BENCH_hotpath.json`` under ``autotune`` —
    ``benchmarks/ci_gate.py`` then flags pick drift between the committed
    record and a fresh smoke run (malformed/missing → hard fail, a changed
    pick → soft warn, since timing is host-dependent);
  * the ``kernels/ops.py`` wrappers merge ``get_pick(kind)`` underneath any
    explicit ``**block_kw`` on their Pallas branches, so installed picks
    apply process-wide while caller overrides still win.

Picks only ever feed jit-static arguments, so installing one changes which
compiled executable serves a call; serving installs picks before
``SearchExecutor.warmup()`` (or never), keeping the zero-post-warmup-compile
guarantee intact.
"""
from __future__ import annotations

import time

import jax

__all__ = [
    "CANDIDATES", "autotune", "set_pick", "get_pick", "all_picks",
    "clear_picks", "install",
]

# search spaces: small grids of tiles the TPU v5e compiler accepts at
# n = 2^20, d = 128 for every table codec the kind serves (replint R4
# prices them; tests/test_tpu_compile.py compiles the defaults). Row tiles
# are multiples of 8, the sublane tile: a block of 2 or 4 rows breaks
# Mosaic's (8, 128) block rule, and block_m is a multiple of the 128
# lanes. The hop stays at 8 query rows: at 16 its two bitset blocks alone
# (16 x 128 KiB, in and out, double-buffered) take 8 MiB of VMEM, and
# with a wide beam (W = 32) the program overflows the 16 MiB.
CANDIDATES = {
    "hop": [
        {"block_b": 8, "window": w} for w in (4, 8, 16)
    ],
    "gather_dist": [
        {"block_b": bb, "block_m": bm, "window": w}
        for bb in (8, 16) for bm in (128, 256) for w in (8, 16)
    ],
    # codec tables (int8/PQ) change the DMA row dtype (8-row blocks picked
    # on arrival, kernels/rowdma.py) and add in-register decode work, so
    # the optimal tile differs from the f32 table's — tuned as its own
    # kind (DESIGN.md §9)
    "gather_dist_codec": [
        {"block_b": bb, "block_m": bm, "window": w}
        for bb in (8, 16) for bm in (128, 256) for w in (8, 16, 32)
    ],
    "edge_select": [
        {"block_f": bf, "window": w}
        for bf in (8, 16) for w in (4, 8)
    ],
    "prune": [
        {"block_b": bb, "window": w}
        for bb in (8, 16) for w in (8, 16)
    ],
}

_PICKS: dict[str, dict] = {}


def set_pick(kind: str, params: dict) -> None:
    """Install ``params`` as the process-wide default block kwargs for
    ``kind``'s Pallas branch (caller-explicit kwargs still override)."""
    if kind not in CANDIDATES:
        raise ValueError(
            f"autotune: unknown kernel kind {kind!r} "
            f"(expected one of {sorted(CANDIDATES)})"
        )
    _PICKS[kind] = dict(params)


def get_pick(kind: str) -> dict:
    return dict(_PICKS.get(kind, {}))


def all_picks() -> dict:
    return {k: dict(v) for k, v in _PICKS.items()}


def clear_picks() -> None:
    _PICKS.clear()


def install(picks: dict) -> None:
    """Install a ``{kind: params}`` mapping (e.g. the ``autotune`` section
    of a committed BENCH artifact) wholesale."""
    for kind, params in picks.items():
        set_pick(kind, params)


def _time_ms(run, params, iters):
    out = run(**params)                     # pays compile + correctness
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run(**params))
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def autotune(kind: str, run, *, iters: int = 3, candidates=None) -> dict:
    """Time ``run(**params)`` over the candidate grid and return the record.

    ``run`` must execute the kernel on a fixed representative probe shape
    and return its (device) outputs; timing is min-of-``iters`` after one
    untimed warmup call per candidate. A candidate that raises (e.g. a tile
    too large for VMEM on a real TPU) is recorded with ``ms=None`` and
    skipped. The returned record is JSON-ready::

        {"kind": ..., "best": {...}, "best_ms": ...,
         "candidates": [{"params": {...}, "ms": ...}, ...]}
    """
    if candidates is None:
        if kind not in CANDIDATES:
            raise ValueError(
                f"autotune: unknown kernel kind {kind!r} "
                f"(expected one of {sorted(CANDIDATES)})"
            )
        candidates = CANDIDATES[kind]
    rows = []
    best_params, best_ms = None, float("inf")
    for params in candidates:
        try:
            ms = _time_ms(run, params, iters)
        except Exception:                   # tile does not fit / bad combo
            rows.append({"params": dict(params), "ms": None})
            continue
        rows.append({"params": dict(params), "ms": round(ms, 4)})
        if ms < best_ms:
            best_params, best_ms = dict(params), ms
    if best_params is None:
        raise RuntimeError(f"autotune: every {kind} candidate failed")
    return {
        "kind": kind,
        "best": best_params,
        "best_ms": round(best_ms, 4),
        "candidates": rows,
    }

"""The benchmark: served range-filtered k-NN queries on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

One process, one run of one cell of ``BENCHMARK.json``:

1. Set-up. Refuse to run without a TPU of a kind that ``peaks.json``
   lists, or with fewer chips than the cell asks for. Load the
   configuration's index from ``.cache/index`` (on a miss, build it from
   the configuration's collection and save it: the first run of a
   configuration in a checkout prints its build time), upload it through a
   ``SearchExecutor`` and warm the traffic's batch buckets at k = 10 from
   JAX's persistent compilation cache in ``.cache/jax``. Draw the requests
   from ``--seed``.
2. The window: ``--seconds`` of the traffic through
   ``AsyncServingEngine.submit``; with ``--trace 1`` under the profiler.
3. After it: read the device's peak memory, close the server, and compare
   a seeded sample of the answers with the exact reference
   (``check.py``).
4. Print each number compared beside its limit as the last lines of
   standard error, and as the last line of standard output one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
   end-to-end metrics, or with ``--trace 1`` its per-layer ones),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.

Configurations, traffic mixes and metric readers are files found by name:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` (a ``read(run)`` that returns a number, or None
where it finds nothing to read).
"""
from __future__ import annotations

import time

T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")
INDEX_CACHE = os.path.join(CACHE, "index")
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import check  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import devtrace  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - T_IMPORT


# ---------------------------------------------------------------------------
# what a cell is made of
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict      # the BENCHMARK.json entry
    cfg: dict           # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    end_to_end: list    # metric entries this cell reports, by --trace
    per_layer: list


def reports(metric: dict, name: str, spec: dict) -> bool:
    if "workloads" in metric:
        return name in metric["workloads"]
    if "moves" not in metric:           # an end-to-end metric
        return True
    moved = next(m for m in spec["end_to_end"] if m["name"] == metric["moves"])
    return reports(moved, name, spec)


def find_cell(spec: dict, name: str) -> Cell:
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return Cell(
        wl, load_json(os.path.join(ROOT, entry["file"])),
        load_json(os.path.join(BENCH, "traffic", wl["traffic"] + ".json")),
        [m for m in spec["end_to_end"] if reports(m, name, spec)],
        [m for m in spec["per_layer"] if reports(m, name, spec)],
    )


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def require_chip(chips: int):
    """The devices, and the peaks of their kind; exits without a result on
    anything but enough TPUs of a listed kind."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX's devices are "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return devs[:chips], peaks[kind]


def enable_compile_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def source_hash() -> str:
    """Hash of the program's sources: a change to them rebuilds."""
    h = hashlib.sha256()
    src = os.path.join(ROOT, "src", "repro")
    for dirpath, dirs, files in sorted(os.walk(src)):
        dirs.sort()
        for fn in sorted(files):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                h.update(os.path.relpath(p, src).encode())
                with open(p, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


BUILD_INPUTS = ("n", "dim", "attribute", "n_clusters", "data_seed", "dtype",
                "build")


def index_stem(cfg: dict, cache_dir: str = INDEX_CACHE) -> str:
    """Where the configuration's index is kept: keyed by what the build
    reads (``BUILD_INPUTS``) and the program's sources, so that a change to
    the search settings or the check reuses the index."""
    inputs = {k: cfg[k] for k in BUILD_INPUTS}
    key = hashlib.sha256(json.dumps(inputs, sort_keys=True).encode())
    return os.path.join(cache_dir, f"{cfg['name']}-{cfg['data_seed']}-"
                                   f"{key.hexdigest()[:8]}-{source_hash()}")


def build_index(cfg: dict, cache_dir: str = INDEX_CACHE) -> float:
    """Build the configuration's index from its collection and save it,
    with the collection's attribute values in rank order (the values
    requests carry) beside it. Returns the build's seconds."""
    from repro.core import BuildConfig, RangeGraphIndex, StorageConfig

    os.makedirs(cache_dir, exist_ok=True)
    stem = index_stem(cfg, cache_dir)
    vectors, attr = data.collection(cfg)
    t = time.monotonic()
    index = RangeGraphIndex.build(
        vectors, attr, BuildConfig(**cfg["build"]),
        storage=StorageConfig(vector_dtype=cfg["dtype"],
                              neighbor_dtype="int32"))
    built = time.monotonic() - t
    sorted_attr = np.sort(attr)
    for ext, save in ((".npy", lambda p: np.save(p, sorted_attr)),
                      (".idx", index.save)):
        tmp = f"{stem}.{os.getpid()}.tmp{ext}"
        save(tmp)
        os.replace(tmp, stem + ext)
    return built


def ensure_index(cfg_file: str, cfg: dict, chip: bool):
    """Build the index into the cache where it is not there yet. On the
    chip the build runs in a child process, before this one touches the
    device, so that its device memory never counts in this process's peak.
    Returns the build's seconds, or None where the cache held it."""
    stem = index_stem(cfg)
    if os.path.exists(stem + ".idx") and os.path.exists(stem + ".npy"):
        return None
    if not chip:
        return build_index(cfg)
    t = time.monotonic()
    done = subprocess.run([sys.executable,
                           os.path.join(BENCH, "build_index.py"), cfg_file])
    if done.returncode != 0 or not os.path.exists(stem + ".idx"):
        raise SystemExit(f"building the index of {cfg['name']} failed "
                         f"({done.returncode})")
    return time.monotonic() - t


def load_index(cfg: dict):
    """(index, attribute values in rank order) from the cache."""
    from repro.core import RangeGraphIndex

    stem = index_stem(cfg)
    return RangeGraphIndex.load(stem + ".idx"), np.load(stem + ".npy")


def make_executor(index, cfg: dict, traffic: dict, reqs):
    """A ``SearchExecutor`` warmed on every batch size the traffic's loop
    can form, at k: each size's bucket program is compiled (from the
    persistent cache after a cell's first run), and one batch of that size
    runs, so that what the executor does per size (the slicing of a padded
    bucket's results) is compiled too."""
    from repro.core import SearchConfig
    from repro.serve import SearchExecutor

    ex = SearchExecutor(index, SearchConfig(**cfg["search"]),
                        max_batch=cfg["max_batch"], warmup=False)
    sizes = traffic["warm_batch_sizes"]     # a list, or "all" of them
    if sizes == "all":
        sizes = range(1, ex.max_batch + 1)
    ex.warmup(batch_buckets=sorted({ex.batch_bucket(b) for b in sizes}),
              k_buckets=(traffic["k"],))
    for b in sizes:
        i = np.arange(b) % len(reqs)
        res = ex.search_ranks(reqs.vectors[i], reqs.L[i], reqs.R[i],
                              k=traffic["k"])
        np.asarray(res.ids)
    return ex


def make_requests(cfg, traffic, seed, seconds, sorted_attr, c):
    arrival = traffic["arrival"]
    if arrival["kind"] == "closed":
        due = None
        count = int(np.ceil(traffic["max_requests_per_s"] * seconds))
    elif arrival["kind"] == "poisson":
        due = traffic_mod.due_times(arrival, seed, seconds)
        count = len(due)
    else:
        raise ValueError(f"arrival kind {arrival['kind']!r}")
    return data.requests(cfg, traffic, seed, count, sorted_attr, c), due


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    seconds: float
    log: traffic_mod.Log
    t0: float
    t_end: float
    setup_s: float
    window_stats: dict
    compiles_in_window: int
    peak_bytes: int = 0
    peaks: dict | None = None
    trace: devtrace.Reduced | None = None
    checks: dict | None = None
    lateness_ms: tuple | None = None
    gc_pauses: "GcPauses | None" = None

    def hop_shape(self):
        """(B, W, m_out, d, itemsize) of one hop launch: B the batch
        bucket of the window's mean flush."""
        s, cfg = self.window_stats, self.cell.cfg
        B = 1 << int(np.ceil(np.log2(max(
            s["dispatched"] / max(s["flushes"], 1), 1))))
        W = min(cfg["search"].get("expand_width", 4),
                cfg["search"].get("ef", 64))
        return (B, W, cfg["build"]["m"], cfg["dim"],
                np.dtype(cfg["dtype"]).itemsize)


class GcPauses:
    """The garbage collector's passes while it is installed: how many, and
    their seconds in all and at most."""

    def __init__(self):
        self.count, self.total_s, self.max_s = 0, 0.0, 0.0
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        self.count += 1
        self.total_s += dt
        self.max_s = max(self.max_s, dt)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


async def serve_window(index, executor, cell, reqs, due, seconds,
                       annotate: bool):
    """Drive the window; returns (log, t0, t_end, engine stats, programs
    the executor compiled before the window closed)."""
    from jax.profiler import TraceAnnotation

    from repro.core import ServeConfig
    from repro.serve import AsyncServingEngine

    engine = AsyncServingEngine(
        index, executor=executor, serve=ServeConfig(**cell.traffic["serve"]),
        faults=False)
    lg = traffic_mod.Log(len(reqs), reqs.k)
    span = TraceAnnotation(devtrace.WINDOW_SPAN) if annotate else None
    compiles = executor.stats["compiles"]
    t0 = time.monotonic()
    t_end = t0 + seconds
    if span is not None:
        span.__enter__()

    async def close():
        await asyncio.sleep(max(t_end - time.monotonic(), 0.0))
        if span is not None:
            span.__exit__(None, None, None)
        return executor.stats["compiles"] - compiles

    closer = asyncio.create_task(close())
    try:
        arrival = cell.traffic["arrival"]
        if arrival["kind"] == "closed":
            await traffic_mod.closed_loop(engine, reqs, lg,
                                          int(arrival["clients"]), t0,
                                          seconds)
        else:
            await traffic_mod.open_loop(engine, reqs, lg, due, t0)
        compiled = await closer
    finally:
        await engine.aclose()
    return lg, t0, t_end, engine.stats, compiled


def run_window(index, executor, cell, reqs, due, seconds, trace_dir=None):
    """One window; with ``trace_dir`` under the profiler. Returns the Run
    without its checks."""
    # a full collection now, with set-up's garbage, rather than inside the
    # window; what survives it (about a million objects: the program, JAX,
    # the request stream's records) is frozen out of later collections,
    # each of which would otherwise walk all of it and stall the event
    # loop, as a long-running server that freezes after its start does
    gc.collect()
    gc.freeze()
    setup_s = process_age()
    with GcPauses() as pauses:
        if trace_dir is None:
            lg, t0, t_end, stats, compiled = asyncio.run(serve_window(
                index, executor, cell, reqs, due, seconds, False))
            reduced = None
        else:
            with devtrace.capture(trace_dir):
                lg, t0, t_end, stats, compiled = asyncio.run(serve_window(
                    index, executor, cell, reqs, due, seconds, True))
    gc.unfreeze()
    if trace_dir is not None:
        reduced = devtrace.Reduced(devtrace.load(trace_dir))
        shutil.rmtree(trace_dir)
    run = Run(cell, float(seconds), lg, t0, t_end, setup_s, stats,
              compiled, trace=reduced, gc_pauses=pauses)
    if due is not None:
        late = (lg.t_sent - lg.t_due)[:lg.sent] * 1e3
        run.lateness_ms = (float(np.median(late)), float(np.percentile(
            late, 99)), float(late.max())) if len(late) else None
    return run


def evaluate(run: Run, coll: reference.Collection, reqs, seed: int):
    idx = check.sample(run.log, seed, int(run.cell.traffic["check_sample"]))
    run.checks = check.compare(coll, reqs, run.log, idx, run.cell.cfg["check"])
    return run.checks


# ---------------------------------------------------------------------------
# output
# ---------------------------------------------------------------------------

def result(run: Run, devices, traced: bool) -> dict:
    metrics = {}
    for m in (run.cell.per_layer if traced else run.cell.end_to_end):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    lg = run.log
    attempted = int(np.sum(lg.t_due[:lg.sent] <= run.t_end))
    answered = int(np.sum((lg.outcome == traffic_mod.OK)
                          & (lg.t_due <= run.t_end)))
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(run.peak_bytes),
    }
    out = {"correct": all(c["ok"] for k, c in run.checks.items()
                          if not k.startswith("_")),
           "attempted": attempted, "failed": attempted - answered,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in run.checks.items() if not k.startswith("_")}
    return out


def report(run: Run, out: dict):
    lg = run.log
    if lg.sent == len(lg.outcome) and run.cell.traffic["arrival"]["kind"] \
            == "closed":
        print(f"the closed loop sent all {lg.sent} requests it had before "
              f"the window closed: raise max_requests_per_s", flush=True)
    counts = {name: int(np.sum(lg.outcome[:lg.sent] == code))
              for code, name in enumerate(traffic_mod.OUTCOME_NAMES)}
    engine = {k: run.window_stats[k] for k in (
        "flushes", "dispatched", "served", "late_results", "timeouts",
        "shed", "rejected")}
    print(f"window: {run.seconds} s, sent {lg.sent}, outcomes {counts}, "
          f"compiles in window {run.compiles_in_window}, engine {engine}",
          flush=True)
    g = run.gc_pauses
    print(f"garbage collections in and after the window: {g.count}, "
          f"{g.total_s:.4f} s in all, longest {g.max_s:.4f} s", flush=True)
    if run.lateness_ms is not None:
        print("generator lateness ms: median %.3f p99 %.3f max %.3f"
              % run.lateness_ms, flush=True)
    if lg.errors:
        log(f"request errors (first {len(lg.errors)}): {lg.errors}")
    log(f"compared {run.checks['_sample']} answers with the reference")
    for k, c in out["checks"].items():
        rel = ">=" if k == "recall_at_10" else "<="
        log(f"check {k} {c['value']!r} {rel} {c['limit']!r}")
    print(json.dumps(out), flush=True)


# ---------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    args.seed %= 1 << 63
    return args


def main(argv=None, *, spec=None, chip=True) -> int:
    """``spec`` replaces BENCHMARK.json and ``chip=False`` skips the look
    for a chip: both for the benchmark's own tests only."""
    args = parse(argv)
    spec = spec or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(spec, args.workload)
    cfg, tr = cell.cfg, cell.traffic
    cfg_file = next(c["file"] for c in spec["configs"]
                    if c["name"] == cfg["name"])
    built = ensure_index(os.path.join(ROOT, cfg_file), cfg, chip)
    if built is not None:
        print(f"index: built {cfg['name']} (n={cfg['n']}, d={cfg['dim']}) "
              f"and saved it in {built:.1f} s", flush=True)
    import jax

    devices, peaks = (require_chip(cell.workload["chips"]) if chip
                      else (jax.devices()[:1], None))
    enable_compile_cache()
    t = time.monotonic()
    index, sorted_attr = load_index(cfg)
    t_index = time.monotonic() - t
    t = time.monotonic()
    reqs, due = make_requests(cfg, tr, args.seed, args.seconds, sorted_attr,
                              data.centres(cfg))
    t_traffic = time.monotonic() - t
    t = time.monotonic()
    executor = make_executor(index, cfg, tr, reqs)
    print(f"setup: index {t_index:.2f} s, traffic {t_traffic:.2f} s "
          f"({len(reqs)} requests), executor and warm-up "
          f"{time.monotonic() - t:.2f} s ({executor.stats['compiles']} "
          f"programs)", flush=True)
    trace_dir = os.path.join(CACHE, "trace", args.workload) if args.trace \
        else None
    run = run_window(index, executor, cell, reqs, due, args.seconds,
                     trace_dir)
    run.peaks = peaks
    stats = devices[0].memory_stats() or {}
    run.peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    executor.close()
    del executor, index
    t = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.reference"):
        coll = reference.Collection(*data.collection(cfg))
        evaluate(run, coll, reqs, args.seed)
    print(f"reference: {time.monotonic() - t:.2f} s", flush=True)
    report(run, result(run, devices, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

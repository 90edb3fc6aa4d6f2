"""SearchExecutor: the one owner of query-pipeline jit state.

``RangeGraphIndex.search_ranks`` is fine for notebooks; serving traffic
needs compiled-program discipline (DESIGN.md §7). The executor provides it:

  * **Compile cache** keyed on ``(SearchConfig, batch_bucket, k_bucket)``:
    each key is AOT-lowered and compiled exactly once
    (``jax.jit(...).lower(...).compile()``) and the executable is called
    directly afterwards, so ``stats["compiles"]`` is an exact program
    count, not a heuristic.
  * **Batch-shape buckets**: an incoming batch pads up to the smallest
    power-of-two bucket (``core/config.py::batch_bucket``), so a 5-request
    flush pays 8-row compute instead of ``max_batch``-row. Padding repeats
    the last real row; the beam engine is row-independent on this path, so
    padded rows can never change a real row's results (the padding-parity
    test pins this bit-exactly).
  * **k buckets**: the requested k rounds up to ``config.bucket_k(k)``
    before hitting the program grid; results slice back to the caller's k.
  * **AOT warmup**: :meth:`warmup` compiles the declared
    ``configs x batch_buckets x k_buckets`` grid up front so the first
    request pays zero compile latency — a warmed executor serves any
    mixed workload inside the grid with zero post-warmup compiles
    (stats-asserted in tests and gated in ``benchmarks/ci_gate.py``).

``serve/engine.py::ServingEngine`` is queueing + per-request stats over
this layer. ``REPRO_SERVE_WARMUP=1`` makes every newly built executor warm
its full grid (the CI executor-warmup leg's hook).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import config as config_mod
from repro.core import knobs as knobs_mod
from repro.core import search as search_mod
from repro.core import storage as storage_mod
from repro.core.config import SearchConfig
from repro.kernels import ops

__all__ = ["SearchExecutor"]


class SearchExecutor:
    def __init__(
        self,
        index,
        config: SearchConfig | None = None,
        *,
        max_batch: int = 64,
        batch_buckets: tuple[int, ...] | None = None,
        warmup: bool | None = None,
        faults=False,
    ):
        """index: a ``RangeGraphIndex``. config: the executor's default
        ``SearchConfig`` (per-call configs may differ; each is its own
        cache-key axis). batch_buckets: explicit padded batch shapes
        (sorted ascending, max element = max_batch) — the default is the
        power-of-two ladder; pass ``(max_batch,)`` to reproduce the
        historical always-pad-to-max behavior. warmup: AOT-compile the
        full grid now (None = the ``REPRO_SERVE_WARMUP`` env). faults: an
        explicit ``FaultConfig``/``FaultInjector`` injecting latency
        spikes into ``search_ranks`` (``serve/faults.py``); the executor
        never picks faults up from the env — results stay bit-exact, only
        timing moves."""
        self.index = index
        self.config = config or SearchConfig()
        self.max_batch = int(max_batch)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if batch_buckets is None:
            self.batch_buckets = config_mod.batch_buckets(self.max_batch)
        else:
            self.batch_buckets = tuple(sorted(int(b) for b in batch_buckets))
            if not self.batch_buckets or \
                    self.batch_buckets[-1] != self.max_batch:
                raise ValueError(
                    f"batch_buckets {batch_buckets} must be non-empty and "
                    f"end at max_batch={self.max_batch}"
                )
        # the hot tables, uploaded once per leaf (possibly codec structs —
        # decode happens inside the jitted search / kernels, at the edge;
        # neighbors go up in the EdgeRows form where the hop kernels DMA
        # from it, else in their stored codec; NamedTuple codecs are
        # pytrees, so their structure sits in the trace signature and the
        # zero-post-warmup-compile guarantee holds)
        cfg = self.config
        nbrs = index.neighbors
        if ops.reads_edge_rows(cfg.hop_impl, cfg.edge_impl, cfg.dist_impl):
            nbrs = storage_mod.pack_edges(nbrs)
        self._vec = storage_mod.as_device(index.vectors)
        self._nbrs = storage_mod.as_device(nbrs)
        self._rerank = storage_mod.as_device(getattr(index, "rerank", None))
        if faults:
            from repro.serve import faults as faults_mod

            self.faults = faults_mod.resolve(faults)
        else:
            self.faults = None
        self.closed = False
        self._cache: dict = {}   # (config, batch_bucket, k_bucket) -> exe
        self.seen_k_buckets: set[int] = set()
        self.stats = {
            "compiles": 0, "warmup_compiles": 0, "cache_hits": 0,
            "batches": 0, "queries": 0, "index_bytes": int(index.nbytes),
        }
        if warmup is None:
            warmup = knobs_mod.get_bool("REPRO_SERVE_WARMUP")
        if warmup:
            self.warmup()

    # -- bucket math ---------------------------------------------------------
    def batch_bucket(self, b: int) -> int:
        """The padded shape a ``b``-row batch runs at (the one
        ``config.pick_bucket`` rule over this executor's ladder)."""
        return config_mod.pick_bucket(b, self.batch_buckets)

    def program_grid(self, configs=None) -> int:
        """Upper bound on compiled programs for ``configs`` (default: the
        executor's own): ``len(configs) * len(batch_buckets) *
        len(k_buckets)`` — the compile-count gate's denominator."""
        configs = tuple(configs) if configs is not None else (self.config,)
        return sum(
            len(self.batch_buckets) * len(cfg.k_buckets()) for cfg in configs
        )

    # -- compilation ---------------------------------------------------------
    def _compile(self, cfg: SearchConfig, bb: int, kb: int, *,
                 warmup: bool = False):
        key = (cfg, bb, kb)
        exe = self._cache.get(key)
        if exe is not None:
            return exe
        d = self.index.dim
        q = jnp.zeros((bb, d), jnp.float32)
        z = jnp.zeros((bb,), jnp.int32)
        lowered = search_mod._search_improvised_jit.lower(
            self._vec, self._nbrs, q, z, z, self._rerank,
            logn=self.index.logn, m_out=self.index.m, k=kb, config=cfg,
        )
        exe = lowered.compile()
        self._cache[key] = exe
        self.stats["compiles"] += 1
        if warmup:
            self.stats["warmup_compiles"] += 1
        return exe

    def warmup(self, batch_buckets=None, k_buckets=None, configs=None) -> int:
        """AOT-compile the declared (config, batch_bucket, k_bucket) grid.

        Defaults to the executor's full grid — every batch bucket times
        every ``config.k_buckets()`` value of the default config. Returns
        the number of programs compiled by this call (already-cached keys
        cost nothing)."""
        configs = tuple(configs) if configs is not None else (self.config,)
        bbs = tuple(batch_buckets) if batch_buckets is not None \
            else self.batch_buckets
        before = self.stats["compiles"]
        for cfg in configs:
            kbs = tuple(k_buckets) if k_buckets is not None \
                else cfg.k_buckets()
            kbs = sorted({cfg.bucket_k(kb) for kb in kbs})
            for bb in bbs:
                bb = self.batch_bucket(int(bb))
                for kb in kbs:
                    self._compile(cfg, bb, kb, warmup=True)
        return self.stats["compiles"] - before

    # -- execution -----------------------------------------------------------
    def search_ranks(self, queries, L, R, *, k: int,
                     config: SearchConfig | None = None):
        """Bucketed, compile-cached improvised search.

        queries f32[B, d], L/R int32[B] rank ranges, any B >= 1 (batches
        beyond ``max_batch`` split). Returns a ``SearchResult`` sliced back
        to ``[B, k]`` — bit-identical to the direct
        ``search_improvised`` call at the same config (padding and k
        rounding cannot leak into real rows)."""
        if self.closed:
            from repro.serve.errors import ShutdownError

            raise ShutdownError("SearchExecutor is closed")
        if self.faults is not None:
            self.faults.maybe_latency()
        cfg = config or self.config
        if k > cfg.ef:
            raise ValueError(
                f"requested k={k} exceeds the config's ef={cfg.ef}; "
                f"raise ef or lower k"
            )
        kb = cfg.bucket_k(k)
        q = np.asarray(queries, np.float32)
        L = np.asarray(L, np.int32).reshape(-1)
        R = np.asarray(R, np.int32).reshape(-1)
        B = q.shape[0]
        if B < 1:
            raise ValueError("empty query batch")
        parts = [
            self._run(q[s : s + self.max_batch], L[s : s + self.max_batch],
                      R[s : s + self.max_batch], kb, cfg)
            for s in range(0, B, self.max_batch)
        ]
        res = parts[0] if len(parts) == 1 else search_mod.SearchResult(
            *(jnp.concatenate(xs, axis=0) for xs in zip(*parts))
        )
        self.seen_k_buckets.add(kb)
        if kb == k:
            return res
        return res._replace(ids=res.ids[:, :k], dists=res.dists[:, :k])

    def close(self):
        """Release the compile cache and refuse further work
        (``search_ranks`` raises ``ShutdownError``). Idempotent; stats
        survive for post-mortem accounting."""
        self.closed = True
        self._cache.clear()

    def _run(self, q, L, R, kb, cfg):
        B = q.shape[0]
        bb = self.batch_bucket(B)
        if bb != B:
            pad = bb - B
            q = np.concatenate([q, np.repeat(q[-1:], pad, axis=0)])
            L = np.concatenate([L, np.repeat(L[-1:], pad)])
            R = np.concatenate([R, np.repeat(R[-1:], pad)])
        key = (cfg, bb, kb)
        exe = self._cache.get(key)
        if exe is not None:
            self.stats["cache_hits"] += 1
        else:
            exe = self._compile(cfg, bb, kb)
        res = exe(self._vec, self._nbrs, jnp.asarray(q), jnp.asarray(L),
                  jnp.asarray(R), self._rerank)
        self.stats["batches"] += 1
        self.stats["queries"] += B
        if bb == B:
            return res
        return search_mod.SearchResult(*(x[:B] for x in res))

"""Reproducible benchmark harness -> machine-readable BENCH_<stamp>.json.

The ONE benchmark entry point (the legacy CSV printer ``benchmarks/
run.py`` was folded in here — ISSUE 5 satellite): every paper table plus
the repo's own engineering tables snapshot into a schema-versioned JSON
document (the perf trajectory — see "BENCH_*.json trajectory" in
benchmarks/README.md):

  batched — the batched VAT engine: one compiled ``vat_batch`` /
            ``ivat_batch`` program over a (b, n, d) stack vs a Python
            loop of b solo fits (the serving-many-workloads story).
  ivat    — the fused Pallas iVAT kernel vs the XLA ``at[].set`` path
            (interpret mode on CPU — correctness-grade timing; compiled
            numbers belong on TPU hardware, the ``mode`` field says
            which you are looking at).
  metrics — the metric-dispatched pairwise kernel (ISSUE 3): XLA vs
            Pallas-interpret per metric, so each metric's tile variant
            is on the perf record from day one.
  flash   — materialized exact VAT vs the matrix-free Flash-VAT engine
            (ISSUE 4): wall time AND peak working-set bytes from XLA's
            compiled-program memory accounting, the table that shows the
            O(n^2) -> O(n·d) memory drop buys exact VAT at bigvat sizes.
  turbo   — the ISSUE 5 headline: the PR-4 stepwise matrix-free engine
            vs the persistent Turbo engine vs the sharded engine on the
            same points — wall time, peak_bytes, and the static dispatch
            census (how many pallas_calls, how many outside any loop)
            of each engine's Pallas variant.
  approx  — the million-point rung (ISSUE 6): the exact matrix-free
            engine vs the kNN-graph Borůvka pipeline on overlap sizes
            where both run — wall time, the kNN kernel's compiled
            working set, and the MST-weight ratio vs exact (a schema-v4
            ``quality`` row: accuracy on record, exempt from the
            wall-time gate).
  serve   — the tendency-as-a-service layer (ISSUE 7): cold-start vs
            warm-cache fit latency through ``TendencyServer`` (the AOT
            program cache's whole point — warm p50 strictly below
            cold), plus p50/p99 and throughput under concurrent
            multi-client load with the coalesce rate and cache hit
            rate on record.  Rows carry the schema-v5 ``percentiles``
            object.
  faults  — the robustness tax (ISSUE 9): warm served fits with input
            admission on vs off (the per-request validation overhead),
            and a poisoned 4-lane coalesced batch recovered through the
            batch-split ladder vs the same batch clean (what graceful
            degradation costs when it actually fires).  Scheduling-
            heavy timings — CI gates this table at the looser 1.5
            threshold.
  monitor — the training-diagnostics subsystem (ISSUE 8): jitted
            train-step wall time with the tendency monitor off vs
            observing every N steps vs every step (the amortized
            overhead story), one warm diag-step latency (the single
            compiled probe-program dispatch), and the history's
            serialized growth rate on the schema-v6 ``bytes_per_step``
            field (a ``quality`` row — storage, not wall time).
  numerics — the numerics shield's price tag (ISSUE 10): Gram-form vs
            direct-form pairwise tiles on the same points (what the
            condition-aware dispatch pays when it switches), the host
            conditioning pre-pass (``numerics.resolve`` — κ statistics
            + transform) on its own, and the end-to-end facade fit
            under ``numerics="fast"`` vs the default ``auto`` on
            ill-conditioned data — the shield tax on record.
  table2/table3 — the paper's Hopkins and clustering-alignment quality
            tables (us_per_call 0 — they record accuracy, not speed).

Every row records the ``metric`` it was measured under and (schema v3)
its ``peak_bytes`` — XLA temp + output allocation of the measured
program, or null where memory was not profiled; tables predating metric
pluggability are euclidean throughout.  Schema v4 adds the optional
per-row ``quality`` flag: true marks rows that carry accuracy, not wall
time, and ``compare.py`` keeps them out of the regression gate.  Schema
v5 adds the optional per-row ``percentiles`` object ({p50_us, p99_us})
for tables measured under load, where best-of-reps would hide the tail.
Schema v6 adds the optional per-row ``bytes_per_step`` number — the
serialized growth rate of a continuously-recorded artifact (the tendency
monitor's history).  Schema v7 adds no row fields; it marks snapshots
that carry the ``faults`` resilience table.  Schema v8 likewise adds no
row fields; it marks snapshots that carry the ``numerics`` shield table.

Run:
  PYTHONPATH=src python -m benchmarks.bench            # full, ~minutes
  PYTHONPATH=src python -m benchmarks.bench --smoke    # CI-sized, ~1 min
  PYTHONPATH=src python -m benchmarks.bench --tables batched,ivat

Validate a snapshot:
  PYTHONPATH=src python -m benchmarks.bench_schema BENCH_<stamp>.json
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

TABLES = ("table1", "table2", "table3", "table4", "batched", "ivat",
          "metrics", "flash", "turbo", "approx", "serve", "monitor",
          "faults", "numerics")

# (b, n, d) batched workloads; smoke keeps compile + run under CI budgets
_BATCH_WORKLOADS = ((8, 256, 8), (16, 512, 8))
_BATCH_WORKLOADS_SMOKE = ((4, 128, 8),)
_IVAT_SIZES = (512, 1024)
_IVAT_SIZES_SMOKE = (192,)
_METRIC_SHAPE = (1024, 64)
_METRIC_SHAPE_SMOKE = (256, 16)
_FLASH_SIZES = (2_048, 8_192)
# smoke must stay big enough that the streamed seed pass's (br, n) tile
# (br caps at 1024) is a strict subset of the matrix — below ~2k the
# row records no memory win and can't catch a regression
_FLASH_SIZES_SMOKE = (4_096,)
_TURBO_SIZES = (8_192,)
_TURBO_SIZES_SMOKE = (2_048,)
# approx-vs-exact overlap sizes: both engines must finish, so the sweep
# tops out where the exact matrix-free engine is still minutes-feasible
_APPROX_SIZES = (20_000, 50_000)
_APPROX_SIZES_SMOKE = (4_096,)
_APPROX_K = 15
# paper datasets the CI-sized table2/table3 keep (mirrors table1 smoke)
_QUALITY_DATASETS_SMOKE = ("iris", "blobs")
# serving-layer load shapes: per-request points, total requests, clients
_SERVE_SIZES = (90, 1024)
_SERVE_SIZES_SMOKE = (48,)
_SERVE_LOAD = (64, 8)
_SERVE_LOAD_SMOKE = (16, 4)
# monitor overhead loop: (seq, batch, steps per measured loop, diag_every)
_MONITOR_SHAPE = (64, 8, 20, 20)
_MONITOR_SHAPE_SMOKE = (32, 4, 8, 4)
# faults table: per-request points for the admission/recovery timings
_FAULTS_SIZES = (90, 512)
_FAULTS_SIZES_SMOKE = (48,)
# numerics table: points for the gram-vs-direct + pre-pass timings
_NUMERICS_SIZES = (2_048, 8_192)
_NUMERICS_SIZES_SMOKE = (512,)


def _time(fn, *args, reps: int = 3) -> float:
    """Best-of-reps wall seconds; warmup call absorbs jit compilation."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _peak_bytes(fn, *args):
    """Peak working set of the compiled program: XLA temp + output bytes.

    Arguments (the inputs the caller already holds, e.g. X itself) are
    excluded — this measures what the *algorithm* allocates, which is
    exactly where materialized VAT's O(n^2) shows up and Flash-VAT's
    doesn't.  Returns None where the backend can't report it.
    """
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return int(ma.temp_size_in_bytes) + int(ma.output_size_in_bytes)
    except Exception:
        return None


def _row(table: str, name: str, seconds: float, *,
         metric: str = "euclidean", peak_bytes=None, **derived) -> dict:
    return {"table": table, "name": f"{table}/{name}", "metric": metric,
            "us_per_call": seconds * 1e6, "peak_bytes": peak_bytes,
            "derived": derived}


# ------------------------------------------------------------ tables ----

def bench_table1(smoke: bool, reps: int) -> list[dict]:
    from benchmarks import vat_tables as T
    kwargs = {"naive_cap": 150, "datasets": ("iris", "blobs")} if smoke else {}
    rows = []
    for r in T.table1(reps=reps, **kwargs):
        # the python baseline is one measured run by design (it is already
        # seconds long); every jitted row is best-of-`reps`
        rows.append(_row("table1", f"{r['dataset']}/python", r["python_s"],
                         scaled=r["scaled"], n=r["n"], reps=1))
        rows.append(_row("table1", f"{r['dataset']}/jax", r["jax_s"],
                         speedup_vs_python=round(r["speedup_jax"], 2)))
        rows.append(_row("table1", f"{r['dataset']}/pallas_interpret",
                         r["pallas_interp_s"], mode="interpret"))
    return rows


def bench_table2(smoke: bool, reps: int) -> list[dict]:
    from benchmarks import vat_tables as T
    datasets = _QUALITY_DATASETS_SMOKE if smoke else None
    return [_row("table2", f"{r['dataset']}/hopkins", 0.0,
                 hopkins=round(r["hopkins"], 4))
            for r in T.table2(datasets=datasets)]


def bench_table3(smoke: bool, reps: int) -> list[dict]:
    from benchmarks import vat_tables as T
    datasets = _QUALITY_DATASETS_SMOKE if smoke else None
    rows = []
    for r in T.table3(datasets=datasets):
        tag = r["dataset"]
        rows.append(_row("table3", f"{tag}/vat", 0.0,
                         block_score=round(r["vat_block_score"], 3),
                         k_est=r["vat_k_est"]))
        rows.append(_row("table3", f"{tag}/kmeans", 0.0,
                         ari=round(r["kmeans_ari"], 3)))
        rows.append(_row("table3", f"{tag}/dbscan", 0.0,
                         ari=round(r["dbscan_ari"], 3)))
    return rows


def bench_table4(smoke: bool, reps: int) -> list[dict]:
    from benchmarks import vat_tables as T
    # the 1M row is the ISSUE-6 headline: the approx rung is the only
    # method that fits it on one CPU (auto-selected past MEDIUM_N)
    sizes = (20_000,) if smoke else (20_000, 50_000, 100_000, 1_000_000)
    rows = []
    for r in T.table4(sizes=sizes, reps=reps):
        rows.append(_row("table4", f"n{r['n']}/{r['method']}", r["fit_s"],
                         points_per_s=round(r["points_per_s"]),
                         k_est=r["k_est"], k_true=r["k_true"],
                         hopkins=round(r["hopkins"], 4)))
    return rows


def bench_batched(smoke: bool, reps: int) -> list[dict]:
    from repro import core
    rows = []
    for b, n, d in (_BATCH_WORKLOADS_SMOKE if smoke else _BATCH_WORKLOADS):
        rng = np.random.default_rng(b * 1000 + n)
        Xb = jnp.asarray(rng.normal(size=(b, n, d)).astype(np.float32))
        tag = f"b{b}xn{n}xd{d}"

        t_batch = _time(lambda A: core.vat_batch(A).rstar, Xb, reps=reps)

        def loop_vat(A):  # b solo programs — what fit_many replaces
            return [core.vat(A[i]).rstar for i in range(A.shape[0])]
        t_loop = _time(loop_vat, Xb, reps=reps)

        rows.append(_row("batched", f"{tag}/vat_batch", t_batch,
                         datasets_per_s=round(b / t_batch, 1),
                         speedup_vs_loop=round(t_loop / t_batch, 2)))
        rows.append(_row("batched", f"{tag}/vat_loop", t_loop,
                         datasets_per_s=round(b / t_loop, 1)))

        t_ib = _time(lambda A: core.ivat_batch(A)[0], Xb, reps=reps)
        rows.append(_row("batched", f"{tag}/ivat_batch", t_ib,
                         datasets_per_s=round(b / t_ib, 1)))
    return rows


def bench_ivat(smoke: bool, reps: int) -> list[dict]:
    from repro import core
    from repro.kernels import ops
    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    rows = []
    for n in (_IVAT_SIZES_SMOKE if smoke else _IVAT_SIZES):
        rng = np.random.default_rng(n)
        X = jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32))
        rstar = core.vat(X).rstar

        t_xla = _time(lambda R: ops.ivat_from_vat(R), rstar, reps=reps)
        t_pal = _time(lambda R: ops.ivat_from_vat(R, use_pallas=True),
                      rstar, reps=reps)
        rows.append(_row("ivat", f"n{n}/xla", t_xla, mode="xla"))
        rows.append(_row("ivat", f"n{n}/pallas", t_pal, mode=mode,
                         speedup_vs_xla=round(t_xla / t_pal, 3)))
    return rows


def bench_metrics(smoke: bool, reps: int) -> list[dict]:
    from repro.kernels import ops
    from repro.kernels.ref import METRICS
    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    n, d = _METRIC_SHAPE_SMOKE if smoke else _METRIC_SHAPE
    rng = np.random.default_rng(n)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    rows = []
    for metric in METRICS:
        t_xla = _time(lambda A: ops.pairwise_dist(A, metric=metric),
                      X, reps=reps)
        t_pal = _time(
            lambda A: ops.pairwise_dist(A, metric=metric, use_pallas=True),
            X, reps=reps)
        tag = f"n{n}xd{d}/{metric}"
        rows.append(_row("metrics", f"{tag}/xla", t_xla, metric=metric,
                         mode="xla"))
        rows.append(_row("metrics", f"{tag}/pallas", t_pal, metric=metric,
                         mode=mode,
                         speedup_vs_xla=round(t_xla / t_pal, 3)))
    return rows


def bench_flash(smoke: bool, reps: int) -> list[dict]:
    """Materialized exact VAT vs matrix-free Flash-VAT: time + memory.

    Both columns produce bitwise-identical orderings (pinned in
    tests/test_flashvat.py); the table records what that equivalence
    costs — the matrix-free engine trades MXU-batched O(n^2) matmul
    throughput for an O(n·d) working set, which is the trade that lets
    exact VAT past the materialized rungs' memory wall.
    """
    from repro import core
    rows = []
    for n in (_FLASH_SIZES_SMOKE if smoke else _FLASH_SIZES):
        rng = np.random.default_rng(n)
        X = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))

        t_mat = _time(lambda A: core.vat(A).order, X, reps=reps)
        pb_mat = _peak_bytes(lambda A: core.vat(A), X)
        t_mf = _time(lambda A: core.vat_matrix_free(A).order, X, reps=reps)
        pb_mf = _peak_bytes(lambda A: core.vat_matrix_free(A), X)

        rows.append(_row("flash", f"n{n}/materialized", t_mat,
                         peak_bytes=pb_mat, nn_bytes=n * n * 4))
        derived = {"time_vs_materialized": round(t_mf / t_mat, 3)}
        if pb_mat and pb_mf:
            derived["mem_shrink_vs_materialized"] = round(pb_mat / pb_mf, 1)
        rows.append(_row("flash", f"n{n}/matrix_free", t_mf,
                         peak_bytes=pb_mf, **derived))
    return rows


def bench_turbo(smoke: bool, reps: int) -> list[dict]:
    """Stepwise vs persistent vs sharded matrix-free VAT (ISSUE 5).

    All three engines produce bitwise-identical orderings (pinned in
    tests/test_turbo.py); this table records what the persistent rewrite
    buys: wall time (XLA engines — the honest CPU numbers; compiled
    megakernel timings belong on TPU hardware), peak working-set bytes,
    and the static dispatch census of each engine's Pallas variant —
    the stepwise engine re-enters a pallas_call every Prim step, the
    Turbo engine compiles to ONE loop-free pallas_call.
    """
    from repro import core
    from repro.core.vat import _streamed_seed_pivot
    from repro.kernels import ops as kops
    rows = []
    for n in (_TURBO_SIZES_SMOKE if smoke else _TURBO_SIZES):
        rng = np.random.default_rng(n)
        X = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
        tag = f"n{n}"

        def stepwise(A):
            return core.vat_matrix_free(A, turbo=False).order

        def persistent(A):
            return core.vat_matrix_free(A).order

        # the streamed seed scan is SHARED by both engines (and was
        # itself sped ~2.5x this PR); measuring it separately lets the
        # rows report the traversal-only speedup the engine swap buys
        t_seed = _time(jax.jit(lambda A: _streamed_seed_pivot(
            A, metric="euclidean")), X, reps=reps)

        t_sw = _time(stepwise, X, reps=reps)
        pb_sw = _peak_bytes(stepwise, X)
        d_sw = kops.kernel_dispatch_stats(
            lambda A: core.vat_matrix_free(A, turbo=False,
                                           use_pallas=True), X)
        rows.append(_row("turbo", f"{tag}/stepwise", t_sw, peak_bytes=pb_sw,
                         seed_us=round(t_seed * 1e6, 1),
                         pallas_calls=d_sw["pallas_calls"],
                         persistent_calls=d_sw["persistent"]))

        t_tb = _time(persistent, X, reps=reps)
        pb_tb = _peak_bytes(persistent, X)
        d_tb = kops.kernel_dispatch_stats(
            lambda A: core.vat_matrix_free(A, use_pallas=True), X)
        rows.append(_row("turbo", f"{tag}/persistent", t_tb,
                         peak_bytes=pb_tb,
                         seed_us=round(t_seed * 1e6, 1),
                         pallas_calls=d_tb["pallas_calls"],
                         persistent_calls=d_tb["persistent"],
                         speedup_vs_stepwise=round(t_sw / t_tb, 2),
                         traversal_speedup_vs_stepwise=round(
                             (t_sw - t_seed) / max(t_tb - t_seed, 1e-9),
                             2)))

        mesh = jax.make_mesh((1,), ("data",))

        def sharded(A):
            return core.vat_matrix_free_sharded(A, mesh).order

        t_sh = _time(sharded, X, reps=reps)
        rows.append(_row("turbo", f"{tag}/sharded_1dev", t_sh,
                         peak_bytes=_peak_bytes(sharded, X),
                         devices=len(jax.devices()),
                         speedup_vs_stepwise=round(t_sw / t_sh, 2)))
    return rows


def bench_approx(smoke: bool, reps: int) -> list[dict]:
    """Exact matrix-free VAT vs the kNN-graph Borůvka rung (ISSUE 6).

    Run on overlap sizes where BOTH engines finish, so every approx row
    carries its ground truth: wall time against the exact engine, the
    kNN kernel's compiled working set against the (n, n) bytes exact
    materialization would need, and the MST-weight ratio (approx / exact
    — 1.0 means the kNN graph contained the true MST).  The ratio row is
    a schema-v4 ``quality`` row: us_per_call 0, exempt from compare.py's
    wall gate, so accuracy regressions surface in review rather than as
    timing flake.
    """
    from repro import core
    from repro.data.synth import make_big_blobs
    from repro.kernels import ops as kops
    k = _APPROX_K
    rows = []
    for n in (_APPROX_SIZES_SMOKE if smoke else _APPROX_SIZES):
        X, _ = make_big_blobs(n=n, k=5)
        Xj = jnp.asarray(X)
        kk = min(k, n - 1)

        exact = core.vat_matrix_free(Xj)                   # warm + reference
        exact_w = float(np.sum(np.asarray(exact.edges), dtype=np.float64))
        t_exact = _time(lambda A: core.vat_matrix_free(A).order, Xj,
                        reps=reps)
        rows.append(_row("approx", f"n{n}/exact_flash", t_exact,
                         peak_bytes=_peak_bytes(
                             lambda A: core.vat_matrix_free(A), Xj)))

        res = core.approx_vat(X, k=kk)                     # warm jit caches
        t_apx = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            res = core.approx_vat(X, k=kk)
            t_apx = min(t_apx, time.perf_counter() - t0)
        # the kNN build dominates the pipeline; its compiled working set
        # is the memory story (vs n^2 * 4 bytes for materialization)
        pb = _peak_bytes(lambda A: kops.knn_graph(A, k=kk)[0], Xj)
        rows.append(_row("approx", f"n{n}/knn_boruvka_k{kk}", t_apx,
                         peak_bytes=pb, nn_bytes=n * n * 4,
                         knn_mode=res.stats.mode,
                         speedup_vs_exact=round(t_exact / t_apx, 2)))
        quality = _row("approx", f"n{n}/mst_weight_ratio_k{kk}", 0.0,
                       weight_ratio=round(res.stats.mst_weight / exact_w, 6),
                       components=res.stats.components,
                       repair_weight=round(res.stats.repair_weight, 4))
        quality["quality"] = True
        rows.append(quality)
    return rows


def bench_serve(smoke: bool, reps: int) -> list[dict]:
    """Tendency-as-a-service latencies (ISSUE 7).

    Three rows per request size:

      cold_fit    — first request on a fresh server: trace + XLA
                    compile + dispatch, the cost the AOT cache exists
                    to amortize.
      warm_fit    — p50 of repeated same-bucket fits (``us_per_call``)
                    with the p50/p99 pair on the row's ``percentiles``;
                    must sit strictly below cold_fit (the acceptance
                    pin — tests/test_serve.py holds the same line).
      concurrent  — p50 under multi-client threaded load through the
                    coalescer (window + batching included), with
                    throughput, coalesce rate, and cache hit rate in
                    ``derived``.

    Percentiles rather than best-of-reps: a serving layer is judged by
    its tail, and best-of would hide exactly the scheduling costs
    (window waits, batched neighbors) this table exists to track.
    """
    from concurrent.futures import ThreadPoolExecutor

    from repro.serve import ServeConfig, TendencyServer
    n_requests, clients = _SERVE_LOAD_SMOKE if smoke else _SERVE_LOAD
    warm_reps = max(8, reps * 4)
    rows = []
    for n in (_SERVE_SIZES_SMOKE if smoke else _SERVE_SIZES):
        rng = np.random.default_rng(n)
        datasets = [rng.normal(size=(n, 8)).astype(np.float32)
                    for _ in range(n_requests)]
        tag = f"n{n}"

        config = ServeConfig(window_s=0.002, max_batch=8)
        with TendencyServer(config) as srv:
            t0 = time.perf_counter()
            srv.fit(datasets[0])                 # cold: compile included
            t_cold = time.perf_counter() - t0
            lat = []
            for _ in range(warm_reps):
                t0 = time.perf_counter()
                srv.fit(datasets[0])
                lat.append(time.perf_counter() - t0)
        p50, p99 = (float(np.percentile(lat, q)) for q in (50, 99))
        rows.append(_row("serve", f"{tag}/cold_fit", t_cold,
                         compile_included=True))
        warm = _row("serve", f"{tag}/warm_fit", p50,
                    speedup_vs_cold=round(t_cold / p50, 1))
        warm["percentiles"] = {"p50_us": p50 * 1e6, "p99_us": p99 * 1e6}
        rows.append(warm)

        with TendencyServer(config) as srv:
            for b in (1, 2, 4, 8):               # pre-compile lane buckets
                srv.warm(n, 8, batch=b)

            def one(X):
                t0 = time.perf_counter()
                srv.fit(X)
                return time.perf_counter() - t0

            t_wall = time.perf_counter()
            with ThreadPoolExecutor(max_workers=clients) as pool:
                lat = list(pool.map(one, datasets))
            t_wall = time.perf_counter() - t_wall
            st = srv.stats()
        p50, p99 = (float(np.percentile(lat, q)) for q in (50, 99))
        conc = _row("serve", f"{tag}/concurrent_c{clients}", p50,
                    requests=n_requests, clients=clients,
                    qps=round(n_requests / t_wall, 1),
                    coalesce_rate=round(st.coalesce_rate, 2),
                    cache_hit_rate=round(st.cache.hit_rate, 3))
        conc["percentiles"] = {"p50_us": p50 * 1e6, "p99_us": p99 * 1e6}
        rows.append(conc)
    return rows


def bench_monitor(smoke: bool, reps: int) -> list[dict]:
    """Training overhead of the tendency monitor (ISSUE 8).

    Five rows per shape:

      train_step       — the plain jitted train step, monitor off (the
                         baseline every overhead row is relative to).
      loop_diag_everyN — amortized per-step wall time of a hand-rolled
                         train loop observing every N steps (the
                         default cadence: N=20 full, N=4 smoke).
      loop_diag_every1 — worst case: one probe dispatch per step.
      diag_step        — one warm ``TendencyMonitor.observe`` (the
                         single compiled probe-program dispatch plus its
                         one host sync).
      history_bytes    — ``quality`` row carrying the history's
                         serialized growth rate on the schema-v6
                         ``bytes_per_step`` field.

    The acceptance line the compare gate holds (monitor=1.5): the
    every-N loop must stay within noise of the plain step — diagnostics
    are free at the default cadence or they won't stay on.
    """
    from repro.configs import smoke_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.tokens import make_batch
    from repro.monitor import TendencyMonitor
    from repro.train import steps as S

    seq, batch_size, loop_steps, diag_every = (
        _MONITOR_SHAPE_SMOKE if smoke else _MONITOR_SHAPE)
    cfg = smoke_config("gemma-2b")
    shape = ShapeConfig("bench", seq, batch_size, "train")
    tc = TrainConfig(lr=1e-3, total_steps=max(loop_steps, 100))
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape).items()}
    state = S.init_state(cfg, tc, jax.random.PRNGKey(0))
    step = jax.jit(S.build_train_step(cfg, tc))
    tag = f"s{seq}xb{batch_size}"

    def loop(diag: int) -> float:
        """Best-of-reps amortized per-step seconds of a loop_steps run."""
        mon = TendencyMonitor(cfg, seed=0)
        st = state
        st, _ = step(st, batch)                  # warm the step program
        if diag:
            mon.observe(0, st.params, batch)     # warm the probe program
        best = float("inf")
        for r in range(reps):
            mon = TendencyMonitor(cfg, seed=0)
            st = state
            t0 = time.perf_counter()
            for i in range(loop_steps):
                st, _ = step(st, batch)
                if diag and (i + 1) % diag == 0:
                    mon.observe(i + 1, st.params, batch)
            jax.block_until_ready(st.params)
            best = min(best, (time.perf_counter() - t0) / loop_steps)
        return best

    t_plain = loop(0)
    rows = [_row("monitor", f"{tag}/train_step", t_plain)]
    t_n = loop(diag_every)
    rows.append(_row("monitor", f"{tag}/loop_diag_every{diag_every}", t_n,
                     overhead_vs_plain=round(t_n / t_plain, 3)))
    t_1 = loop(1)
    rows.append(_row("monitor", f"{tag}/loop_diag_every1", t_1,
                     overhead_vs_plain=round(t_1 / t_plain, 3)))

    mon = TendencyMonitor(cfg, seed=0)
    mon.observe(0, state.params, batch)          # warm
    best = float("inf")
    for r in range(reps):
        t0 = time.perf_counter()
        mon.observe(r + 1, state.params, batch)  # observe() host-syncs
        best = min(best, time.perf_counter() - t0)
    rows.append(_row("monitor", f"{tag}/diag_step", best,
                     probes=len(mon.specs)))

    hist = _row("monitor", f"{tag}/history_bytes", 0.0,
                probes=len(mon.specs))
    hist["quality"] = True
    hist["bytes_per_step"] = mon.history.nbytes_per_step()
    rows.append(hist)
    return rows


def bench_faults(smoke: bool, reps: int) -> list[dict]:
    """The robustness tax (ISSUE 9): admission overhead + split recovery.

    Four rows per request size:

      warm_fit_unvalidated — p50 warm served fit, admission checks off
                             (the PR-8 warm path, the baseline).
      warm_fit_validated   — the same fit with the O(n·d) admission
                             pass on (the default); ``derived``
                             carries the overhead ratio — the pin is
                             "validation is noise on a warm fit".
      batch_clean_4lane    — wall time for a 4-lane coalesced batch,
                             submit-to-all-resolved, nothing armed.
      batch_split_recovery — the same 4-lane batch with one lane
                             poisoned via the ``serve.execute`` fault
                             site: the ladder retries, splits, serves
                             the three survivors solo, and fails the
                             poison typed.  ``derived`` carries the
                             recovery-vs-clean ratio (bounded retry
                             backoff included — that IS the recovery
                             latency).
    """
    from concurrent.futures import wait

    from repro import faults as F
    from repro.serve import ServeConfig, TendencyServer
    warm_reps = max(8, reps * 4)
    rows = []
    for n in (_FAULTS_SIZES_SMOKE if smoke else _FAULTS_SIZES):
        rng = np.random.default_rng(n)
        tag = f"n{n}"
        X = rng.normal(size=(n, 8)).astype(np.float32)

        p50s = {}
        for validate in (False, True):
            config = ServeConfig(window_s=0.002, max_batch=8,
                                 validate=validate)
            with TendencyServer(config) as srv:
                srv.fit(X)                       # cold compile absorbed
                lat = []
                for _ in range(warm_reps):
                    t0 = time.perf_counter()
                    srv.fit(X)
                    lat.append(time.perf_counter() - t0)
            p50s[validate] = float(np.percentile(lat, 50))
        rows.append(_row("faults", f"{tag}/warm_fit_unvalidated",
                         p50s[False]))
        rows.append(_row("faults", f"{tag}/warm_fit_validated", p50s[True],
                         validation_overhead=round(
                             p50s[True] / p50s[False], 3)))

        datasets = [rng.normal(size=(n, 8)).astype(np.float32)
                    for _ in range(4)]
        config = ServeConfig(window_s=0.2, max_batch=4)
        with TendencyServer(config) as srv:
            srv.warm(n, 8, batch=4)              # the coalesced program
            srv.warm(n, 8, batch=1)              # the split-lane program

            def batch_once() -> float:
                t0 = time.perf_counter()
                futs = [srv.submit(Xi, tag=f"lane{i}")
                        for i, Xi in enumerate(datasets)]
                wait(futs, timeout=300)
                return time.perf_counter() - t0

            t_clean = min(batch_once() for _ in range(reps))
            F.arm("serve.execute", times=-1,
                  match=lambda ctx: "lane0" in ctx.get("tags", ()))
            try:
                t_recover = min(batch_once() for _ in range(reps))
            finally:
                F.disarm_all()
        rows.append(_row("faults", f"{tag}/batch_clean_4lane", t_clean,
                         lanes=4))
        rows.append(_row("faults", f"{tag}/batch_split_recovery", t_recover,
                         lanes=4, survivors=3,
                         recovery_vs_clean=round(t_recover / t_clean, 2)))
    return rows


def bench_numerics(smoke: bool, reps: int) -> list[dict]:
    """The numerics shield's price tag (ISSUE 10).

    Seven rows per size, all measured on ill-conditioned points (a 1e4
    common offset — the canonical Gram catastrophe the shield exists
    for):

      pairwise_gram    — the Gram-decomposition tile (the pre-shield
                         fast path, what ``fast``/unconditioned ``auto``
                         runs).
      pairwise_direct  — the cancellation-free (x−y)² tile the auto
                         policy switches to past KAPPA_SAFE; ``derived``
                         carries the cost ratio the dispatch trades for
                         its certified bound.
      prepass_resolve  — the host-side conditioning pre-pass on its own
                         (κ statistics + mean-center/rescale transform),
                         the fixed per-fit tax every policy but ``fast``
                         pays; κ and the decision are in ``derived``.
      kappa            — schema-v4 ``quality`` row (us_per_call 0,
                         exempt from the wall-time gate) putting the
                         measured condition estimate and its
                         post-conditioning value on the perf record.
      fit_fast         — end-to-end ``FastVAT(numerics="fast")`` warm
                         fit: the pre-shield baseline.
      fit_safe         — the always-condition policy: the shield's
                         worst-case price (``derived.cost_vs_fast``).
      fit_auto         — the default policy (here: pre-pass +
                         conditioned direct-form tiles, since the data
                         is hostile); ``derived.shield_overhead`` is
                         the headline — certified orderings on hostile
                         data cost percents, not multiples.
    """
    from repro.api import FastVAT
    from repro.kernels import ops as kops
    from repro.numerics import resolve
    rows = []
    for n in (_NUMERICS_SIZES_SMOKE if smoke else _NUMERICS_SIZES):
        rng = np.random.default_rng(n)
        half = n // 2
        X = np.concatenate([
            rng.normal(size=(half, 8)),
            rng.normal(size=(n - half, 8)) + 6.0]).astype(np.float32)
        X += np.float32(1.0e4)
        Xj = jnp.asarray(X)
        tag = f"n{n}"

        t_gram = _time(lambda A: kops.pairwise_dist(A, form="gram"),
                       Xj, reps=reps)
        t_dir = _time(lambda A: kops.pairwise_dist(A, form="direct"),
                      Xj, reps=reps)
        rows.append(_row("numerics", f"{tag}/pairwise_gram", t_gram,
                         peak_bytes=_peak_bytes(
                             lambda A: kops.pairwise_dist(A, form="gram"),
                             Xj)))
        rows.append(_row("numerics", f"{tag}/pairwise_direct", t_dir,
                         peak_bytes=_peak_bytes(
                             lambda A: kops.pairwise_dist(A, form="direct"),
                             Xj),
                         cost_vs_gram=round(t_dir / t_gram, 3)))

        best = float("inf")
        rep = None
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            _, rep = resolve(X, metric="euclidean")
            best = min(best, time.perf_counter() - t0)
        rows.append(_row("numerics", f"{tag}/prepass_resolve", best,
                         form=rep.form, conditioned=rep.conditioned))
        from repro.numerics import condition_stats
        stats = condition_stats(X)
        quality = _row("numerics", f"{tag}/kappa", 0.0,
                       kappa=round(stats.kappa, 1),
                       kappa_centered=round(stats.kappa_centered, 3))
        quality["quality"] = True
        rows.append(quality)

        t_fit = {}
        for mode in ("fast", "safe", "auto"):
            fv = FastVAT(numerics=mode)
            fv.fit(X)                            # warm the program cache
            t_best = float("inf")
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                FastVAT(numerics=mode).fit(X)
                t_best = min(t_best, time.perf_counter() - t0)
            t_fit[mode] = t_best
        rows.append(_row("numerics", f"{tag}/fit_fast", t_fit["fast"]))
        rows.append(_row("numerics", f"{tag}/fit_safe", t_fit["safe"],
                         cost_vs_fast=round(
                             t_fit["safe"] / t_fit["fast"], 3)))
        rows.append(_row("numerics", f"{tag}/fit_auto", t_fit["auto"],
                         shield_overhead=round(
                             t_fit["auto"] / t_fit["fast"], 3)))
    return rows


_BENCHES = {"table1": bench_table1, "table2": bench_table2,
            "table3": bench_table3, "table4": bench_table4,
            "batched": bench_batched, "ivat": bench_ivat,
            "metrics": bench_metrics, "flash": bench_flash,
            "turbo": bench_turbo, "approx": bench_approx,
            "serve": bench_serve, "monitor": bench_monitor,
            "faults": bench_faults, "numerics": bench_numerics}
assert set(_BENCHES) == set(TABLES)


# ------------------------------------------------------------ driver ----

def run(tables=TABLES, *, smoke: bool = False, reps: int = 3) -> dict:
    """Execute the requested tables; returns the schema-valid document."""
    rows = []
    for t in tables:
        print(f"# bench: {t} ...", file=sys.stderr)
        rows.extend(_BENCHES[t](smoke, reps))
    return {
        "schema_version": 8,
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
        },
        "config": {"smoke": smoke, "reps": reps, "tables": list(tables)},
        "rows": rows,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized run: tiny datasets, ~1 minute on CPU")
    p.add_argument("--tables", default=",".join(TABLES),
                   help=f"comma-separated subset of {TABLES}")
    p.add_argument("--reps", type=int, default=3,
                   help="timing repetitions (best-of)")
    p.add_argument("--out", default=None,
                   help="output path (default BENCH_<stamp>.json in cwd)")
    a = p.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    tables = tuple(t.strip() for t in a.tables.split(",") if t.strip())
    if unknown := set(tables) - set(TABLES):
        p.error(f"unknown tables {sorted(unknown)}; choose from {TABLES}")

    doc = run(tables, smoke=a.smoke, reps=a.reps)

    from benchmarks.bench_schema import validate
    validate(doc)  # never write an out-of-schema snapshot

    stamp = doc["created_utc"].replace(":", "").replace("-", "")
    out = a.out or f"BENCH_{stamp}.json"
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {out} ({len(doc['rows'])} rows)")
    for r in doc["rows"]:
        print(f"  {r['name']:40s} {r['us_per_call']:>14.1f} us  {r['derived']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

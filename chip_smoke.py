"""Bring-up smoke run of the FastVAT fit and serving paths on a TPU.

Drives the main path once through the entry points users call
(``repro.FastVAT`` and ``repro.serve.TendencyServer``) at the sizes
they run, and checks every answer:

  exact/default   FastVAT().fit(X), n = 50 000, d = 128: auto picks
                  flashvat; the order is a permutation; assess() finds
                  the 8 mixture components.
  exact/pallas    the same X with use_pallas=True: the megakernel is in
                  the program (no silent fall back to the XLA mirror),
                  its order is compared with the default path's, and
                  the two engines' spanning-tree weights agree to f32
                  rounding.
  oracle          numerics.certify.certify_fit against the f64
                  naive-Prim oracle at n = 2048 for vat, ivat, flashvat
                  and approx, XLA and Pallas: euclidean and cosine at
                  d = 128 (the exact and served fits' width), cosine at
                  d = 768 (the embedding fit's width).
  embedding       FastVAT(metric="cosine").fit(E), n = 63 000,
                  d = 768: auto picks approx; XLA and Pallas.
  serve           a warmed TendencyServer(use_pallas=True) answers
                  concurrent requests; every served order equals its
                  solo fit, the resilience counters stay zero, and the
                  warm requests add no trace and no cache miss.

Usage (from the repository root; no PYTHONPATH needed):

  python chip_smoke.py                 # one chip, every phase above
  python chip_smoke.py --four-chips    # only: the sharded exact engine
                                       # on a 4-device mesh vs the solo
                                       # engine on device 0
  python chip_smoke.py --rehearse      # tiny sizes, any backend (the
                                       # CPU runs Pallas in interpret
                                       # mode); for tests, not for timing

Without ``--rehearse`` the script exits 2 before any work unless JAX's
first device is a TPU.  Phase wall times (to ``block_until_ready``) are
printed as one bring-up run; they are not benchmark numbers.  Any failed
phase makes the exit code 1.  On success the last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    exact_n: int
    exact_d: int
    exact_method: str          # what auto must pick ("auto" = pass nothing)
    oracle_n: int
    oracle_cells: tuple        # (metric, d) pairs
    embed_n: int
    embed_d: int
    embed_method: str
    serve_ns: tuple
    serve_d: int


FULL = Sizes(exact_n=50_000, exact_d=128, exact_method="auto",
             oracle_n=2048,
             oracle_cells=(("euclidean", 128), ("cosine", 128),
                           ("cosine", 768)),
             embed_n=63_000, embed_d=768,
             embed_method="auto", serve_ns=(1000, 3000, 12_000), serve_d=128)
# the rehearsal pins the rungs by name: auto would pick them only at
# sizes the Pallas interpreter cannot run in test time
REHEARSE = Sizes(exact_n=300, exact_d=16, exact_method="flashvat",
                 oracle_n=96, oracle_cells=(("euclidean", 8), ("cosine", 24)),
                 embed_n=400, embed_d=24,
                 embed_method="approx", serve_ns=(60, 200), serve_d=8)

K = 8  # mixture components in every generated dataset
#: Two exact Prim traversals of one dataset build spanning trees of one
#: weight, whatever near-ties they break differently: the relative gap
#: between the two engines' f64 edge sums may only be f32 rounding.
TREE_WEIGHT_RTOL = 1e-6


def mixture(n: int, d: int, seed: int, sep: float = 3.0):
    """Seeded K-component Gaussian mixture (d >= K), points in random
    order.  Unit-variance components around mutually orthogonal centers
    of norm sep·sqrt(d): every pair of components is equally far apart
    in euclidean and in cosine geometry, so k_est has one right answer."""
    import numpy as np
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(d, K)))
    centers = q.T * (sep * np.sqrt(d))
    labels = rng.integers(0, K, size=n)
    return (centers[labels] + rng.normal(size=(n, d))).astype(np.float32)


def log(msg: str) -> None:
    print(msg, flush=True)


class Run:
    """Phase runner: times each phase, keeps going after a failure."""

    def __init__(self):
        self.failed: list[str] = []

    def phase(self, name: str, fn) -> None:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — report every phase, then fail
            self.failed.append(name)
            log(f"[phase {name}] FAILED after "
                f"{time.perf_counter() - t0:.3f} s")
            traceback.print_exc(file=sys.stdout)
            return
        log(f"[phase {name}] ok, wall {time.perf_counter() - t0:.3f} s "
            "(one bring-up run, not a benchmark)")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def is_permutation(order, n: int) -> bool:
    import numpy as np
    return np.array_equal(np.sort(np.asarray(order)), np.arange(n))


def pallas_in_program(fn, *args, on_tpu: bool) -> dict:
    """Dispatch census of fn's program (``kernel_dispatch_stats``); on a
    TPU also require the Mosaic kernel (``tpu_custom_call``) in its
    lowered text."""
    import jax
    from repro.kernels import ops as kops
    stats = kops.kernel_dispatch_stats(fn, *args)
    if on_tpu:
        check("tpu_custom_call" in jax.jit(fn).lower(*args).as_text(),
              "no tpu_custom_call in the lowered program")
    return stats


# ------------------------------------------------------------- phases ----

def exact_phases(run: Run, sz: Sizes, on_tpu: bool) -> None:
    import jax
    import numpy as np
    from repro import FastVAT, core

    X = mixture(sz.exact_n, sz.exact_d, seed=1)
    method = sz.exact_method
    fits = {}

    def fit(use_pallas: bool):
        fv = FastVAT(method=method, use_pallas=use_pallas).fit(X)
        jax.block_until_ready(fv.result.order)
        check(fv.method_resolved == "flashvat",
              f"method {fv.method_resolved!r}, want flashvat")
        order = np.asarray(fv.order())
        check(is_permutation(order, sz.exact_n), "order is no permutation")
        k_est = fv.assess()["k_est"]
        check(k_est == K, f"k_est {k_est}, want {K}")
        fits[use_pallas] = fv
        log(f"  flashvat use_pallas={use_pallas}: n={sz.exact_n} "
            f"d={sz.exact_d} k_est={k_est}")

    def default():
        fit(False)

    def pallas():
        fit(True)
        form = fits[True].result.meta.numerics.form
        stats = pallas_in_program(
            lambda A: core.vat_matrix_free(A, form=form,
                                           use_pallas=True).order,
            jax.numpy.asarray(X), on_tpu=on_tpu)
        # one loop-free pallas_call is the megakernel; the XLA mirror
        # fallback would leave only the seed scan's looped tiles
        check(stats["persistent"] == 1,
              f"megakernel not taken (dispatch census {stats})")
        log(f"  megakernel in program: {stats}")
        if False in fits:
            a = np.asarray(fits[False].order())
            b = np.asarray(fits[True].order())
            diff = int(np.sum(a != b))
            log(f"  pallas vs default order: {diff} of {sz.exact_n} "
                "positions differ" + ("" if diff else " (equal)"))
        # an order that differs must still be an exact Prim traversal:
        # both engines on the same input, spanning-tree weights compared
        Xj = jax.numpy.asarray(X)
        weight = {}
        for use_pallas in (False, True):
            r = core.vat_matrix_free(Xj, form=form, use_pallas=use_pallas)
            weight[use_pallas] = float(np.sum(np.asarray(r.edges, np.float64)))
        rel = abs(weight[True] - weight[False]) / weight[False]
        log(f"  spanning-tree weight: mirror {weight[False]!r}, megakernel "
            f"{weight[True]!r}, relative gap {rel:.3g} "
            f"(limit {TREE_WEIGHT_RTOL:g})")
        check(rel <= TREE_WEIGHT_RTOL,
              f"tree weights differ by {rel:.3g} relative")

    run.phase("exact/default", default)
    run.phase("exact/pallas", pallas)


def oracle_phase(run: Run, sz: Sizes) -> None:
    from repro.numerics import NumericsPolicy
    from repro.numerics.certify import DEFAULT_METHODS, certify_fit

    def body():
        bad = []
        for metric, d in sz.oracle_cells:
            X = mixture(sz.oracle_n, d, seed=2)
            for use_pallas in (False, True):
                for method in DEFAULT_METHODS:
                    slack = 0.0
                    if method == "approx":   # the rung's own kNN defect
                        slack = certify_fit(
                            X, method="approx", metric=metric,
                            policy=NumericsPolicy(mode="safe"),
                            use_pallas=use_pallas).excess
                    r = certify_fit(X, method=method, metric=metric,
                                    use_pallas=use_pallas, slack=slack)
                    log(f"  oracle n={sz.oracle_n} d={d:<4} {method:<8} "
                        f"{metric:<9} "
                        f"pallas={use_pallas!s:<5} exact={r.exact!s:<5} "
                        f"excess={r.excess:.3g} bound={r.bound:.3g} "
                        f"{'ok' if r.ok else 'FAIL'}")
                    if not r.ok:
                        bad.append((method, metric, d, use_pallas))
        check(not bad, f"cells off the oracle: {bad}")

    run.phase("oracle", body)


def embed_phase(run: Run, sz: Sizes, on_tpu: bool) -> None:
    import jax
    from repro import FastVAT
    from repro.kernels import ops as kops

    E = mixture(sz.embed_n, sz.embed_d, seed=3)

    def fit(use_pallas: bool):
        def body():
            fv = FastVAT(method=sz.embed_method, metric="cosine",
                         use_pallas=use_pallas).fit(E)
            jax.block_until_ready(fv.result.order)
            check(fv.method_resolved == "approx",
                  f"method {fv.method_resolved!r}, want approx")
            check(is_permutation(fv.order(), sz.embed_n),
                  "order is no permutation")
            k_est = fv.assess()["k_est"]
            check(k_est == K, f"k_est {k_est}, want {K}")
            mode = fv.result.meta.approx.mode
            msg = (f"  approx cosine use_pallas={use_pallas}: "
                   f"kNN {mode}, k_est={k_est}")
            if use_pallas:
                # the Pallas kernel the fit's kNN stage runs: the top-k
                # fold (exact mode) or the pairwise tile of the blocked
                # anchor-assignment pass (anchored mode), at its shapes
                Ej = jax.numpy.asarray(E)
                if mode == "exact":
                    k = fv.knn_k
                    stats = pallas_in_program(
                        lambda A: kops.knn_graph(A, k=k, metric="cosine",
                                                 use_pallas=True)[1],
                        Ej, on_tpu=on_tpu)
                else:
                    anchors = max(32, round(sz.embed_n ** 0.5))
                    stats = pallas_in_program(
                        lambda A, B: kops.pairwise_dist(
                            A, B, metric="cosine", use_pallas=True),
                        Ej[:8192], Ej[:anchors], on_tpu=on_tpu)
                check(stats["pallas_calls"] >= 1, "kNN-stage kernel not taken")
                msg += f", kernel {stats}"
            log(msg)
        return body

    run.phase("embedding/default", fit(False))
    run.phase("embedding/pallas", fit(True))


def serve_phase(run: Run, sz: Sizes, on_tpu: bool) -> None:
    import numpy as np
    from repro import FastVAT
    from repro.numerics import resolve
    from repro.serve import ServeConfig, TendencyServer
    from repro.serve.server import trace_census

    # two requests per (n, metric) key: each key's pair coalesces into
    # one 2-lane batch inside the window, so warming batch=2 covers all
    reqs = [(n, metric, mixture(n, sz.serve_d, seed=10 + i))
            for i, (n, metric) in enumerate(
                (n, m) for n in sz.serve_ns for m in ("euclidean", "cosine")
                for _ in range(2))]

    def body():
        cfg = ServeConfig(use_pallas=True, window_s=2.0, max_batch=8)
        with TendencyServer(cfg) as srv:
            for n, metric, X in reqs[::2]:
                _, rep = resolve(X, metric=metric)
                srv.warm(n, sz.serve_d, metric=metric, batch=2,
                         num_form=rep.form, num_dtype=rep.dtype)
            warm = srv.stats()
            traces0 = trace_census()["traces"]
            with ThreadPoolExecutor(max_workers=len(reqs)) as pool:
                futs = list(pool.map(
                    lambda r: srv.submit(r[2], metric=r[1], timeout_s=600),
                    reqs))
                served = [f.result() for f in futs]
            st = srv.stats()
            res = st.resilience
            for field in ("fallbacks", "degraded", "splits", "retries",
                          "failed", "numerics_fallbacks"):
                check(getattr(res, field) == 0,
                      f"resilience.{field} = {getattr(res, field)}")
            check(trace_census()["traces"] == traces0,
                  "warm requests traced again")
            check(st.cache.misses == warm.cache.misses,
                  f"cache misses {warm.cache.misses} -> {st.cache.misses}")
            if on_tpu:
                for key, prog in srv.programs().items():
                    check("tpu_custom_call" in prog.as_text(),
                          f"no Pallas kernel in served program {key}")
            log(f"  served {len(reqs)} requests in "
                f"{st.dispatched_batches} batches; programs "
                f"{st.cache.size}, cache misses {st.cache.misses}, "
                f"resilience all zero")
        for (n, metric, X), res in zip(reqs, served):
            solo = FastVAT(metric=metric, use_pallas=True).fit(X)
            a, b = np.asarray(res.order), np.asarray(solo.order())
            check(np.array_equal(a, b),
                  f"served != solo at n={n} {metric} ({res.meta.method}): "
                  f"{int(np.sum(a != b))} positions differ")
        log(f"  every served order equals its solo FastVAT.fit")

    run.phase("serve", body)


def four_chip_phase(run: Run, sz: Sizes) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro import core

    devs = jax.devices()
    X = mixture(sz.exact_n, sz.exact_d, seed=4)

    def body():
        check(len(devs) >= 4, f"--four-chips needs 4 devices, found "
                              f"{len(devs)}")
        mesh = Mesh(np.array(devs[:4]), ("data",))
        for use_pallas in (False, True):
            t0 = time.perf_counter()
            sh = core.vat_matrix_free_sharded(X, mesh, use_pallas=use_pallas)
            jax.block_until_ready(sh.order)
            t1 = time.perf_counter()
            solo = core.vat_matrix_free(jax.device_put(X, devs[0]),
                                        use_pallas=use_pallas)
            jax.block_until_ready(solo.order)
            t2 = time.perf_counter()
            a, b = np.asarray(sh.order), np.asarray(solo.order)
            check(is_permutation(a, sz.exact_n), "sharded order")
            log(f"  use_pallas={use_pallas}: sharded {t1 - t0:.3f} s, "
                f"solo {t2 - t1:.3f} s (one run, compile included); "
                f"{int(np.sum(a != b))} positions differ")
            check(np.array_equal(a, b),
                  f"sharded != solo order (use_pallas={use_pallas})")

    run.phase("four-chips/sharded-vs-solo", body)


# --------------------------------------------------------------- main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded exact engine on 4 devices "
                         "against the solo engine on device 0")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend (CPU tests)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    count = len(jax.devices())
    log(f"jax {jax.__version__}: platform={dev.platform} "
        f"kind={dev.device_kind!r} count={count}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              "refusing to run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    sz = REHEARSE if args.rehearse else FULL
    run = Run()
    if args.four_chips:
        four_chip_phase(run, sz)
    else:
        exact_phases(run, sz, on_tpu)
        oracle_phase(run, sz)
        embed_phase(run, sz, on_tpu)
        serve_phase(run, sz, on_tpu)
    if run.failed:
        log(f"failed phases: {run.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

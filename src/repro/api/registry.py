"""The rung registry: method names -> fitters + capability flags.

``FastVAT`` is pure data-driven dispatch over this table — it never
branches on a method name.  Each ``Rung`` entry owns:

  * ``fit`` / ``fit_batch``: adapters that run a ``repro.core`` rung and
    wrap its output into the uniform ``TendencyResult``,
  * capability flags (``supports_batch`` via ``fit_batch``,
    ``supports_precomputed``, ``max_n``, an optional ``check`` hook for
    environment requirements like dvat's device count),
  * the auto-selection threshold (``auto_threshold``; None = opt-in
    only, ``inf`` = the unbounded fallback rung).

Third-party rungs (a ConiVAT-style constrained VAT, a DeepVAT embedding
pipeline) register here and immediately work through ``FastVAT`` and
``select_method`` without touching the facade:

>>> from repro.api import registry
>>> sorted(registry.registered())
['approx', 'bigvat', 'dvat', 'embed', 'flashvat', 'ivat', 'svat', 'vat']
>>> registry.select_method(100), registry.select_method(10_000)
('vat', 'flashvat')
>>> registry.select_method(1_000_000)
'approx'
>>> registry.get_rung("bigvat").supports_batch
False
>>> registry.get_rung("vat").supports_precomputed
True
>>> registry.get_rung("flashvat").supports_precomputed  # never holds (n,n)
False
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import core
from repro.api.result import SALT_FIT, ResultMeta, TendencyResult
from repro.kernels import ops as kops

#: Auto-selection thresholds (see docs/scaling.md): materialized exact
#: VAT below SMALL_N, matrix-free exact VAT (flashvat) to MEDIUM_N, the
#: kNN-graph Boruvka approximation (approx) beyond — the million-point
#: rung.  sVAT and bigvat (the sampled approximations the exact/approx
#: ladder obsoletes in their former windows) stay registered as opt-in
#: rungs.  The Turbo persistent engine (ISSUE 5) cut flashvat's per-fit
#: wall time ~4x, so its practical ceiling rose from 20k to 50k points.
SMALL_N = 2_048
MEDIUM_N = 50_000

#: Smallest n the flashvat rung auto-shards over a multi-device mesh;
#: below it the per-step collectives cost more than they parallelize.
FLASH_SHARD_MIN_N = 4_096


class RungOptions(NamedTuple):
    """Facade knobs forwarded to a fitter (metric/seed/pallas ride on
    ``ResultMeta``).

    ``turbo`` picks the flashvat traversal engine: None (default) lets
    the rung auto-select — the persistent Turbo engine solo, the sharded
    engine when more than one device is visible and n is worth the
    collectives; True forces the SOLO persistent engine (opting out of
    auto-sharding); False forces the PR-4 stepwise engine (solo only).

    ``knn_k`` is the approx rung's accuracy knob: neighbours kept per
    point in the kNN graph its Boruvka MST runs over.  Larger k tightens
    the kNN-MST toward the exact MST (identical at k = n-1) at O(n·k)
    memory and time; the error actually incurred is reported on
    ``ResultMeta.approx``.

    ``encoder`` is the "embed" rung's model hook: a callable mapping the
    fit input to an (n, d) activation matrix (DeepVAT-style).  The
    facade encodes before dispatch and leaves this None; set it when
    driving the rung directly through the registry.

    ``num_form`` is the numerics shield's tile-form plan: "gram"
    (default — the ‖x‖²+‖y‖²−2x·y trick, MXU-friendly) or "direct"
    (per-coordinate (x−y)², no cancellation).  The facade sets it from
    ``numerics.resolve``'s static dispatch decision; it is threaded to
    every distance/traversal kernel a rung runs (see docs/numerics.md).
    """
    sample_size: int = 256
    block: int = 4096
    turbo: bool | None = None
    knn_k: int = 15
    encoder: Any = None
    num_form: str = "gram"


Fitter = Callable[[Any, ResultMeta, RungOptions], TendencyResult]


@dataclasses.dataclass(frozen=True)
class LatencyModel:
    """Per-rung wall-time model — the SLO router's cost data (ISSUE 7).

    ``predict_us(n, batch) = base_us + batch * (per_point_us * n +
    per_sq_point_us * n^2)``.  Coefficients are calibrated against the
    committed ``BENCH_*.json`` trajectory (CPU numbers from this repo's
    flash/turbo/approx/table4 rows — recalibrate when an accelerator
    trajectory exists); they exist to *rank* rungs and gate SLOs, they
    are not latency promises.

    Attributes:
      base_us: fixed dispatch + host-glue cost per fit.
      per_point_us: O(n) coefficient (kNN edges, sampling passes).
      per_sq_point_us: O(n^2) coefficient (materialized matrices, the
        matrix-free engines' recompute work).
      cap_n: feasibility ceiling — e.g. the O(n^2) matrix memory wall of
        the materialized rungs; the router never offers a rung past it
        no matter how generous the SLO.
    """

    base_us: float
    per_point_us: float = 0.0
    per_sq_point_us: float = 0.0
    cap_n: int | None = None

    def predict_us(self, n: int, batch: int = 1) -> float:
        """Predicted wall microseconds for a (batch, n, d)-ish fit."""
        per = self.per_point_us * n + self.per_sq_point_us * float(n) * n
        return self.base_us + batch * per

    def feasible(self, n: int) -> bool:
        """Whether the rung is offered at all at this n."""
        return self.cap_n is None or n <= self.cap_n


def predict_latency_us(method: str, n: int, *, batch: int = 1) -> float | None:
    """Predicted fit latency of a registered rung; None when unmodeled."""
    model = get_rung(method).latency_model
    return None if model is None else model.predict_us(n, batch=batch)


def select_method_for_slo(n: int, slo_us: float, *, batch: int = 1,
                          restrict=None) -> str:
    """Pick the rung to run under a latency SLO (the serving router).

    Policy: among the feasible, latency-modeled rungs (optionally
    restricted to a candidate set), return the **highest-fidelity rung
    the budget affords** — fidelity read from each rung's explicit
    ``fidelity`` rank (ivat's geodesic image > vat's raw image >
    flashvat's band render > the sampled/approx rungs), NOT proxied by
    predicted cost: fixed dispatch overhead (flashvat's base cost
    dominates at small n) would otherwise make the router buy a
    *costlier but coarser* picture.  Ties in fidelity go to the
    cheaper rung.  When no candidate fits the SLO, degrade gracefully
    to the cheapest feasible rung (best effort beats an error under
    load); callers that need a hard guarantee compare
    ``predict_latency_us`` against the SLO themselves.

    Args:
      n: points per dataset.
      slo_us: the latency budget in microseconds.
      batch: datasets per dispatch (coalesced serving amortizes base
        cost but multiplies per-dataset work).
      restrict: iterable of method names to choose among; None means
        every registered rung with a latency model.

    Returns:
      The selected method name.

    Raises:
      LookupError: no feasible modeled candidate exists.
    """
    names = tuple(restrict) if restrict is not None else registered()
    cands = []
    for name in names:
        model = get_rung(name).latency_model
        if model is not None and model.feasible(n):
            cands.append((name, model.predict_us(n, batch=batch)))
    if not cands:
        raise LookupError(
            f"no latency-modeled rung is feasible at n={n} "
            f"(candidates considered: {list(names)})")
    fitting = [c for c in cands if c[1] <= slo_us]
    if fitting:
        return max(fitting,
                   key=lambda c: (get_rung(c[0]).fidelity, -c[1]))[0]
    return min(cands, key=lambda c: c[1])[0]


@dataclasses.dataclass(frozen=True)
class Rung:
    """One registered VAT method.

    Attributes:
      name: the ``method=`` string.
      fit: solo fitter — (X_or_D, meta, options) -> TendencyResult.
      fit_batch: batched fitter over a (b, n, d) stack (or (b, n, n)
        precomputed stack); None means the rung doesn't batch.
      supports_precomputed: accepts metric="precomputed" input.
      auto_threshold: largest n ``select_method`` hands this rung
        (math.inf = unbounded fallback); None = never auto-selected.
      max_n: hard cap enforced at fit time; None = uncapped.
      check: optional environment validation hook, called with n before
        fitting (e.g. dvat's device-count requirements).
      latency_model: calibrated wall-time model for SLO routing
        (``select_method_for_slo``); None = the rung is never offered
        by the router (it stays reachable via explicit ``method=``).
      fidelity: explicit rank of how faithful the rung's picture is
        (higher = more faithful; exact geodesic > exact raw > banded
        render > sampled/approximate).  The SLO router picks the
        highest-fidelity rung fitting the budget — fidelity is ranked
        explicitly rather than proxied by cost, because fixed dispatch
        overhead can make a coarser rung predict costlier at small n.
        Third-party rungs slot in relative to the built-in ranks.
      description: one-liner for docs/tooling.
    """

    name: str
    fit: Fitter
    fit_batch: Fitter | None = None
    supports_precomputed: bool = False
    auto_threshold: float | None = None
    max_n: int | None = None
    check: Callable[[int], None] | None = None
    latency_model: LatencyModel | None = None
    fidelity: float = 0.0
    description: str = ""

    @property
    def supports_batch(self) -> bool:
        return self.fit_batch is not None


_REGISTRY: dict[str, Rung] = {}


def register(rung: Rung, *, overwrite: bool = False) -> Rung:
    """Add a rung; its name becomes a valid ``FastVAT(method=...)``.

    Args:
      rung: the entry to add. ``name`` must not be "auto".
      overwrite: allow replacing an existing entry of the same name.

    Returns:
      The registered rung (for decorator-ish chaining).
    """
    if rung.name == "auto" or not rung.name:
        raise ValueError(f"invalid rung name {rung.name!r}")
    if rung.name in _REGISTRY and not overwrite:
        raise ValueError(f"rung {rung.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[rung.name] = rung
    return rung


def get_rung(name: str) -> Rung:
    """Look up a registered rung by method name."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown method {name!r}; registered: "
                       f"{registered()}") from None


def registered() -> tuple[str, ...]:
    """Names of every registered rung (live — includes third-party)."""
    return tuple(_REGISTRY)


def methods() -> tuple[str, ...]:
    """Everything ``FastVAT(method=...)`` accepts: "auto" + the rungs."""
    return ("auto",) + registered()


def select_method(n: int, *, precomputed: bool = False,
                  batched: bool = False, strict: bool = False) -> str:
    """The auto-selection policy, data-driven over rung capabilities.

    Args:
      n: points per dataset.
      precomputed: restrict to rungs accepting metric="precomputed".
      batched: restrict to rungs with a batched fitter.
      strict: raise LookupError when no candidate's threshold covers n
        instead of falling back to the largest-threshold candidate (the
        fallback serves precomputed input, where the O(n^2) matrix
        already exists so the exact rung stays the right answer).

    Returns:
      The selected method name.
    """
    cands = [r for r in _REGISTRY.values() if r.auto_threshold is not None]
    if precomputed:
        cands = [r for r in cands if r.supports_precomputed]
    if batched:
        cands = [r for r in cands if r.supports_batch]
    cands.sort(key=lambda r: r.auto_threshold)
    if not cands:
        raise LookupError("no auto-selectable rung matches "
                          f"(precomputed={precomputed}, batched={batched})")
    for r in cands:
        if n <= r.auto_threshold:
            return r.name
    if strict:
        raise LookupError(f"no auto-selectable rung covers n={n}")
    return cands[-1].name


# ---------------------------------------------------------------------
# Built-in rung fitters: run a repro.core rung, wrap into TendencyResult.
# ---------------------------------------------------------------------

def _as_f32(X) -> jax.Array:
    return X if isinstance(X, jax.Array) else jnp.asarray(
        np.asarray(X, np.float32))


def _vat_result(data, meta: ResultMeta, opts: RungOptions) -> core.VATResult:
    if meta.metric == "precomputed":
        return core.vat_from_dist(_as_f32(data))
    return core.vat(_as_f32(data), use_pallas=meta.use_pallas,
                    metric=meta.metric, form=opts.num_form)


def _vat_result_batch(data, meta: ResultMeta,
                      opts: RungOptions) -> core.VATResult:
    if meta.metric == "precomputed":
        return core.vat_batch_from_dist(_as_f32(data))
    return core.vat_batch(_as_f32(data), use_pallas=meta.use_pallas,
                          metric=meta.metric, form=opts.num_form)


def _fit_vat(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    res = _vat_result(data, meta, opts)
    return TendencyResult(order=res.order, rstar=res.rstar, ivat_image=None,
                          sample_idx=None, extension_labels=None, meta=meta)


def _fit_vat_batch(data, meta: ResultMeta,
                   opts: RungOptions) -> TendencyResult:
    res = _vat_result_batch(data, meta, opts)
    return TendencyResult(order=res.order, rstar=res.rstar, ivat_image=None,
                          sample_idx=None, extension_labels=None, meta=meta)


def _fit_ivat(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    res = _vat_result(data, meta, opts)
    iv = core.ivat_from_vat(res.rstar, use_pallas=meta.use_pallas)
    return TendencyResult(order=res.order, rstar=res.rstar, ivat_image=iv,
                          sample_idx=None, extension_labels=None, meta=meta)


def _fit_ivat_batch(data, meta: ResultMeta,
                    opts: RungOptions) -> TendencyResult:
    res = _vat_result_batch(data, meta, opts)
    iv = core.ivat_from_vat(res.rstar, use_pallas=meta.use_pallas)
    return TendencyResult(order=res.order, rstar=res.rstar, ivat_image=iv,
                          sample_idx=None, extension_labels=None, meta=meta)


def _fit_svat(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    res = core.svat(_as_f32(data), meta.jax_key(SALT_FIT),
                    s=min(opts.sample_size, meta.n),
                    use_pallas=meta.use_pallas, metric=meta.metric)
    return TendencyResult(order=res.vat.order, rstar=res.vat.rstar,
                          ivat_image=None, sample_idx=res.sample_idx,
                          extension_labels=None, meta=meta)


def _fit_bigvat(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    res = core.bigvat(data, meta.jax_key(SALT_FIT), s=opts.sample_size,
                      block=opts.block, use_pallas=meta.use_pallas,
                      metric=meta.metric)
    return TendencyResult(order=res.order, rstar=res.sample.vat.rstar,
                          ivat_image=res.ivat,
                          sample_idx=res.sample.sample_idx,
                          extension_labels=res.labels, meta=meta,
                          group_sizes=res.group_sizes)


def _flash_groups(n: int, m: int):
    """Partition VAT-order positions 0..n-1 into m contiguous groups.

    Returns (sizes (m,) int64, mids (m,) int64): per-group lengths
    (remainder spread over the leading groups) and each group's middle
    position — the representative whose distances render that band.
    """
    base, extra = divmod(n, m)
    sizes = np.full(m, base, np.int64)
    sizes[:extra] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return sizes, starts + sizes // 2


def _rep_ivat(Rrep: jax.Array, use_pallas: bool) -> jax.Array:
    """iVAT image of a representative matrix, returned in band order.

    The Havens-Bezdek recurrence is only valid along a Prim traversal of
    the matrix it is applied to, and the band order (representatives
    sorted by their position in the *full-n* ordering) is generally not
    one — so the geodesics are computed along the representatives' own
    Prim order (``vat_from_dist``) and the result is permuted back to
    band order for rendering.  O(m^2) work on an (m, m) object.
    """
    sres = core.vat_from_dist(Rrep)
    iv_s = core.ivat_from_vat(sres.rstar, use_pallas=use_pallas)
    m = Rrep.shape[0]
    rank = jnp.zeros((m,), jnp.int32).at[sres.order].set(
        jnp.arange(m, dtype=jnp.int32))
    return iv_s[rank][:, rank]


def _flash_order(Xj, meta: ResultMeta, opts: RungOptions):
    """The flashvat rung's engine auto-select (ISSUE 5).

    ``opts.turbo`` None (auto) routes the Turbo persistent engine, or —
    with more than one visible device and n past ``FLASH_SHARD_MIN_N``,
    where the per-step collectives amortize — the X-row-sharded engine
    (same orderings bit for bit, per-device memory divided by P).
    ``turbo=True`` FORCES the solo persistent engine (the documented
    escape hatch from auto-sharding); ``turbo=False`` pins the PR-4
    stepwise engine.  The sharded engine speaks the Gram tile form only,
    so a "direct" numerics plan (``opts.num_form``) pins the solo
    persistent engine instead — conditioned fits trade the mesh for the
    cancellation-free tiles.
    """
    devs = jax.devices()
    if (opts.turbo is None and len(devs) > 1
            and meta.n >= FLASH_SHARD_MIN_N and opts.num_form == "gram"):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devs), ("data",))
        return core.vat_matrix_free_sharded(Xj, mesh, metric=meta.metric,
                                            use_pallas=meta.use_pallas)
    return core.vat_matrix_free(Xj, metric=meta.metric,
                                form=opts.num_form,
                                use_pallas=meta.use_pallas,
                                turbo=True if opts.turbo is None
                                else opts.turbo)


def _band_render(Xj: jax.Array, order: jax.Array, meta: ResultMeta,
                 opts: RungOptions) -> TendencyResult:
    """bigvat-style banded rendering of a full-n ordering.

    The rendering idea is bigvat's in reverse: m = sample_size
    representatives are taken at the middle of m contiguous bands of the
    given full-n ordering, their (m, m) dissimilarity matrix inherits
    that band order, and ``TendencyResult.image`` expands it by the true
    band sizes — so the picture shows all n points while only an (m, m)
    object ever exists.  The iVAT companion runs along the
    representatives' own Prim traversal (see ``_rep_ivat``) and is
    re-indexed to the same bands.  Shared by the flashvat (exact order)
    and approx (kNN-MST order) rungs.
    """
    n, m = meta.n, min(opts.sample_size, meta.n)
    sizes, mids = _flash_groups(n, m)
    rep_idx = order[jnp.asarray(mids)]
    Rrep = kops.pairwise_dist(Xj[rep_idx], use_pallas=meta.use_pallas,
                              metric=meta.metric, form=opts.num_form)
    iv = _rep_ivat(Rrep, meta.use_pallas)
    gid = jnp.asarray(np.repeat(np.arange(m, dtype=np.int32), sizes))
    labels = jnp.zeros((n,), jnp.int32).at[order].set(gid)
    return TendencyResult(order=order, rstar=Rrep, ivat_image=iv,
                          sample_idx=rep_idx, extension_labels=labels,
                          group_sizes=jnp.asarray(sizes, jnp.int32),
                          meta=meta)


def _fit_flashvat(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    """Flash-VAT: exact matrix-free ordering + bigvat-style tiled render.

    The ordering is the exact full-n VAT order (bitwise-identical to the
    materialized path) at O(n·d) memory — computed by the engine
    ``_flash_order`` selects (Turbo persistent / sharded / stepwise) —
    then rendered through the shared ``_band_render`` tail.
    """
    Xj = _as_f32(data)
    res = _flash_order(Xj, meta, opts)
    return _band_render(Xj, res.order, meta, opts)


def _fit_approx(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    """Approx-VAT: kNN-graph Boruvka MST ordering, the million-point rung.

    The ordering comes from ``core.approx_vat`` — a Prim traversal of
    the minimum spanning tree of the k-nearest-neighbour graph (exact
    blocked kNN below its crossover, anchor-partitioned beyond), built
    by a jitted Boruvka fold at O(n·k) edge memory.  It is exact
    whenever the kNN graph contains the true MST (guaranteed at
    k = n-1, typical for modest k on clusterable data); the incurred
    error is measured, not guessed: ``ResultMeta.approx`` carries the
    spanning defect (components before repair, edges the repair pass
    added and their weight) next to the kNN-MST weight, so callers can
    bound the approximation or rerun with a larger ``knn_k``.  Rendering
    shares flashvat's banded tail — no (n, n) object at any stage.
    """
    Xj = _as_f32(data)
    res = core.approx_vat(Xj, k=opts.knn_k, metric=meta.metric,
                          use_pallas=meta.use_pallas)
    meta = dataclasses.replace(meta, approx=res.stats)
    return _band_render(Xj, jnp.asarray(res.order), meta, opts)


def _fit_flashvat_batch(data, meta: ResultMeta,
                        opts: RungOptions) -> TendencyResult:
    """Batched Flash-VAT: one compiled program, per-lane exact orderings."""
    Xj = _as_f32(data)
    res = core.vat_matrix_free_batch(
        Xj, metric=meta.metric, form=opts.num_form,
        use_pallas=meta.use_pallas,
        turbo=True if opts.turbo is None else opts.turbo)
    n, m = meta.n, min(opts.sample_size, meta.n)
    sizes, mids = _flash_groups(n, m)
    rep_idx = res.order[:, jnp.asarray(mids)]                    # (b, m)
    prot = jnp.take_along_axis(Xj, rep_idx[:, :, None], axis=1)  # (b, m, d)
    Rrep = kops.pairwise_dist_batch(prot, use_pallas=meta.use_pallas,
                                    metric=meta.metric, form=opts.num_form)
    iv = jax.vmap(lambda R: _rep_ivat(R, meta.use_pallas))(Rrep)
    gid = jnp.asarray(np.repeat(np.arange(m, dtype=np.int32), sizes))
    labels = jax.vmap(
        lambda o: jnp.zeros((n,), jnp.int32).at[o].set(gid))(res.order)
    return TendencyResult(order=res.order, rstar=Rrep, ivat_image=iv,
                          sample_idx=rep_idx, extension_labels=labels,
                          group_sizes=jnp.asarray(sizes, jnp.int32),
                          meta=meta)


def _fit_embed(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    """The embeddings front-end rung (DeepVAT): assess activations.

    Raw inputs (pixels, tokens) are rarely clusterable; learned
    embeddings are.  This rung maps the input through an encoder —
    ``opts.encoder`` (a callable X -> (n, d) activations), or the data
    is already pre-encoded and ``meta.encoder`` carries the fingerprint
    — then delegates to whatever rung ``select_method`` picks for the
    activation count.  ``meta.method`` stays "embed" and
    ``meta.encoder`` records provenance; everything else (images,
    assess, serving adoption) is the inner rung's standard output.
    """
    enc = opts.encoder
    if callable(enc):
        from repro.monitor.probes import callable_fingerprint
        acts = np.asarray(jax.device_get(enc(data)), np.float32)
        if not meta.encoder:
            meta = dataclasses.replace(meta,
                                       encoder=callable_fingerprint(enc))
    elif meta.encoder:
        acts = np.asarray(data, np.float32)   # pre-encoded by the caller
    else:
        raise ValueError(
            "method='embed' needs an encoder: pass options.encoder (a "
            "callable X -> activations), or pre-encoded activations with "
            "the encoder fingerprint on meta.encoder — e.g. via "
            "FastVAT.fit(X, encoder=...) / FastVAT.fit_embeddings(...)")
    if acts.ndim > 2:
        acts = acts.reshape(-1, acts.shape[-1])
    meta = dataclasses.replace(meta, n=int(acts.shape[0]))
    inner = get_rung(select_method(meta.n))
    return inner.fit(acts, meta, opts)


def _check_dvat(n: int):
    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            f"method='dvat' needs >1 device, found {len(devs)}; "
            "use 'bigvat' on a single host")
    if n % len(devs):
        raise ValueError(
            f"method='dvat' needs n divisible by the device count "
            f"({n} % {len(devs)} != 0); pad or truncate X first")


def _fit_dvat(data, meta: ResultMeta, opts: RungOptions) -> TendencyResult:
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("data",))
    Xj = _as_f32(data)
    dres = core.dvat(Xj, mesh, metric=meta.metric)
    # a maximin-sample image gives dvat the same assessable rstar every
    # other rung carries (the full-n ordering stays the headline output).
    # Cost: one O(n s) maximin pass + an (s, s) VAT at fit time — small
    # next to dvat's own O(n^2 d / P) exact-start pass, and it buys the
    # uniform image()/assess() surface without a lazy special case
    sres = core.svat(Xj, meta.jax_key(SALT_FIT),
                     s=min(opts.sample_size, meta.n),
                     use_pallas=meta.use_pallas, metric=meta.metric)
    return TendencyResult(order=dres.order, rstar=sres.vat.rstar,
                          ivat_image=None, sample_idx=sres.sample_idx,
                          extension_labels=None, meta=meta)


# Latency-model calibration (ISSUE 7): CPU coefficients fitted by eye
# against the committed BENCH_*.json trajectory — flash table (n=8192
# materialized ~0.86 s, persistent matrix-free ~0.15 s; n=100k ~59 s),
# table4 (approx at n=1e6 ~130 s, ~130 us/point), table1/batched for the
# small-n fixed costs.  cap_n = 20_000 is the materialized rungs' (n, n)
# memory wall (1.6 GB f32) — past it the router only offers matrix-free
# rungs regardless of SLO.  dvat carries no model: its cost is
# mesh-shaped, not n-shaped, and the router must not pretend otherwise.
_MATERIALIZE_CAP_N = 20_000

register(Rung(
    name="vat", fit=_fit_vat, fit_batch=_fit_vat_batch,
    supports_precomputed=True, auto_threshold=SMALL_N,
    latency_model=LatencyModel(base_us=3e3, per_point_us=1.5,
                               per_sq_point_us=1.3e-2,
                               cap_n=_MATERIALIZE_CAP_N),
    fidelity=50.0,
    description="exact VAT — O(n^2) matrix fits easily"))
register(Rung(
    name="ivat", fit=_fit_ivat, fit_batch=_fit_ivat_batch,
    supports_precomputed=True, auto_threshold=None,
    latency_model=LatencyModel(base_us=4e3, per_point_us=1.5,
                               per_sq_point_us=3.2e-2,
                               cap_n=_MATERIALIZE_CAP_N),
    fidelity=60.0,
    description="exact VAT + geodesic (iVAT) image; opt-in"))
register(Rung(
    name="svat", fit=_fit_svat, auto_threshold=None,
    latency_model=LatencyModel(base_us=4e3, per_point_us=25.0),
    fidelity=30.0,
    description="maximin sample VAT, O(ns + s^2); opt-in (flashvat "
                "covers its former auto window exactly)"))
register(Rung(
    name="flashvat", fit=_fit_flashvat, fit_batch=_fit_flashvat_batch,
    auto_threshold=MEDIUM_N,
    latency_model=LatencyModel(base_us=2.5e4, per_point_us=4.0,
                               per_sq_point_us=4e-3),
    fidelity=40.0,
    description="matrix-free exact VAT (Flash-VAT): fused streaming "
                "Prim, O(n·d) memory, no (n, n) object"))
register(Rung(
    name="bigvat", fit=_fit_bigvat, auto_threshold=None,
    latency_model=LatencyModel(base_us=2e5, per_point_us=60.0),
    fidelity=20.0,
    description="out-of-core clusiVAT pipeline, no (n, n) object; "
                "opt-in (approx covers its former auto window with a "
                "measured error bound)"))
register(Rung(
    name="approx", fit=_fit_approx, auto_threshold=math.inf,
    latency_model=LatencyModel(base_us=6e5, per_point_us=130.0),
    fidelity=10.0,
    description="kNN-graph Boruvka MST VAT, O(n·k) edges — the "
                "million-point rung; error reported on meta.approx"))
register(Rung(
    name="dvat", fit=_fit_dvat, check=_check_dvat, auto_threshold=None,
    description="matrix-free distributed VAT; needs >1 device"))
register(Rung(
    name="embed", fit=_fit_embed, auto_threshold=None,
    description="embeddings front-end (DeepVAT): encode, then run the "
                "exact/approx ladder on activations; encoder "
                "fingerprint on meta.encoder"))

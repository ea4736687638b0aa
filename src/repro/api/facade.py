"""FastVAT — one front door for every VAT variant in this repo.

Picks the right scaling rung automatically (see ``docs/scaling.md``):

  n <= SMALL_N  (2_048)   exact ``vat``   — O(n^2) matrix fits easily
  n <= MEDIUM_N (50_000)  ``flashvat``    — exact, matrix-free, O(n·d),
                          Turbo persistent engine (auto-sharded on a
                          multi-device mesh)
  larger                  ``approx``      — kNN-graph Borůvka MST VAT,
                          O(n·k) edges, the million-point rung (the
                          ``knn_k`` knob trades error for speed)

``method`` overrides come from the rung registry (``repro.api.registry``)
— "vat" | "ivat" | "svat" | "flashvat" | "bigvat" | "approx" | "dvat" |
"embed" plus anything third-party code registered.  Every rung returns
the same ``TendencyResult`` pytree, so ``order()`` / ``image()`` /
``assess()`` below are branch-free reads.

Deep embeddings (DeepVAT): ``fit(X, encoder=fn)`` runs the ladder on
``fn(X)`` activations instead of raw inputs, and
``fit_embeddings(params, cfg, batch)`` does the same for a model from
the repo zoo — see docs/monitoring.md.

>>> import numpy as np
>>> rng = np.random.default_rng(0)
>>> X = np.concatenate([rng.normal(size=(30, 3)),
...                     rng.normal(size=(30, 3)) + 8]).astype(np.float32)
>>> fv = FastVAT().fit(X)                # auto-selects by n
>>> fv.method_resolved
'vat'
>>> fv.image().shape
(60, 60)
>>> rep = fv.assess()                    # TendencyReport, dict-like
>>> (rep["method"], rep["k_est"], rep["clustered"])
('vat', 2, True)

Any pairwise dissimilarity works — computed (``metric=``) or handed in
directly (``metric="precomputed"``):

>>> from repro.kernels import ops as kops
>>> D = np.asarray(kops.pairwise_dist(X))           # any (n, n) matrix
>>> fd = FastVAT(metric="precomputed").fit(D)
>>> bool(np.array_equal(fd.order(), fv.order()))
True

Batched: a (b, n, d) stack of datasets is assessed in one compiled
program (see ``docs/api.md``):

>>> Xs = np.stack([X, X[::-1]])
>>> fb = FastVAT(method="ivat").fit_many(Xs)
>>> fb.image().shape
(2, 60, 60)
>>> [r["batch_index"] for r in fb.assess()]
[0, 1]
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro import core, spans
from repro.api import registry
from repro.api.metrics import as_dissimilarity, validate_metric
from repro.api.registry import SMALL_N, RungOptions, select_method
from repro.api.validation import (InvalidInput, validate_dissimilarity,
                                  validate_points)
from repro.api.result import (SALT_ASSESS, SALT_HOPKINS, ResultMeta,
                              TendencyReport, TendencyResult)
from repro.core.bigvat import DEFAULT_BLOCK
from repro.numerics import NumericsReport, as_policy
from repro.numerics import resolve as resolve_numerics

#: Method names at import time ("auto" + built-in rungs). The live list —
#: including later third-party registrations — is ``registry.methods()``.
METHODS = registry.methods()


class FastVAT:
    """Facade over the registered rungs with auto-selection.

    Parameters
    ----------
    method:       "auto" or any name in ``registry.methods()``; "auto"
                  picks by n at fit time.
    metric:       dissimilarity metric — one of ``repro.api.METRICS``:
                  "euclidean" | "sqeuclidean" | "manhattan" | "cosine",
                  or "precomputed" to pass ``fit`` an (n, n) matrix
                  directly (exact rungs only).
    sample_size:  s for svat/bigvat prototypes and flashvat's rendered
                  representative count.
    block:        row-block size of bigvat's tiled assignment pass.
    use_pallas:   route distance/iVAT work through the Pallas kernels
                  (interpret mode on CPU; compiled on TPU).
    turbo:        flashvat traversal engine — None (default) auto-selects
                  the persistent Turbo engine (and the sharded engine on
                  a multi-device mesh); True forces the solo persistent
                  engine (opting out of auto-sharding); False pins the
                  stepwise engine.  Orderings are identical either way;
                  only the wall clock moves.
    knn_k:        the approx rung's error-bound knob — neighbours per
                  point in its kNN graph.  Larger k costs linearly more
                  and drives the kNN-MST weight monotonically down to
                  the exact MST weight (reached at k = n-1); the fit's
                  ``ResultMeta.approx`` reports the realized error
                  model (components repaired, repair weight).
    seed:         the single seed every sampling path (device and host
                  side) derives from — see ``ResultMeta``.
    validate:     admission-check inputs before they reach a kernel
                  (one O(n·d) pass: finite values, real dtype, n >= 4,
                  non-degenerate, no zero-norm rows under cosine) and
                  fail with the typed ``InvalidInput`` — the kernels'
                  min/argmin folds are silent on NaN/Inf and would
                  return garbage orderings otherwise.  ``False`` skips
                  the pass for trusted hot loops.
    numerics:     the numerics shield's policy — a
                  ``repro.numerics.NumericsPolicy`` or a mode string
                  ("fast" | "safe" | "auto", default "auto").  Before
                  dispatch, ``numerics.resolve`` estimates the Gram
                  -cancellation condition κ and picks the tile form
                  (Gram vs direct) plus the conditioning transform;
                  what actually ran lands on
                  ``result.meta.numerics`` (a ``NumericsReport``).
                  Precomputed and np.memmap input bypass the pre-pass
                  (no point coordinates / out-of-core respectively).
    """

    def __init__(self, method: str = "auto", *, metric: str = "euclidean",
                 sample_size: int = 256, block: int = DEFAULT_BLOCK,
                 use_pallas: bool = False, turbo: bool | None = None,
                 knn_k: int = 15, seed: int = 0, validate: bool = True,
                 numerics="auto"):
        methods = registry.methods()
        if method not in methods:
            raise ValueError(f"method must be one of {methods}, "
                             f"got {method!r}")
        validate_metric(metric)
        self.method = method
        self.metric = metric
        self.sample_size = sample_size
        self.block = block
        self.use_pallas = use_pallas
        self.turbo = turbo
        self.knn_k = knn_k
        self.seed = seed
        self.validate = validate
        self.numerics = as_policy(numerics)
        self.method_resolved: str | None = None
        self.result: TendencyResult | None = None
        self._X = None

    @property
    def batched(self) -> bool:
        """True after ``fit_many`` (the result carries a batch axis)."""
        return self.result is not None and self.result.is_batched

    @classmethod
    def from_result(cls, result: TendencyResult, X=None) -> "FastVAT":
        """Adopt an externally produced fit (e.g. a served one).

        The serving layer (``repro.serve``) returns bare
        ``TendencyResult`` pytrees; wrapping one here restores the full
        facade surface — ``order()`` / ``image()`` / ``assess()`` —
        configured from the result's own meta, so a served fit assesses
        identically to the solo ``FastVAT(...).fit(X)`` it mirrors.

        Args:
          result: a fit result from any rung (solo or batched).
          X: the original dataset(s); required for ``assess()`` on
            non-precomputed metrics (the Hopkins probe needs points).

        Returns:
          A fitted facade (``fit`` was effectively already called).
        """
        m = result.meta
        fv = cls(method=m.method, metric=m.metric,
                 sample_size=(m.sample_size if m.sample_size is not None
                              else 256),
                 use_pallas=m.use_pallas, seed=m.seed)
        fv.result = result
        fv.method_resolved = m.method
        fv._X = None if X is None else np.asarray(X)
        return fv

    def _meta(self, method: str, n: int, batch: int | None,
              numerics: NumericsReport | None = None) -> ResultMeta:
        return ResultMeta(method=method, metric=self.metric, n=n,
                          batch=batch, seed=self.seed,
                          sample_size=self.sample_size,
                          use_pallas=self.use_pallas, numerics=numerics)

    def _options(self, num_form: str = "gram") -> RungOptions:
        return RungOptions(sample_size=self.sample_size, block=self.block,
                           turbo=self.turbo, knn_k=self.knn_k,
                           num_form=num_form)

    def _numerics_prepass(self, X, *, batched: bool = False):
        """Run the numerics shield on point input; (X', report | None).

        np.memmap input is passed through untouched — the conditioning
        transform would materialize an O(n·d) RAM copy and defeat the
        bigvat rung's out-of-core contract.
        """
        if isinstance(X, np.memmap):
            return X, None
        return resolve_numerics(X, metric=self.metric,
                                policy=self.numerics, batched=batched)

    # ------------------------------------------------------------- fit ----

    @spans.span("fastvat.fit")
    def fit(self, X, *, encoder=None) -> "FastVAT":
        """Run the resolved rung on one dataset.

        Args:
          X: (n, d) array-like of points (np.memmap ok for bigvat), or —
            with ``metric="precomputed"`` — an (n, n) dissimilarity
            matrix (square, symmetric, zero diagonal).
          encoder: route through the "embed" front-end rung
            (DeepVAT-style).  A callable maps X to an (n, d) activation
            matrix (any leading shape; flattened to rows) which the
            ladder then assesses; a string means X is *already* the
            activation matrix and the string is its encoder fingerprint.
            Either way ``result.meta.encoder`` records provenance and
            the inner rung is auto-selected by activation count.

        Returns:
          self; ``self.result`` is the rung's ``TendencyResult``.
        """
        if encoder is not None:
            return self._fit_embed_front(X, encoder)
        precomputed = self.metric == "precomputed"
        if precomputed:
            if self.validate:
                validate_dissimilarity(X)
            X = as_dissimilarity(X)
        elif self.validate and self.method != "embed":
            # the embed rung validates its *activations* (see
            # _fit_embed_front); raw fit(X) without an encoder is the
            # rung's own "encoder required" error, not an admission case
            validate_points(X, metric=self.metric)
        num_report = None
        if not precomputed and self.method != "embed":
            X, num_report = self._numerics_prepass(X)
        n = int(X.shape[0])
        method = (self.method if self.method != "auto"
                  else select_method(n, precomputed=precomputed))
        rung = registry.get_rung(method)
        if precomputed and not rung.supports_precomputed:
            ok = [r for r in registry.registered()
                  if registry.get_rung(r).supports_precomputed]
            raise ValueError(f"method {method!r} does not accept "
                             f"metric='precomputed'; rungs that do: {ok}")
        if rung.max_n is not None and n > rung.max_n:
            raise ValueError(f"method {method!r} caps at n={rung.max_n}, "
                             f"got n={n}")
        if rung.check is not None:
            rung.check(n)
        meta = self._meta(method, n, batch=None, numerics=num_report)
        self.result = rung.fit(X, meta, self._options(
            num_report.form if num_report is not None else "gram"))
        self.method_resolved = method
        self._X = X
        return self

    def _fit_embed_front(self, X, encoder) -> "FastVAT":
        """fit(X, encoder=...) tail: encode, then run the embed rung.

        Encoding happens here (not inside the rung fitter) so the
        activations become ``self._X`` — ``assess()``'s Hopkins probe
        then reads the embedding space the fit actually assessed, the
        DeepVAT semantics.
        """
        from repro.monitor.probes import callable_fingerprint
        if self.metric == "precomputed":
            raise ValueError("encoder= assesses activations; it is "
                             "incompatible with metric='precomputed'")
        if self.method not in ("auto", "embed"):
            raise ValueError("encoder= routes through the 'embed' rung; "
                             "method must be 'auto' or 'embed', got "
                             f"{self.method!r}")
        if callable(encoder):
            acts = np.asarray(jax.device_get(encoder(X)), np.float32)
            fingerprint = callable_fingerprint(encoder)
        else:
            acts = np.asarray(X, np.float32)
            fingerprint = str(encoder)
        if acts.ndim > 2:
            acts = acts.reshape(-1, acts.shape[-1])
        if self.validate:
            validate_points(acts, name="activations", metric=self.metric)
        acts, num_report = self._numerics_prepass(acts)
        n = int(acts.shape[0])
        meta = dataclasses.replace(
            self._meta("embed", n, batch=None, numerics=num_report),
            encoder=fingerprint)
        self.result = registry.get_rung("embed").fit(
            acts, meta,
            self._options(num_report.form if num_report is not None
                          else "gram"))
        self.method_resolved = "embed"
        self._X = acts
        return self

    def fit_embeddings(self, params, cfg, batch) -> "FastVAT":
        """Assess the cluster tendency of a model's activations.

        The DeepVAT workflow for the repo's model zoo: run one forward
        pass, flatten the final hidden states to (batch*seq, d_model)
        rows, and route them through the "embed" rung (which delegates
        to the exact/approx ladder by activation count).  The model's
        fingerprint — architecture identity + a weights digest — lands
        on ``result.meta.encoder``.

        Args:
          params: model parameter pytree (``models.model.init_params``).
          cfg: the ``ModelConfig`` matching params.
          batch: input batch dict (``data.tokens.make_batch``) — tokens
            plus any family extras (patches, enc_frames).

        Returns:
          self; ``self.result`` is a standard ``TendencyResult``.
        """
        from repro.monitor.probes import encode_batch, model_fingerprint
        acts = np.asarray(jax.device_get(encode_batch(params, cfg, batch)),
                          np.float32)
        return self.fit(acts, encoder=model_fingerprint(cfg, params))

    def fit_many(self, Xs) -> "FastVAT":
        """Assess a stack of datasets in ONE compiled program.

        Args:
          Xs: (b, n, d) array-like — b independent datasets of n points
            each (pad or truncate to a common n first; a Python list of
            equal-shape (n, d) arrays also works). With
            ``metric="precomputed"``: a (b, n, n) dissimilarity stack.

        Returns:
          self. ``order()`` then yields (b, n), ``image()`` (b, n, n),
          and ``assess()`` a list of b per-dataset reports.

        Only rungs with a batched fitter batch (built-ins: "vat",
        "ivat", "flashvat"; "auto" resolves among them and refuses n
        past the largest batch-capable threshold). Each dataset's
        ordering is bitwise-identical to a solo ``fit`` — the batch is a
        vmap / batched Pallas grid, never an approximation. For larger n,
        loop ``fit()`` per dataset instead (svat/bigvat don't vectorize
        over datasets yet).
        """
        precomputed = self.metric == "precomputed"
        num_report = None
        if precomputed:
            if self.validate:
                validate_dissimilarity(Xs)
            Xs = as_dissimilarity(Xs, batched=True)
        else:
            if self.validate:
                validate_points(Xs, batched=True, metric=self.metric)
            Xs = np.asarray(Xs, np.float32)
            if Xs.ndim != 3:
                raise ValueError(f"fit_many wants a (b, n, d) stack, got "
                                 f"shape {Xs.shape}")
            Xs, num_report = self._numerics_prepass(Xs, batched=True)
            Xs = jnp.asarray(Xs)
        b, n = int(Xs.shape[0]), int(Xs.shape[1])
        method = self.method
        if method == "auto":
            try:
                # precomputed input may exceed the exact rung's threshold:
                # the O(n^2) matrix already exists, so fall back to it
                method = select_method(n, precomputed=precomputed,
                                       batched=True, strict=not precomputed)
            except LookupError:
                cap = max((r.auto_threshold for r in
                           map(registry.get_rung, registry.registered())
                           if r.supports_batch and
                           r.auto_threshold is not None), default=SMALL_N)
                raise ValueError(
                    f"fit_many batches the exact rungs only (n <= {cap}),"
                    f" got per-dataset n={n}; loop fit() per dataset for"
                    " the svat/bigvat rungs") from None
        rung = registry.get_rung(method)
        if not rung.supports_batch:
            batchable = [r for r in registry.registered()
                         if registry.get_rung(r).supports_batch]
            raise ValueError(
                f"fit_many supports methods with a batched fitter "
                f"({batchable} or 'auto'), got {self.method!r}")
        if precomputed and not rung.supports_precomputed:
            raise ValueError(f"method {method!r} does not accept "
                             "metric='precomputed'")
        if rung.max_n is not None and n > rung.max_n:
            raise ValueError(f"method {method!r} caps at n={rung.max_n}, "
                             f"got n={n}")
        if rung.check is not None:
            rung.check(n)
        meta = self._meta(method, n, batch=b, numerics=num_report)
        self.result = rung.fit_batch(Xs, meta, self._options(
            num_report.form if num_report is not None else "gram"))
        self.method_resolved = method
        self._X = np.asarray(Xs)
        return self

    # --------------------------------------------------------- queries ----
    # All branch-free: they read the uniform TendencyResult fields.

    def _require_fit(self) -> TendencyResult:
        if self.result is None:
            raise RuntimeError("call fit(X) first")
        return self.result

    def order(self) -> np.ndarray:
        """VAT ordering: all n points (vat/ivat/bigvat/dvat) or the sample
        (svat — use sample_indices() to map back to dataset rows).
        After ``fit_many`` the result is a (b, n) stack of orderings."""
        return np.asarray(self._require_fit().order)

    def sample_indices(self) -> np.ndarray | None:
        """Dataset rows of the prototypes (svat/bigvat/dvat), else None."""
        idx = self._require_fit().sample_idx
        return None if idx is None else np.asarray(idx)

    def image(self, *, resolution: int = 256,
              use_ivat: bool | None = None) -> np.ndarray:
        """The reordered dissimilarity image (the thing you look at).

        Delegates to ``TendencyResult.image``: the geodesic (iVAT) image
        is used wherever one was computed (``use_ivat=None``) or demanded
        (``use_ivat=True``, derived on demand otherwise); results with a
        full-data extension (bigvat) are expanded to ``resolution``
        pixels by group size.  After ``fit_many`` the result carries a
        leading batch axis: (b, n, n).
        """
        return self._require_fit().image(resolution=resolution,
                                         use_ivat=use_ivat)

    def _hopkins_subsample(self, X, meta: ResultMeta,
                           cap: int = 2_048) -> np.ndarray:
        """Uniform random rows of X for the Hopkins statistic.

        Maximin prototypes are deliberately spread out, which biases
        Hopkins toward 0.5 — so the svat/bigvat rungs must not reuse them
        here.  Row indexing (sorted) keeps np.memmap inputs out-of-core.
        The rng derives from the fit's single seed source
        (``meta.host_rng``), so reports are repeatable per seed.
        """
        n = X.shape[0]
        if n <= cap:
            idx = np.arange(n)
        else:
            idx = np.sort(meta.host_rng(SALT_HOPKINS).choice(
                n, cap, replace=False))
        return np.asarray(X[idx], np.float32)

    def _assess_one(self, rstar, X, key, meta: ResultMeta,
                    batch_index: int | None) -> TendencyReport:
        """Score one (rstar, X) pair: Hopkins + block structure."""
        score, k_est = core.block_structure_score(rstar)
        if meta.metric == "precomputed":
            # no point coordinates to probe — Hopkins is undefined
            h, clustered = float("nan"), bool(float(score) > 0.3)
        else:
            Xh = self._hopkins_subsample(X, meta)
            h = float(core.hopkins(jnp.asarray(Xh), key))
            clustered = bool(h > 0.75 and float(score) > 0.3)
        return TendencyReport(method=meta.method, metric=meta.metric,
                              n=meta.n, hopkins=h,
                              block_score=float(score), k_est=int(k_est),
                              clustered=clustered, batch_index=batch_index)

    @spans.span("fastvat.assess")
    def assess(self, key: jax.Array | None = None):
        """Machine-checkable tendency report: Hopkins + block structure.

        Returns one ``TendencyReport`` after ``fit`` and a list of b of
        them after ``fit_many`` — the same keys either way (dict-like
        access included; ``batch_index`` is None for solo fits).
        """
        res = self._require_fit()
        meta = res.meta
        if key is None:
            key = meta.jax_key(SALT_ASSESS)
        if meta.batch is not None:
            keys = jax.random.split(key, meta.batch)
            return [
                self._assess_one(res.rstar[i], self._X[i], keys[i], meta, i)
                for i in range(meta.batch)
            ]
        return self._assess_one(res.rstar, self._X, key, meta, None)


def assess_tendency(X, **kwargs) -> TendencyReport:
    """One-shot convenience: FastVAT(**kwargs).fit(X).assess()."""
    return FastVAT(**kwargs).fit(X).assess()

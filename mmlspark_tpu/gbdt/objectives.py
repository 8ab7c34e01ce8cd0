"""Training objectives: gradient/hessian functions.

TPU-native analogs of LightGBM's ``ObjectiveFunction`` subclasses, which the
reference selects via its ``objective`` param and passes to the native engine
(SURVEY.md §2.1 LightGBM params; §3.1 hot loop computes grad/hess natively).
Each objective is a pure jax function ``(scores, labels, weights) → (g, h)``
so it fuses into the jitted training step.

Semantics track LightGBM:

* ``binary``: logistic loss with ``sigmoid`` scaling and optional
  ``is_unbalance``/``scale_pos_weight`` label weighting;
  ``boost_from_average`` init score = log(p/(1-p))/sigmoid.
* ``regression`` (l2), ``regression_l1`` (gradient = sign, hessian = 1),
  ``huber``, ``fair``, ``poisson``, ``quantile``, ``mape``.
* ``multiclass``: one-vs-all softmax, K trees per iteration,
  hessian = 2·p·(1-p) · factor (K/(K-1)) as in LightGBM.
* ``lambdarank``: in :mod:`mmlspark_tpu.gbdt.ranking` (pairwise ΔNDCG).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray
GradFn = Callable[[Array, Array, Array], Tuple[Array, Array]]


def sigmoid(x):
    return jax.nn.sigmoid(x)


class Objective:
    """Base: subclasses define grad/hess and the boost-from-average init.

    Objectives are passed to jitted boost steps as *static* arguments, so
    they hash by value (type + full instance state, including what
    ``prepare`` resolved): two fits with identical objective config hit the
    same XLA executable instead of recompiling per estimator instance.
    """

    name = "base"
    num_model_per_iteration = 1
    #: substring written into the LightGBM model file objective line
    model_str = "custom"

    def _key(self):
        return (type(self), tuple(sorted(self.__dict__.items())))

    def __eq__(self, other):
        return isinstance(other, Objective) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def prepare(self, labels: np.ndarray, weights: np.ndarray) -> None:
        """Resolve label statistics (class weights etc.); always called once
        before training, independent of boost_from_average."""

    def init_score(self, labels: np.ndarray, weights: np.ndarray) -> float:
        return 0.0

    def grad_hess(self, scores: Array, labels: Array,
                  weights: Array) -> Tuple[Array, Array]:
        raise NotImplementedError

    def transform_prediction(self, scores: Array) -> Array:
        """Raw margin → output space (e.g. sigmoid for binary)."""
        return scores

    def train_loss(self, scores: np.ndarray, labels: np.ndarray,
                   weights: Optional[np.ndarray] = None
                   ) -> Optional[float]:
        """Cheap host-side training loss for live telemetry (the
        ``train_loss`` gauge / ``boost_chunk`` journal field) —
        objectives without a closed form return ``None`` and the
        monitor skips the gauge.  Pure numpy on HOST copies: called at
        chunk boundaries, never inside the jitted step."""
        return None


class BinaryObjective(Objective):
    name = "binary"
    model_str = "binary sigmoid:1"

    def __init__(self, sigmoid_coef: float = 1.0, is_unbalance: bool = False,
                 scale_pos_weight: float = 1.0):
        self.sigma = float(sigmoid_coef)
        self.is_unbalance = is_unbalance
        self.scale_pos_weight = float(scale_pos_weight)
        self.model_str = f"binary sigmoid:{self.sigma:g}"
        self._pos_w = 1.0  # resolved by prepare() from label stats
        self._neg_w = 1.0

    def prepare(self, labels, weights):
        pos = float(np.sum(weights * (labels > 0)))
        neg = float(np.sum(weights)) - pos
        if self.is_unbalance and pos > 0 and neg > 0:
            # up-weight whichever class is rarer, as LightGBM does
            if pos < neg:
                self._pos_w = neg / pos
            else:
                self._neg_w = pos / neg
        elif self.scale_pos_weight != 1.0:
            self._pos_w = self.scale_pos_weight

    def init_score(self, labels, weights):
        pos = float(np.sum(weights * (labels > 0)))
        neg = float(np.sum(weights)) - pos
        if pos <= 0 or neg <= 0:
            return 0.0
        p = pos / (pos + neg)
        return float(np.log(p / (1.0 - p)) / self.sigma)

    def grad_hess(self, scores, labels, weights):
        p = sigmoid(self.sigma * scores)
        w = weights * jnp.where(labels > 0, self._pos_w, self._neg_w)
        g = self.sigma * (p - labels) * w
        h = self.sigma * self.sigma * p * (1.0 - p) * w
        return g, h

    def transform_prediction(self, scores):
        return sigmoid(self.sigma * scores)

    def train_loss(self, scores, labels, weights=None):
        """Weighted logloss (numpy, clipped for stability)."""
        y = (np.asarray(labels) > 0).astype(np.float64)
        p = 1.0 / (1.0 + np.exp(-self.sigma * np.asarray(
            scores, np.float64)))
        p = np.clip(p, 1e-12, 1.0 - 1e-12)
        ll = -(y * np.log(p) + (1.0 - y) * np.log(1.0 - p))
        w = (np.ones_like(ll) if weights is None
             else np.asarray(weights, np.float64))
        s = float(w.sum())
        return float((ll * w).sum() / s) if s > 0 else None


class RegressionL2(Objective):
    name = "regression"
    model_str = "regression"

    def init_score(self, labels, weights):
        s = float(np.sum(weights))
        return float(np.sum(weights * labels) / s) if s > 0 else 0.0

    def grad_hess(self, scores, labels, weights):
        return (scores - labels) * weights, weights

    def train_loss(self, scores, labels, weights=None):
        """Weighted mean squared error (numpy)."""
        err = (np.asarray(scores, np.float64)
               - np.asarray(labels, np.float64)) ** 2
        w = (np.ones_like(err) if weights is None
             else np.asarray(weights, np.float64))
        s = float(w.sum())
        return float((err * w).sum() / s) if s > 0 else None


class RegressionL1(Objective):
    name = "regression_l1"
    model_str = "regression_l1"

    def init_score(self, labels, weights):
        return float(np.median(labels))

    def grad_hess(self, scores, labels, weights):
        g = jnp.sign(scores - labels) * weights
        return g, weights


class HuberObjective(Objective):
    name = "huber"
    model_str = "huber"

    def __init__(self, alpha: float = 0.9):
        self.alpha = float(alpha)

    def init_score(self, labels, weights):
        s = float(np.sum(weights))
        return float(np.sum(weights * labels) / s) if s > 0 else 0.0

    def grad_hess(self, scores, labels, weights):
        d = scores - labels
        g = jnp.where(jnp.abs(d) <= self.alpha, d,
                      self.alpha * jnp.sign(d)) * weights
        return g, weights


class FairObjective(Objective):
    name = "fair"
    model_str = "fair"

    def __init__(self, c: float = 1.0):
        self.c = float(c)

    def init_score(self, labels, weights):
        return 0.0

    def grad_hess(self, scores, labels, weights):
        d = scores - labels
        g = self.c * d / (jnp.abs(d) + self.c) * weights
        h = self.c * self.c / jnp.square(jnp.abs(d) + self.c) * weights
        return g, h


class PoissonObjective(Objective):
    name = "poisson"
    model_str = "poisson"

    def __init__(self, max_delta_step: float = 0.7):
        self.max_delta_step = float(max_delta_step)

    def init_score(self, labels, weights):
        s = float(np.sum(weights))
        mean = float(np.sum(weights * labels) / s) if s > 0 else 1.0
        return float(np.log(max(mean, 1e-12)))

    def grad_hess(self, scores, labels, weights):
        mu = jnp.exp(scores)
        g = (mu - labels) * weights
        h = mu * jnp.exp(self.max_delta_step) * weights
        return g, h

    def transform_prediction(self, scores):
        return jnp.exp(scores)


class QuantileObjective(Objective):
    name = "quantile"
    model_str = "quantile"

    def __init__(self, alpha: float = 0.9):
        self.alpha = float(alpha)

    def init_score(self, labels, weights):
        return float(np.quantile(labels, self.alpha))

    def grad_hess(self, scores, labels, weights):
        d = scores - labels
        g = jnp.where(d >= 0, 1.0 - self.alpha, -self.alpha) * weights
        return g, weights


class MapeObjective(Objective):
    name = "mape"
    model_str = "mape"

    def init_score(self, labels, weights):
        return float(np.median(labels))

    def grad_hess(self, scores, labels, weights):
        denom = jnp.maximum(jnp.abs(labels), 1.0)
        g = jnp.sign(scores - labels) / denom * weights
        h = weights / denom
        return g, h


class GammaObjective(Objective):
    """Gamma deviance with log link (LightGBM objective=gamma;
    src/objective/regression_objective.hpp RegressionGammaLoss, expected
    path, UNVERIFIED): g = 1 - y·e^{-s}, h = y·e^{-s}."""

    name = "gamma"
    model_str = "gamma"

    def init_score(self, labels, weights):
        s = float(np.sum(weights))
        mean = float(np.sum(weights * labels) / s) if s > 0 else 1.0
        return float(np.log(max(mean, 1e-12)))

    def grad_hess(self, scores, labels, weights):
        ey = labels * jnp.exp(-scores)
        g = (1.0 - ey) * weights
        h = ey * weights
        return g, h

    def transform_prediction(self, scores):
        return jnp.exp(scores)


class TweedieObjective(Objective):
    """Tweedie deviance, log link, variance power ρ ∈ (1, 2) (LightGBM
    objective=tweedie, tweedie_variance_power; RegressionTweedieLoss,
    expected path, UNVERIFIED):
    g = -y·e^{(1-ρ)s} + e^{(2-ρ)s}, h the score derivative of g."""

    name = "tweedie"

    def __init__(self, rho: float = 1.5):
        if not 1.0 < rho < 2.0:
            raise ValueError("tweedie_variance_power must be in (1, 2), "
                             f"got {rho}")
        self.rho = float(rho)
        self.model_str = "tweedie"

    def init_score(self, labels, weights):
        s = float(np.sum(weights))
        mean = float(np.sum(weights * labels) / s) if s > 0 else 1.0
        return float(np.log(max(mean, 1e-12)))

    def grad_hess(self, scores, labels, weights):
        a = jnp.exp((1.0 - self.rho) * scores)
        b = jnp.exp((2.0 - self.rho) * scores)
        g = (-labels * a + b) * weights
        h = (-labels * (1.0 - self.rho) * a
             + (2.0 - self.rho) * b) * weights
        return g, h

    def transform_prediction(self, scores):
        return jnp.exp(scores)


class CrossEntropyObjective(Objective):
    """Cross-entropy on PROBABILITY labels in [0, 1] (LightGBM
    objective=cross_entropy / xentropy): the binary gradient g = σ(s) - y
    without requiring hard 0/1 labels."""

    name = "cross_entropy"
    model_str = "cross_entropy"

    def init_score(self, labels, weights):
        s = float(np.sum(weights))
        p = float(np.sum(weights * labels) / s) if s > 0 else 0.5
        p = min(max(p, 1e-12), 1.0 - 1e-12)
        return float(np.log(p / (1.0 - p)))

    def grad_hess(self, scores, labels, weights):
        p = jax.nn.sigmoid(scores)
        g = (p - labels) * weights
        h = jnp.maximum(p * (1.0 - p), 1e-16) * weights
        return g, h

    def transform_prediction(self, scores):
        return jax.nn.sigmoid(scores)


class MulticlassOvaObjective(Objective):
    """One-vs-all multiclass (LightGBM objective=multiclassova): K
    INDEPENDENT sigmoid classifiers, one tree per class per iteration;
    prediction = per-class sigmoids normalized to sum 1 (LightGBM's
    OVA converter)."""

    name = "multiclassova"

    def __init__(self, num_class: int, sigmoid_coef: float = 1.0):
        if num_class < 2:
            raise ValueError("multiclassova requires num_class >= 2")
        self.num_class = int(num_class)
        self.num_model_per_iteration = self.num_class
        self.sigma = float(sigmoid_coef)
        self.model_str = (f"multiclassova num_class:{self.num_class} "
                          f"sigmoid:{self.sigma:g}")

    def init_score(self, labels, weights):
        return 0.0

    def grad_hess(self, scores, labels, weights):
        y = jax.nn.one_hot(labels.astype(jnp.int32), self.num_class,
                           dtype=scores.dtype)
        p = jax.nn.sigmoid(self.sigma * scores)
        w = weights[:, None]
        g = self.sigma * (p - y) * w
        h = self.sigma * self.sigma * p * (1.0 - p) * w
        return g, h

    def transform_prediction(self, scores):
        p = jax.nn.sigmoid(self.sigma * scores)
        return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-12)


class MulticlassObjective(Objective):
    """Softmax over K per-class score columns; K trees per iteration."""

    name = "multiclass"

    def __init__(self, num_class: int):
        if num_class < 2:
            raise ValueError("multiclass requires num_class >= 2")
        self.num_class = int(num_class)
        self.num_model_per_iteration = self.num_class
        self.model_str = f"multiclass num_class:{self.num_class}"
        self.factor = self.num_class / (self.num_class - 1.0)

    def init_score(self, labels, weights):
        return 0.0

    def grad_hess(self, scores, labels, weights):
        """scores: (n, K); labels: (n,) int class ids → (n, K) g/h."""
        p = jax.nn.softmax(scores, axis=-1)
        y = jax.nn.one_hot(labels.astype(jnp.int32), self.num_class,
                           dtype=p.dtype)
        w = weights[:, None]
        g = (p - y) * w
        h = self.factor * p * (1.0 - p) * w
        return g, h

    def transform_prediction(self, scores):
        return jax.nn.softmax(scores, axis=-1)

    def train_loss(self, scores, labels, weights=None):
        """Weighted softmax cross-entropy (numpy, log-sum-exp)."""
        s = np.asarray(scores, np.float64)
        s = s - s.max(axis=-1, keepdims=True)
        logp = s - np.log(np.exp(s).sum(axis=-1, keepdims=True))
        y = np.asarray(labels).astype(np.int64)
        nll = -logp[np.arange(len(y)), y]
        w = (np.ones_like(nll) if weights is None
             else np.asarray(weights, np.float64))
        tot = float(w.sum())
        return float((nll * w).sum() / tot) if tot > 0 else None


class _LambdarankStub(Objective):
    """Metadata-only objective: the ranker supplies grad/hess via its
    query layout (gbdt/ranking.LambdarankObjective); init score is 0."""

    name = "lambdarank"
    model_str = "lambdarank"

    def grad_hess(self, scores, labels, weights):
        raise ValueError(
            "objective='lambdarank' needs query structure; use "
            "LightGBMRanker (with groupCol) instead of "
            "LightGBMClassifier/Regressor")


def _lambdarank_stub() -> Objective:
    return _LambdarankStub()


def get_objective(name: str, num_class: int = 1, **kwargs) -> Objective:
    name = name.lower()
    aliases = {
        "binary": lambda: BinaryObjective(
            sigmoid_coef=kwargs.get("sigmoid", 1.0),
            is_unbalance=kwargs.get("is_unbalance", False),
            scale_pos_weight=kwargs.get("scale_pos_weight", 1.0)),
        "regression": RegressionL2, "regression_l2": RegressionL2,
        "l2": RegressionL2, "mean_squared_error": RegressionL2,
        "mse": RegressionL2,
        "regression_l1": RegressionL1, "l1": RegressionL1,
        "mae": RegressionL1,
        "huber": lambda: HuberObjective(alpha=kwargs.get("alpha", 0.9)),
        "fair": lambda: FairObjective(c=kwargs.get("fair_c", 1.0)),
        "poisson": lambda: PoissonObjective(
            max_delta_step=kwargs.get("poisson_max_delta_step", 0.7)),
        "quantile": lambda: QuantileObjective(alpha=kwargs.get("alpha", 0.9)),
        "mape": MapeObjective,
        "gamma": GammaObjective,
        "tweedie": lambda: TweedieObjective(
            rho=kwargs.get("tweedie_variance_power", 1.5)),
        "cross_entropy": CrossEntropyObjective,
        "xentropy": CrossEntropyObjective,
        "multiclass": lambda: MulticlassObjective(num_class),
        "softmax": lambda: MulticlassObjective(num_class),
        "multiclassova": lambda: MulticlassOvaObjective(
            num_class, sigmoid_coef=kwargs.get("sigmoid", 1.0)),
        "ova": lambda: MulticlassOvaObjective(
            num_class, sigmoid_coef=kwargs.get("sigmoid", 1.0)),
        "lambdarank": _lambdarank_stub,
    }
    if name not in aliases:
        raise ValueError(f"Unknown objective {name!r}; "
                         f"supported: {sorted(aliases)}")
    return aliases[name]()

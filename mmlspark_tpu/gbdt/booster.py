"""Booster: the trained GBDT model container.

TPU-native analog of the reference's ``LightGBMBooster`` (serializable model
wrapper + predict; lightgbm/LightGBMBooster.scala, expected path, UNVERIFIED).
The reference wraps a native handle and round-trips models as LightGBM's
*text* format — an interop contract (SURVEY.md §5.4) this class preserves:
``save_native_model``/``load_native_model`` emit/parse LightGBM v3 model
files, so models exported here load in stock LightGBM and vice versa
(numerical splits; categorical splits are round 2).

Prediction runs as a single jitted scan over stacked tree arrays: rows
traverse all trees in parallel with gather-based walks (n·T·depth gathers),
instead of the reference's per-row JNI ``LGBM_BoosterPredictForMat`` calls —
its known scoring sore point (SURVEY.md §3.2).
"""

from __future__ import annotations

import functools
import hashlib
import io
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.profiler import get_profiler
from .grower import TreeArrays
from .binning import BinMapper

#: content-digest header (ISSUE 14 satellite): ``save_native_model``
#: prepends ONE comment line ``# mmlspark_tpu.digest.sha256=<hex>``
#: hashing everything after it, so model-file corruption (torn write,
#: bit rot) is detected at load EVERYWHERE — the registry, the fleet's
#: spawn-mode model handoff, a bare ``load_native_model`` — not only
#: where a registry manifest happens to carry a second digest.
#: Digest-less files (stock LightGBM exports, pre-ISSUE-14 saves) load
#: unchanged; the model-string API stays byte-identical to the
#: reference's text format for interop.
DIGEST_HEADER = "# mmlspark_tpu.digest.sha256="


class ModelDigestError(ValueError):
    """A native-model file's content no longer hashes to its embedded
    digest header — refuse to build a Booster from corrupt bytes."""


def with_digest_header(text: str) -> str:
    """Prepend the digest header line (idempotent: an already-stamped
    text is re-verified and returned unchanged)."""
    if text.startswith(DIGEST_HEADER):
        split_native_digest(text)     # re-verify, raises on mismatch
        return text
    h = hashlib.sha256(text.encode("utf-8")).hexdigest()
    return f"{DIGEST_HEADER}{h}\n{text}"


def split_native_digest(text: str) -> str:
    """Strip and VERIFY the digest header when present; return the
    bare model text.  Digest-less input passes through untouched
    (backward compatibility with stock LightGBM files)."""
    if not text.startswith(DIGEST_HEADER):
        # a bit-flipped HEADER must not demote the file to "digest-less"
        # and load unverified: any first line still recognisable as a
        # digest stamp but not byte-exact is corruption
        if ".digest.sha256=" in text[:len(DIGEST_HEADER) + 16]:
            raise ModelDigestError(
                "native model digest header is mangled (bit-flipped "
                "header line); refusing to load")
        return text
    line, _, body = text.partition("\n")
    want = line[len(DIGEST_HEADER):].strip()
    got = hashlib.sha256(body.encode("utf-8")).hexdigest()
    if got != want:
        raise ModelDigestError(
            f"native model content fails its embedded digest (want "
            f"sha256:{want[:12]}…, got sha256:{got[:12]}…): the file "
            "is torn or bit-flipped; refusing to load")
    return body


@dataclass
class HostTree:
    """One tree with real-valued thresholds, trimmed to its actual size."""
    split_feature: np.ndarray   # (m,) i32
    threshold: np.ndarray       # (m,) f64  (x <= threshold -> left)
    split_gain: np.ndarray      # (m,) f64
    left_child: np.ndarray      # (m,) i32  (>=0 node, <0 leaf ~idx)
    right_child: np.ndarray     # (m,) i32
    decision_type: np.ndarray   # (m,) i32
    leaf_value: np.ndarray      # (L,) f64
    leaf_weight: np.ndarray     # (L,) f64
    leaf_count: np.ndarray      # (L,) i64
    internal_value: np.ndarray  # (m,) f64
    internal_weight: np.ndarray  # (m,) f64
    internal_count: np.ndarray  # (m,) i64
    shrinkage: float = 1.0
    #: categorical splits (LightGBM layout): for a node with
    #: decision_type bit0 set, ``threshold`` holds an index j into
    #: ``cat_boundaries``; words ``cat_threshold[cat_boundaries[j]:
    #: cat_boundaries[j+1]]`` form a bitset over raw category values —
    #: bit set → value goes LEFT.
    num_cat: int = 0
    cat_boundaries: np.ndarray = field(
        default_factory=lambda: np.zeros(1, np.int32))
    cat_threshold: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.uint32))

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)

    def max_depth(self) -> int:
        if self.num_leaves <= 1:
            return 0
        m = len(self.split_feature)
        depth = np.zeros(m, dtype=np.int64)
        out = 1
        for i in range(m):  # children always have larger node ids
            for c in (self.left_child[i], self.right_child[i]):
                if c >= 0:
                    depth[c] = depth[i] + 1
                    out = max(out, int(depth[c]) + 1)
        return out


def host_tree_from_arrays(tree: TreeArrays, mapper: BinMapper,
                          missing_bin: int) -> HostTree:
    """Trim a device TreeArrays to its actual size with real thresholds."""
    num_leaves = int(tree.num_leaves)
    m = max(num_leaves - 1, 0)
    feat = np.asarray(tree.node_feat)[:m]
    bins = np.asarray(tree.node_bin)[:m]
    is_cat = np.asarray(tree.node_is_cat)[:m] > 0
    cat_bits = np.asarray(tree.node_cat_bits)[:m]
    thr = np.array([mapper.bin_threshold_value(int(f), int(b))
                    for f, b in zip(feat, bins)], dtype=np.float64)
    # decision_type (LightGBM): bit 1 default_left, bits 2-3 the missing
    # type.  2 = default left with missing None (a feature without NaN);
    # 8 = missing NaN, default right; 10 = missing NaN, default left.  A
    # tree grown without missing values sends NaN right; one grown with
    # them says where each split sent it (``node_dleft``)
    has_nan = mapper.has_missing[feat] if m else np.zeros(0, bool)
    if tree.node_dleft is None:
        dt = np.where(has_nan, 8, 2).astype(np.int32)
    else:
        dleft = np.asarray(tree.node_dleft)[:m] >= 0
        dt = np.where(has_nan | dleft, 8 | (2 * dleft),
                      2).astype(np.int32)
    num_cat = 0
    cat_boundaries = [0]
    cat_words: List[np.ndarray] = []
    if is_cat.any():
        # bin bitsets -> LightGBM's bitsets over RAW category values: a
        # split on a column of ten million values exports up to 316 000
        # words, so the words are set by numpy, not by a Python loop
        with get_profiler().region("train.cat_bitsets", words=0) as sp:
            shifts = np.arange(32, dtype=np.uint32)
            for i in np.flatnonzero(is_cat):
                cats = mapper.cat_values[int(feat[i])]
                in_set = ((cat_bits[i][:, None] >> shifts) & 1
                          ).astype(bool).reshape(-1)
                left_cats = np.asarray(cats, np.int64)[in_set[:len(cats)]]
                nwords = int(left_cats.max(initial=0) // 32) + 1
                words = np.zeros(nwords, np.uint32)
                np.bitwise_or.at(
                    words, left_cats >> 5,
                    np.uint32(1) << (left_cats & 31).astype(np.uint32))
                dt[i] = 1 | (2 if in_set[missing_bin] else 0)
                thr[i] = float(num_cat)       # index into cat_boundaries
                cat_words.append(words)
                cat_boundaries.append(cat_boundaries[-1] + nwords)
                num_cat += 1
            sp["words"] = int(cat_boundaries[-1])
    return HostTree(
        split_feature=feat.astype(np.int32),
        threshold=thr,
        split_gain=np.asarray(tree.node_gain, np.float64)[:m],
        left_child=np.asarray(tree.node_left, np.int32)[:m],
        right_child=np.asarray(tree.node_right, np.int32)[:m],
        decision_type=dt,
        leaf_value=np.asarray(tree.leaf_value, np.float64)[:num_leaves],
        leaf_weight=np.asarray(tree.leaf_weight, np.float64)[:num_leaves],
        leaf_count=np.asarray(tree.leaf_count, np.float64)[:num_leaves]
            .astype(np.int64),
        internal_value=np.asarray(tree.node_value, np.float64)[:m],
        internal_weight=np.asarray(tree.node_weight, np.float64)[:m],
        internal_count=np.asarray(tree.node_count, np.float64)[:m]
            .astype(np.int64),
        num_cat=num_cat,
        cat_boundaries=np.asarray(cat_boundaries, np.int32),
        cat_threshold=(np.concatenate(cat_words).astype(np.uint32)
                       if cat_words else np.zeros(0, np.uint32)),
    )


def _nan_left(decision_type: np.ndarray) -> np.ndarray:
    """Where a node sends NaN LEFT (1), as LightGBM's decision reads the
    bits: a categorical node by its default_left bit; a numeric node by
    it only where its missing type (bits 2-3) is NaN.  Every other NaN
    goes right (a numeric node of missing type None or Zero: LightGBM
    reads NaN as zero there; this walk does not)."""
    dt = np.asarray(decision_type, np.int32)
    default_left = (dt & 2) > 0
    nan_type = ((dt >> 2) & 3) == 2
    return (default_left & (((dt & 1) > 0) | nan_type)).astype(np.int32)


class Booster:
    """A trained forest + objective metadata; predicts via jitted traversal."""

    def __init__(self, trees: List[HostTree], num_class: int = 1,
                 objective_str: str = "regression",
                 init_score: float = 0.0,
                 feature_names: Optional[List[str]] = None,
                 feature_infos: Optional[List[str]] = None,
                 max_feature_idx: Optional[int] = None,
                 params: Optional[Dict[str, str]] = None):
        self.trees = trees
        self.num_class = num_class
        self.objective_str = objective_str
        self.init_score = init_score
        self.max_feature_idx = max_feature_idx if max_feature_idx is not None \
            else (max((int(t.split_feature.max()) for t in trees
                       if len(t.split_feature)), default=0))
        nf = self.max_feature_idx + 1
        self.feature_names = feature_names or [
            f"Column_{i}" for i in range(nf)]
        self.feature_infos = feature_infos or ["none"] * nf
        self.params = params or {}
        self._stacked = None
        self._stacked_np = None
        # bumped whenever the stacked prediction cache is dropped; a
        # CompiledPredictor captures the token at build time and refuses
        # to score a forest that changed under it
        self._cache_token = 0
        # fit-time data-quality baseline (ISSUE 15): the engine attaches
        # a core.sketch.ReferenceProfile after training; the registry
        # persists it beside the model and drift monitors compare live
        # traffic against it.  None for loaded/extended models whose
        # profile wasn't captured — drift monitoring is simply off then.
        self.reference_profile = None

    def extended(self, continuation: "Booster") -> "Booster":
        """The merged model of continued training (LightGBM's
        ``init_model``): this booster's trees followed by the
        ``continuation`` forest that was trained with this booster's
        margins as init scores.  Predictions of the merged model equal
        base margins + continuation margins by additivity.  Reference:
        LightGBMBooster model round-trip + LightGBM's
        init_model/keep_training_booster capability (SURVEY.md §5.4)."""
        if continuation.num_class != self.num_class:
            raise ValueError(
                f"cannot extend a {self.num_class}-class model with a "
                f"{continuation.num_class}-class continuation")
        if continuation.max_feature_idx != self.max_feature_idx:
            raise ValueError(
                f"feature count mismatch: base model uses "
                f"{self.max_feature_idx + 1} features, continuation "
                f"{continuation.max_feature_idx + 1}")
        params = dict(continuation.params)
        old_it = len(self.trees) // max(self.num_class, 1)
        new_it = len(continuation.trees) // max(self.num_class, 1)
        params["num_iterations"] = str(old_it + new_it)
        return Booster(
            list(self.trees) + list(continuation.trees),
            num_class=self.num_class,
            objective_str=continuation.objective_str,
            init_score=self.init_score,
            feature_names=continuation.feature_names,
            feature_infos=continuation.feature_infos,
            max_feature_idx=self.max_feature_idx,
            params=params)

    # -- prediction ----------------------------------------------------------

    def invalidate_cache(self) -> None:
        """Drop the stacked prediction arrays.  Call after mutating
        ``trees`` in place; any outstanding :class:`CompiledPredictor`
        raises on its next call instead of silently scoring the old
        forest."""
        self._stacked = None
        self._stacked_np = None
        self._cache_token += 1

    def predictor(self, num_iteration: Optional[int] = None,
                  backend: str = "auto",
                  tree_range: Optional[Tuple[int, int]] = None,
                  include_init_score: bool = True
                  ) -> "CompiledPredictor":
        """Serving-hot-path margin scorer with all per-call dispatch
        (shape checks, ``_stack()`` dict indexing, ``use_t`` slicing,
        native-vs-jit backend probe) resolved ONCE at construction.
        ``backend``: "auto" (native when available on cpu, else jit),
        "native", or "jit" (force the XLA walk — the accelerator path,
        also what benchmarks pin for apples-to-apples comparisons).

        ``tree_range=(lo, hi)`` scores only trees ``lo..hi-1`` — the
        sharded scoring fleet's tree-range partial scorer (ISSUE 11).
        Bounds must align to ``num_class`` (shards hold whole boosting
        iterations, since tree→class assignment is positional).  With
        ``include_init_score=False`` the partial carries NO init score,
        so summing the shards' partials reproduces the full margin
        (shard 0 keeps the init score exactly once)."""
        return CompiledPredictor(self, num_iteration, backend,
                                 tree_range=tree_range,
                                 include_init_score=include_init_score)

    def _stack(self):
        """Pad trees to uniform arrays for a jitted scan."""
        if self._stacked is not None:
            return self._stacked
        T = len(self.trees)
        if T == 0:
            self._stacked = None
            return None
        m = max(max(len(t.split_feature) for t in self.trees), 1)
        L = max(max(t.num_leaves for t in self.trees), 1)
        depth = max(max(t.max_depth() for t in self.trees), 1)

        def pad(arrs, width, dtype, fill=0):
            out = np.full((T, width), fill, dtype=dtype)
            for i, a in enumerate(arrs):
                out[i, :len(a)] = a
            return out

        def thr32(t):
            # Round thresholds UP to float32 so the f32 decision `x <= thr`
            # agrees with the exact f64 threshold for every f32-representable
            # x (rounding down could flip a midpoint onto the right value).
            # LightGBM's 1e300 (a split isolating the missing bin) is inf.
            with np.errstate(over="ignore"):
                v = t.threshold.astype(np.float32)
            low = v.astype(np.float64) < t.threshold
            v[low] = np.nextafter(v[low], np.float32(np.inf))
            return v

        ncat_max = max(max(t.num_cat for t in self.trees), 1)
        words_max = max(max(len(t.cat_threshold) for t in self.trees), 1)
        stacked = {
            "feat": pad([t.split_feature for t in self.trees], m, np.int32),
            "thr": pad([thr32(t) for t in self.trees], m, np.float32),
            "left": pad([t.left_child for t in self.trees], m, np.int32),
            "right": pad([t.right_child for t in self.trees], m, np.int32),
            "leaf": pad([t.leaf_value for t in self.trees], L, np.float32),
            "single": np.array(
                [t.num_leaves <= 1 for t in self.trees], np.bool_),
            "is_cat": pad([(t.decision_type & 1).astype(np.int32)
                           for t in self.trees], m, np.int32),
            "dleft": pad([_nan_left(t.decision_type)
                          for t in self.trees], m, np.int32),
            # zero-padded; padded entries are only read for numeric nodes
            # whose categorical branch result is discarded
            "cat_bnd": pad([t.cat_boundaries for t in self.trees],
                           ncat_max + 1, np.int32),
            "cat_words": pad([t.cat_threshold for t in self.trees],
                             words_max, np.uint32),
            "depth": depth,
            "has_cat": any(t.num_cat > 0 for t in self.trees),
        }
        # host copy retained only where the native scorer can use it —
        # on accelerators it would just double host memory per model
        self._stacked_np = stacked if jax.default_backend() == "cpu" \
            else None
        self._stacked = {k: (jnp.asarray(v) if isinstance(v, np.ndarray)
                             else v) for k, v in stacked.items()}
        return self._stacked

    def predict_margin(self, X, num_iteration: Optional[int] = None):
        """Raw margins: (n,) for single-class, (n, K) for multiclass."""
        shape = np.shape(X)
        if len(shape) != 2 or shape[1] <= self.max_feature_idx:
            raise ValueError(
                f"Model uses feature index {self.max_feature_idx} but input "
                f"has shape {shape}; expected (n, >= "
                f"{self.max_feature_idx + 1})")
        n = shape[0]
        s = self._stack()
        K = self.num_class
        if s is None:
            base = jnp.full((n,), self.init_score, jnp.float32)
            return jnp.tile(base[:, None], (1, K))[:, 0] if K == 1 else \
                jnp.tile(base[:, None], (1, K))
        T = s["feat"].shape[0]
        use_t = T if num_iteration is None else min(num_iteration * K, T)
        sn = self._stacked_np
        if sn is not None and not isinstance(X, jax.core.Tracer) \
                and jax.default_backend() == "cpu":
            from .. import native
            if native.predict_forest_available():
                Xnp = np.ascontiguousarray(np.asarray(X, np.float32))
                out = np.zeros((n, K), np.float32)
                native.predict_forest(
                    Xnp, sn["feat"][:use_t], sn["thr"][:use_t],
                    sn["left"][:use_t], sn["right"][:use_t],
                    sn["leaf"][:use_t], sn["single"][:use_t],
                    sn["is_cat"][:use_t], sn["dleft"][:use_t],
                    sn["cat_bnd"][:use_t], sn["cat_words"][:use_t],
                    K, sn["has_cat"], out)
                out += np.float32(self.init_score)
                return out[:, 0] if K == 1 else out
        X = jnp.asarray(X, jnp.float32)
        margins = _predict_forest(X, s["feat"][:use_t], s["thr"][:use_t],
                                  s["left"][:use_t], s["right"][:use_t],
                                  s["leaf"][:use_t], s["single"][:use_t],
                                  s["is_cat"][:use_t], s["dleft"][:use_t],
                                  s["cat_bnd"][:use_t],
                                  s["cat_words"][:use_t],
                                  s["depth"], K, s["has_cat"])
        margins = margins + self.init_score
        return margins[:, 0] if K == 1 else margins

    def predict(self, X, raw_score: bool = False,
                num_iteration: Optional[int] = None):
        m = self.predict_margin(X, num_iteration)
        if raw_score:
            return m
        obj = self.objective_str.split(" ")[0]
        if obj == "binary":
            sig = _param_from_str(self.objective_str, "sigmoid", 1.0)
            return jax.nn.sigmoid(sig * m)
        if obj in ("multiclass", "softmax"):
            return jax.nn.softmax(m, axis=-1)
        if obj in ("poisson", "gamma", "tweedie"):
            return jnp.exp(m)                    # log link
        if obj in ("cross_entropy", "xentropy"):
            return jax.nn.sigmoid(m)
        if obj == "multiclassova":
            sig = _param_from_str(self.objective_str, "sigmoid", 1.0)
            p = jax.nn.sigmoid(sig * m)
            return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True),
                                   1e-12)
        return m

    def predict_contrib(self, X) -> np.ndarray:
        """Per-row TreeSHAP contributions, LightGBM pred_contrib layout:
        (n, num_class * (num_features + 1)) with the expected value in
        each class's trailing slot (see mmlspark_tpu/gbdt/shap.py)."""
        from .shap import predict_contrib
        return predict_contrib(self, X)

    def predict_leaf_index(self, X):
        X = jnp.asarray(X, jnp.float32)
        s = self._stack()
        if s is None:
            return jnp.zeros((X.shape[0], 0), jnp.int32)
        return _predict_leaves(X, s["feat"], s["thr"], s["left"], s["right"],
                               s["single"], s["is_cat"], s["dleft"],
                               s["cat_bnd"], s["cat_words"], s["depth"],
                               s["has_cat"])

    # -- feature importance --------------------------------------------------

    def feature_importances(self, importance_type: str = "split"):
        nf = self.max_feature_idx + 1
        out = np.zeros(nf)
        for t in self.trees:
            if importance_type == "gain":
                np.add.at(out, t.split_feature, t.split_gain)
            else:
                np.add.at(out, t.split_feature, 1.0)
        return out

    # -- LightGBM text-format interop (SURVEY.md §5.4 contract) --------------

    def save_native_model_string(self) -> str:
        buf = io.StringIO()
        nf = self.max_feature_idx + 1
        buf.write("tree\n")
        buf.write("version=v3\n")
        buf.write(f"num_class={self.num_class}\n")
        buf.write(f"num_tree_per_iteration={self.num_class}\n")
        buf.write("label_index=0\n")
        buf.write(f"max_feature_idx={self.max_feature_idx}\n")
        buf.write(f"objective={self.objective_str}\n")
        buf.write("feature_names=" + " ".join(self.feature_names[:nf]) + "\n")
        buf.write("feature_infos=" + " ".join(self.feature_infos[:nf]) + "\n")

        tree_bufs = []
        for i, t in enumerate(self.trees):
            tb = io.StringIO()
            tb.write(f"Tree={i}\n")
            tb.write(f"num_leaves={t.num_leaves}\n")
            tb.write(f"num_cat={t.num_cat}\n")
            if t.num_leaves > 1:
                tb.write(_arr_line("split_feature", t.split_feature))
                tb.write(_arr_line("split_gain", t.split_gain))
                tb.write(_arr_line("threshold", t.threshold))
                tb.write(_arr_line("decision_type", t.decision_type))
                tb.write(_arr_line("left_child", t.left_child))
                tb.write(_arr_line("right_child", t.right_child))
                tb.write(_arr_line("leaf_value", t.leaf_value))
                tb.write(_arr_line("leaf_weight", t.leaf_weight))
                tb.write(_arr_line("leaf_count", t.leaf_count))
                tb.write(_arr_line("internal_value", t.internal_value))
                tb.write(_arr_line("internal_weight", t.internal_weight))
                tb.write(_arr_line("internal_count", t.internal_count))
                if t.num_cat > 0:
                    tb.write(_arr_line("cat_boundaries", t.cat_boundaries))
                    tb.write(_arr_line("cat_threshold", t.cat_threshold))
            else:
                tb.write(_arr_line("leaf_value", t.leaf_value))
            tb.write("is_linear=0\n")
            tb.write(f"shrinkage={t.shrinkage:g}\n")
            tb.write("\n\n")
            tree_bufs.append(tb.getvalue())

        buf.write("tree_sizes=" + " ".join(
            str(len(tb.encode("utf-8"))) for tb in tree_bufs) + "\n\n")
        for tb in tree_bufs:
            buf.write(tb)
        buf.write("end of trees\n\n")
        buf.write("feature_importances:\n")
        imp = self.feature_importances("gain")
        order = np.argsort(-imp)
        for j in order:
            if imp[j] > 0:
                buf.write(f"{self.feature_names[j]}={imp[j]:g}\n")
        buf.write("\nparameters:\n")
        for k, v in self.params.items():
            buf.write(f"[{k}: {v}]\n")
        buf.write("end of parameters\n")
        return buf.getvalue()

    def save_native_model(self, path: str) -> None:
        """Write the native-model text with the content-digest header
        (:data:`DIGEST_HEADER`) prepended, so any later load detects a
        torn or bit-flipped file instead of serving it.  The header is
        one comment line; ``save_native_model_string`` stays the bare
        interop text."""
        with open(path, "w") as f:
            f.write(with_digest_header(self.save_native_model_string()))

    @classmethod
    def load_native_model_string(cls, text: str) -> "Booster":
        # digest header (when present) is verified and stripped FIRST:
        # corrupt bytes raise ModelDigestError before any parsing
        text = split_native_digest(text)
        header, _, rest = text.partition("Tree=")
        head = _parse_kv(header)
        num_class = int(head.get("num_class", 1))
        objective = head.get("objective", "regression")
        feature_names = head.get("feature_names", "").split()
        feature_infos = head.get("feature_infos", "").split()
        max_feature_idx = int(head.get("max_feature_idx", 0))

        trees: List[HostTree] = []
        body = rest.split("end of trees")[0]
        blocks = re.split(r"Tree=\d+\n", "Tree=" + body)
        for block in blocks:
            block = block.strip()
            if not block or block == "Tree=":
                continue
            kv = _parse_kv(block)
            if "num_leaves" not in kv:
                continue
            L = int(kv["num_leaves"])
            num_cat = int(kv.get("num_cat", 0))
            if L > 1:
                dt = _parse_arr(kv["decision_type"], np.int32)
                trees.append(HostTree(
                    split_feature=_parse_arr(kv["split_feature"], np.int32),
                    threshold=_parse_arr(kv["threshold"], np.float64),
                    split_gain=_parse_arr(
                        kv.get("split_gain", "0"), np.float64),
                    left_child=_parse_arr(kv["left_child"], np.int32),
                    right_child=_parse_arr(kv["right_child"], np.int32),
                    decision_type=dt,
                    leaf_value=_parse_arr(kv["leaf_value"], np.float64),
                    leaf_weight=_parse_arr(
                        kv.get("leaf_weight", "0"), np.float64),
                    leaf_count=_parse_arr(
                        kv.get("leaf_count", "0"), np.int64),
                    internal_value=_parse_arr(
                        kv.get("internal_value", "0"), np.float64),
                    internal_weight=_parse_arr(
                        kv.get("internal_weight", "0"), np.float64),
                    internal_count=_parse_arr(
                        kv.get("internal_count", "0"), np.int64),
                    shrinkage=float(kv.get("shrinkage", 1.0)),
                    num_cat=num_cat,
                    cat_boundaries=(_parse_arr(kv["cat_boundaries"],
                                               np.int64).astype(np.int32)
                                    if num_cat > 0
                                    else np.zeros(1, np.int32)),
                    cat_threshold=(_parse_arr(kv["cat_threshold"],
                                              np.int64).astype(np.uint32)
                                   if num_cat > 0
                                   else np.zeros(0, np.uint32)),
                ))
            else:
                lv = _parse_arr(kv["leaf_value"], np.float64)
                trees.append(HostTree(
                    split_feature=np.zeros(0, np.int32),
                    threshold=np.zeros(0, np.float64),
                    split_gain=np.zeros(0, np.float64),
                    left_child=np.zeros(0, np.int32),
                    right_child=np.zeros(0, np.int32),
                    decision_type=np.zeros(0, np.int32),
                    leaf_value=lv,
                    leaf_weight=np.zeros(1, np.float64),
                    leaf_count=np.zeros(1, np.int64),
                    internal_value=np.zeros(0, np.float64),
                    internal_weight=np.zeros(0, np.float64),
                    internal_count=np.zeros(0, np.int64),
                    shrinkage=float(kv.get("shrinkage", 1.0)),
                ))
        return cls(trees, num_class=num_class, objective_str=objective,
                   init_score=0.0, feature_names=feature_names or None,
                   feature_infos=feature_infos or None,
                   max_feature_idx=max_feature_idx)

    @classmethod
    def load_native_model(cls, path: str) -> "Booster":
        with open(path, "rb") as f:
            raw = f.read()
        try:
            text = raw.decode("utf-8")
        except UnicodeDecodeError as e:
            # a digest-stamped file may decode with replacement
            # characters: they alter the body, so the digest check
            # below rejects the file with the right verdict
            # (ModelDigestError, not UnicodeDecodeError).  A
            # digest-less legacy file has no such net — replacement
            # characters would be silently PARSED — so refuse it
            # outright instead of accepting mangled bytes.
            head = raw[:len(DIGEST_HEADER) + 16]
            if raw.startswith(DIGEST_HEADER.encode("utf-8")) \
                    or b".digest.sha256=" in head:
                text = raw.decode("utf-8", errors="replace")
            else:
                raise ModelDigestError(
                    f"native model file {path!r} is not valid UTF-8 "
                    "and carries no digest header; the file is torn "
                    "or binary-corrupted — refusing to load") from e
        return cls.load_native_model_string(text)


class CompiledPredictor:
    """Margin scorer with the prediction path resolved once.

    ``Booster.predict_margin`` re-does shape checks, ``_stack()`` dict
    indexing, ``use_t`` slicing, and the native-vs-jit backend probe on
    EVERY call — pure overhead at serving batch sizes where the walk
    itself is microseconds.  This captures the resolved dispatch at
    construction: pre-sliced stacked arrays, the chosen backend, and the
    class/init-score constants.  Margins are bit-exact with
    ``predict_margin`` (the native path and the jitted walk are pinned
    against each other in tests/test_native_forest.py; this class only
    removes per-call resolution, not arithmetic).

    Staleness contract: the predictor is bound to the forest it was
    built from.  ``Booster.invalidate_cache()`` (required after mutating
    ``trees`` in place) bumps a token; a stale predictor raises
    ``RuntimeError`` on its next call instead of silently scoring the
    old forest.  ``Booster.extended()`` and model loads return NEW
    boosters (with a fresh, empty cache), so predictors of the base
    model stay valid for the base forest.
    """

    def __init__(self, booster: Booster,
                 num_iteration: Optional[int] = None,
                 backend: str = "auto",
                 tree_range: Optional[Tuple[int, int]] = None,
                 include_init_score: bool = True):
        if backend not in ("auto", "native", "jit"):
            raise ValueError(f"backend must be auto|native|jit, "
                             f"got {backend!r}")
        self._booster = booster
        self._token = booster._cache_token
        self._num_trees = len(booster.trees)
        self._K = booster.num_class
        self._init_score = booster.init_score if include_init_score \
            else 0.0
        self.num_features = booster.max_feature_idx + 1
        self.num_iteration = num_iteration
        self.tree_range = tree_range
        s = booster._stack()
        if s is None:
            self._mode = "empty"
            return
        T = s["feat"].shape[0]
        if tree_range is not None:
            # tree-range partial scorer (the fleet's shard slice):
            # bounds must land on num_class boundaries because BOTH
            # walkers assign class = local tree index % K — a
            # misaligned lo would silently rotate classes
            if num_iteration is not None:
                raise ValueError(
                    "pass num_iteration OR tree_range, not both")
            lo, hi = int(tree_range[0]), int(tree_range[1])
            if not 0 <= lo <= hi <= T:
                raise ValueError(
                    f"tree_range {tree_range} outside [0, {T}]")
            if lo % self._K or (hi % self._K and hi != T):
                raise ValueError(
                    f"tree_range {tree_range} must align to "
                    f"num_class={self._K} boundaries")
            if lo == hi:
                self._mode = "empty"
                return
            sl = slice(lo, hi)
        else:
            use_t = T if num_iteration is None \
                else min(num_iteration * self._K, T)
            sl = slice(0, use_t)
        sn = booster._stacked_np
        from .. import native
        native_ok = sn is not None and jax.default_backend() == "cpu" \
            and native.predict_forest_available()
        if backend == "native" and not native_ok:
            raise RuntimeError(
                "backend='native' requested but the native forest "
                "scorer is unavailable on this backend")
        if backend != "jit" and native_ok:
            self._mode = "native"
            self._nargs = (sn["feat"][sl], sn["thr"][sl],
                           sn["left"][sl], sn["right"][sl],
                           sn["leaf"][sl], sn["single"][sl],
                           sn["is_cat"][sl], sn["dleft"][sl],
                           sn["cat_bnd"][sl], sn["cat_words"][sl])
            self._has_cat = sn["has_cat"]
        else:
            self._mode = "jit"
            self._jargs = (s["feat"][sl], s["thr"][sl],
                           s["left"][sl], s["right"][sl],
                           s["leaf"][sl], s["single"][sl],
                           s["is_cat"][sl], s["dleft"][sl],
                           s["cat_bnd"][sl], s["cat_words"][sl])
            self._depth = s["depth"]
            self._has_cat = s["has_cat"]

    @property
    def mode(self) -> str:
        """Resolved backend: 'native', 'jit', or 'empty'."""
        return self._mode

    def _check_fresh(self) -> None:
        b = self._booster
        if b._cache_token != self._token \
                or len(b.trees) != self._num_trees:
            raise RuntimeError(
                "stale CompiledPredictor: the bound Booster's forest "
                "changed after this predictor was built (invalidate_"
                "cache() was called or trees were added); rebuild with "
                "booster.predictor()")

    def __call__(self, X):
        """Raw margins, bit-exact with ``predict_margin``: (n,) float32
        for single-class, (n, K) for multiclass."""
        self._check_fresh()
        shape = np.shape(X)
        if len(shape) != 2 or shape[1] < self.num_features:
            raise ValueError(
                f"Model uses feature index {self.num_features - 1} but "
                f"input has shape {shape}; expected (n, >= "
                f"{self.num_features})")
        n = shape[0]
        K = self._K
        if self._mode == "empty":
            base = jnp.full((n,), self._init_score, jnp.float32)
            return jnp.tile(base[:, None], (1, K))[:, 0] if K == 1 else \
                jnp.tile(base[:, None], (1, K))
        if self._mode == "native":
            from .. import native
            Xnp = np.ascontiguousarray(np.asarray(X, np.float32))
            out = np.zeros((n, K), np.float32)
            native.predict_forest(Xnp, *self._nargs, K, self._has_cat,
                                  out)
            out += np.float32(self._init_score)
            return out[:, 0] if K == 1 else out
        X = jnp.asarray(X, jnp.float32)
        margins = _predict_forest(X, *self._jargs, self._depth, K,
                                  self._has_cat)
        margins = margins + self._init_score
        return margins[:, 0] if K == 1 else margins


def _float_text(v) -> str:
    """``v`` to 17 places after the point, or, where that does not read
    back as ``v`` (under 0.1 it keeps too few digits: a threshold equal
    to a value in the data, such as a pixel level, would send that value
    the other way), the shortest text that does."""
    text = np.format_float_positional(v, precision=17, trim="0")
    if float(text) == v or v != v:
        return text
    return np.format_float_positional(v, trim="0")


def _arr_line(name: str, arr: np.ndarray) -> str:
    if arr.dtype.kind == "f":
        vals = " ".join(_float_text(v) for v in arr)
    else:
        # a forest with categorical splits on wide columns holds tens of
        # millions of bitset words: no Python-level loop over them
        vals = " ".join(map(str, np.asarray(arr).tolist()))
    return f"{name}={vals}\n"


def _parse_kv(block: str) -> Dict[str, str]:
    out = {}
    for line in block.splitlines():
        if "=" in line:
            k, _, v = line.partition("=")
            out[k.strip()] = v.strip()
    return out


def _parse_arr(s: str, dtype) -> np.ndarray:
    if not s:
        return np.zeros(0, dtype)
    return np.array(s.split(), dtype=np.float64).astype(dtype)


def _param_from_str(s: str, key: str, default: float) -> float:
    m = re.search(rf"{key}:([0-9.eE+-]+)", s)
    return float(m.group(1)) if m else default


def _cat_go_left(x, j, tdleft_node, cat_bnd, cat_words):
    """Raw-value categorical decision: x in node j's bitset → left.

    NaN routes by the node's default_left bit; negative / out-of-range
    values (unseen categories) route right, matching LightGBM.
    """
    j = jnp.clip(j, 0, cat_bnd.shape[0] - 2)
    b0 = cat_bnd[j]
    b1 = cat_bnd[j + 1]
    xnan = jnp.isnan(x)
    c = jnp.where(xnan, -1.0, x).astype(jnp.int32)
    widx = b0 + (c >> 5)
    ok = (c >= 0) & (widx < b1)
    word = cat_words[jnp.clip(widx, 0, cat_words.shape[0] - 1)]
    bit = ((word >> (c & 31).astype(jnp.uint32)) & 1).astype(bool)
    return jnp.where(xnan, tdleft_node > 0, ok & bit)


@functools.partial(jax.jit,
                   static_argnames=("depth", "num_class", "has_cat"))
def _predict_forest(X, feat, thr, left, right, leaf, single, is_cat, dleft,
                    cat_bnd, cat_words, depth, num_class, has_cat=True):
    """Sum tree outputs: scan over trees, fixed-depth gather walk per tree."""
    n = X.shape[0]
    K = num_class

    def one_tree(carry, tree):
        scores = carry
        (tfeat, tthr, tleft, tright, tleaf, tsingle, tcat, tdleft,
         tbnd, twords, k) = tree
        node = jnp.where(tsingle, jnp.full(n, -1, jnp.int32),
                         jnp.zeros(n, jnp.int32))

        def body(_, node):
            is_leaf = node < 0
            safe = jnp.maximum(node, 0)
            f = tfeat[safe]
            x = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
            # NaN fails the compare and goes by the node's NaN direction
            go_left = (x <= tthr[safe]) | (jnp.isnan(x) & (tdleft[safe] > 0))
            if has_cat:  # static: numeric-only forests skip the bitset walk
                left_cat = _cat_go_left(x, tthr[safe].astype(jnp.int32),
                                        tdleft[safe], tbnd, twords)
                go_left = jnp.where(tcat[safe] > 0, left_cat, go_left)
            nxt = jnp.where(go_left, tleft[safe], tright[safe])
            return jnp.where(is_leaf, node, nxt)

        node = jax.lax.fori_loop(0, depth, body, node)
        vals = tleaf[-(node + 1)]
        scores = scores.at[:, k].add(vals)
        return scores, None

    ks = jnp.arange(feat.shape[0], dtype=jnp.int32) % K
    init = jnp.zeros((n, K), jnp.float32)
    out, _ = jax.lax.scan(one_tree, init,
                          (feat, thr, left, right, leaf, single, is_cat,
                           dleft, cat_bnd, cat_words, ks))
    return out


@functools.partial(jax.jit, static_argnames=("depth", "has_cat"))
def _predict_leaves(X, feat, thr, left, right, single, is_cat, dleft,
                    cat_bnd, cat_words, depth, has_cat=True):
    n = X.shape[0]

    def one_tree(_, tree):
        tfeat, tthr, tleft, tright, tsingle, tcat, tdleft, tbnd, twords = \
            tree
        node = jnp.where(tsingle, jnp.full(n, -1, jnp.int32),
                         jnp.zeros(n, jnp.int32))

        def body(_, node):
            is_leaf = node < 0
            safe = jnp.maximum(node, 0)
            f = tfeat[safe]
            x = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
            # NaN fails the compare and goes by the node's NaN direction
            go_left = (x <= tthr[safe]) | (jnp.isnan(x) & (tdleft[safe] > 0))
            if has_cat:  # static: numeric-only forests skip the bitset walk
                left_cat = _cat_go_left(x, tthr[safe].astype(jnp.int32),
                                        tdleft[safe], tbnd, twords)
                go_left = jnp.where(tcat[safe] > 0, left_cat, go_left)
            nxt = jnp.where(go_left, tleft[safe], tright[safe])
            return jnp.where(is_leaf, node, nxt)

        node = jax.lax.fori_loop(0, depth, body, node)
        return None, -(node + 1)

    _, leaves = jax.lax.scan(one_tree, None,
                             (feat, thr, left, right, single, is_cat,
                              dleft, cat_bnd, cat_words))
    return leaves.T.astype(jnp.int32)

"""LightGBM-compatible estimator base.

TPU-native analog of the reference's ``LightGBMBase`` shared train()
orchestration (lightgbm/LightGBMBase.scala, expected path, UNVERIFIED;
SURVEY.md §3.1).  Where the reference coalesces partitions to one task per
executor, runs a socket rendezvous and boots the native engine per executor,
this estimator bins features on host, ships the binned matrix to the device
mesh, and runs the jitted boosting loop (:mod:`mmlspark_tpu.gbdt.engine`).

Param names mirror the reference's public API (numIterations, learningRate,
numLeaves, …) so existing mmlspark code ports unchanged.  Cluster-shaped
params that have no TPU meaning (``useBarrierExecutionMode``, ``numTasks``,
``numThreads``) are accepted and recorded but do not affect execution.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

from ..core.params import (Param, Params, TypeConverters, HasFeaturesCol,
                           HasLabelCol, HasPredictionCol, HasWeightCol,
                           HasValidationIndicatorCol)
from ..core.pipeline import Estimator, Model
from ..core.schema import DataTable, SparseColumn, features_matrix
from ..core import serialize
from .binning import fit_bin_mapper
from .booster import Booster
from .engine import TrainParams, train
from .objectives import get_objective

log = logging.getLogger("mmlspark_tpu.gbdt")


class LightGBMParams(HasFeaturesCol, HasLabelCol, HasPredictionCol,
                     HasWeightCol, HasValidationIndicatorCol):
    """Shared LightGBM params — names track the reference's LightGBMParams."""

    numIterations = Param("numIterations", "Number of boosting iterations",
                          default=100, typeConverter=TypeConverters.toInt)
    learningRate = Param("learningRate", "Shrinkage rate", default=0.1,
                         typeConverter=TypeConverters.toFloat)
    numLeaves = Param("numLeaves", "Max leaves per tree", default=31,
                      typeConverter=TypeConverters.toInt)
    maxDepth = Param("maxDepth", "Max tree depth (<=0 means no limit)",
                     default=-1, typeConverter=TypeConverters.toInt)
    maxBin = Param("maxBin", "Max number of feature bins", default=255,
                   typeConverter=TypeConverters.toInt)
    lambdaL1 = Param("lambdaL1", "L1 regularization", default=0.0,
                     typeConverter=TypeConverters.toFloat)
    lambdaL2 = Param("lambdaL2", "L2 regularization", default=0.0,
                     typeConverter=TypeConverters.toFloat)
    minSumHessianInLeaf = Param("minSumHessianInLeaf",
                                "Minimal sum of hessians in one leaf",
                                default=1e-3,
                                typeConverter=TypeConverters.toFloat)
    minDataInLeaf = Param("minDataInLeaf",
                          "Minimal number of rows in one leaf", default=20,
                          typeConverter=TypeConverters.toInt)
    minGainToSplit = Param("minGainToSplit", "Minimal split gain", default=0.0,
                           typeConverter=TypeConverters.toFloat)
    baggingFraction = Param("baggingFraction", "Row subsample fraction",
                            default=1.0, typeConverter=TypeConverters.toFloat)
    baggingFreq = Param("baggingFreq",
                        "Resample rows every k iterations (0 disables)",
                        default=0, typeConverter=TypeConverters.toInt)
    baggingSeed = Param("baggingSeed", "Bagging seed", default=3,
                        typeConverter=TypeConverters.toInt)
    featureFraction = Param("featureFraction",
                            "Feature subsample fraction per tree",
                            default=1.0, typeConverter=TypeConverters.toFloat)
    earlyStoppingRound = Param("earlyStoppingRound",
                               "Stop if validation metric doesn't improve "
                               "for this many rounds (0 disables)",
                               default=0, typeConverter=TypeConverters.toInt)
    boostFromAverage = Param("boostFromAverage",
                             "Start scores from the label average",
                             default=True, typeConverter=TypeConverters.toBool)
    verbosity = Param("verbosity", "Engine verbosity", default=1,
                      typeConverter=TypeConverters.toInt)
    objective = Param("objective", "Training objective", default="regression",
                      typeConverter=TypeConverters.toString)
    parallelism = Param("parallelism",
                        "Tree learner parallelism: serial, data, feature or "
                        "voting (mapped to mesh axes on TPU)",
                        default="data", typeConverter=TypeConverters.toString)
    autoMeshMinRows = Param(
        "autoMeshMinRows",
        "Minimum training rows before fit() auto-shards across all "
        "visible devices when no mesh is pinned; smaller fits train "
        "serially (the per-fit shard_map compile and collective "
        "overhead dwarfs any win on small data).  setMesh() always "
        "shards regardless of size; 0 restores unconditional "
        "auto-sharding.",
        default=65536, typeConverter=TypeConverters.toInt)
    useBarrierExecutionMode = Param(
        "useBarrierExecutionMode",
        "Accepted for API parity; TPU meshes are always gang-scheduled",
        default=False, typeConverter=TypeConverters.toBool)
    numTasks = Param("numTasks",
                     "Accepted for API parity; the mesh shape decides "
                     "task layout on TPU", default=0,
                     typeConverter=TypeConverters.toInt)
    numThreads = Param("numThreads", "Accepted for API parity", default=0,
                       typeConverter=TypeConverters.toInt)
    initScoreCol = Param("initScoreCol", "Column with per-row initial scores",
                         default=None, typeConverter=TypeConverters.toString)
    initModelPath = Param(
        "initModelPath",
        "Path to a saved native (LightGBM-text) model to CONTINUE "
        "training from: its margins seed the boosting scores and its "
        "trees prepend the fitted forest (LightGBM's init_model / "
        "keep_training_booster)", default="",
        typeConverter=TypeConverters.toString)
    checkpointDir = Param(
        "checkpointDir",
        "Directory for chunk-boundary training checkpoints: a killed "
        "fit re-run with the same settings resumes from the last "
        "completed chunk, bit-identically (empty disables)", default="",
        typeConverter=TypeConverters.toString)
    featuresShapCol = Param("featuresShapCol",
                            "Output column for SHAP values (empty disables)",
                            default="", typeConverter=TypeConverters.toString)
    seed = Param("seed", "Random seed", default=42,
                 typeConverter=TypeConverters.toInt)
    boostingType = Param("boostingType",
                         "gbdt (plain boosting), goss (gradient-based "
                         "one-side sampling), dart (dropout boosting) or "
                         "rf (random forest)", default="gbdt",
                         typeConverter=TypeConverters.toString)
    dropRate = Param("dropRate", "dart: per-tree dropout probability",
                     default=0.1, typeConverter=TypeConverters.toFloat)
    maxDrop = Param("maxDrop", "dart: max trees dropped per iteration",
                    default=50, typeConverter=TypeConverters.toInt)
    skipDrop = Param("skipDrop", "dart: probability of skipping dropout "
                     "for an iteration", default=0.5,
                     typeConverter=TypeConverters.toFloat)
    dropSeed = Param("dropSeed", "dart: dropout random seed", default=4,
                     typeConverter=TypeConverters.toInt)
    topRate = Param("topRate",
                    "GOSS: fraction of rows kept by largest gradient",
                    default=0.2, typeConverter=TypeConverters.toFloat)
    otherRate = Param("otherRate",
                      "GOSS: fraction of remaining rows sampled (amplified "
                      "by (1-topRate)/otherRate)", default=0.1,
                      typeConverter=TypeConverters.toFloat)
    histogramMethod = Param("histogramMethod",
                            "Histogram build: auto (native on the CPU, "
                            "dot16 on the TPU, segment elsewhere), native, "
                            "segment, dot16, onehot", default="auto",
                            typeConverter=TypeConverters.toString)
    collective = Param("collective",
                       "Cross-shard histogram reduction on mesh fits: "
                       "auto, psum (XLA all-reduce) or ring (Pallas "
                       "on-chip ring reduce-scatter/all-gather; "
                       "docs/collectives.md)", default="auto",
                       typeConverter=TypeConverters.toString)
    quantizedGrad = Param(
        "quantizedGrad",
        "Quantized-gradient training (LightGBM use_quantized_grad "
        "analog): 'off' keeps f32 gradients; '16'/'8' discretize (g,h) "
        "per boost round onto a seeded stochastically-rounded integer "
        "grid, accumulate histograms in int32 and cross shards in the "
        "narrowest wire dtype the row count admits "
        "(docs/collectives.md).  Gains still evaluate in f32.  "
        "gbdt/goss/rf only; dart and ranking fits fall back to f32",
        default="off", typeConverter=TypeConverters.toString)
    categoricalSlotIndexes = Param(
        "categoricalSlotIndexes",
        "Feature indexes treated as categorical (reference "
        "LightGBMParams.categoricalSlotIndexes)", default=None,
        typeConverter=TypeConverters.toListInt)
    categoricalSlotNames = Param(
        "categoricalSlotNames",
        "Feature names treated as categorical (resolved against the "
        "features column names)", default=None,
        typeConverter=TypeConverters.toListString)
    catSmooth = Param("catSmooth", "Categorical smoothing (cat_smooth)",
                      default=10.0, typeConverter=TypeConverters.toFloat)
    catL2 = Param("catL2", "Extra L2 for categorical splits (cat_l2)",
                  default=10.0, typeConverter=TypeConverters.toFloat)
    maxCatThreshold = Param(
        "maxCatThreshold", "Max categories on the smaller split side",
        default=32, typeConverter=TypeConverters.toInt)
    maxCatToOnehot = Param(
        "maxCatToOnehot", "Cardinality at or below which one-vs-rest "
        "splits are used", default=4, typeConverter=TypeConverters.toInt)
    faultTolerantRetries = Param(
        "faultTolerantRetries",
        "Chunk-level training failure recovery: snapshot boosting state "
        "at chunk boundaries and replay a failed chunk up to this many "
        "times (0 disables; SURVEY.md section 5.3 analog of executor "
        "gang-restart)", default=0, typeConverter=TypeConverters.toInt)
    topK = Param("topK",
                 "voting parallelism (PV-Tree): features each worker "
                 "votes per split (reference LightGBMParams.topK)",
                 default=20, typeConverter=TypeConverters.toInt)
    enableBundle = Param(
        "enableBundle",
        "Exclusive Feature Bundling (LightGBM enable_bundle): merge "
        "mutually-exclusive sparse features (one-hot blocks) into single "
        "bundle columns so histogram work scales with bundles, not "
        "features.  The table is bundled once, at binning time, from a "
        "dense or a sparse (SparseColumn) feature column, and at "
        "maxConflictRate 0 no row loses a value.  Off by default; applies "
        "to numeric tables of at most 256 bins under any boosting type, "
        "one device or a data mesh (not with a ranker, categorical slots, "
        "feature shards, or voting, goss or dart on a mesh)",
        default=False, typeConverter=TypeConverters.toBool)
    maxConflictRate = Param(
        "maxConflictRate",
        "EFB conflict budget (LightGBM max_conflict_rate): fraction of "
        "rows allowed to violate exclusivity inside one bundle",
        default=0.0, typeConverter=TypeConverters.toFloat)
    passThroughArgs = Param("passThroughArgs",
                            "Raw 'key=value key=value' LightGBM param string "
                            "recorded into the model file",
                            default="", typeConverter=TypeConverters.toString)
    profileTraceDir = Param(
        "profileTraceDir",
        "Directory for a jax.profiler device trace of the whole fit "
        "(empty disables).  Perfetto/TensorBoard-readable; "
        "core.profiling.summarize_trace and idle_by_span parse it (the "
        "fit logs both tables) — the "
        "TPU-native replacement for the reference's Spark-UI stage "
        "timings (SURVEY.md section 5.1)",
        default="", typeConverter=TypeConverters.toString)

    def _train_params(self) -> TrainParams:
        pass_through = {}
        for tok in self.getPassThroughArgs().split():
            if "=" in tok:
                k, _, v = tok.partition("=")
                pass_through[k] = v
        return TrainParams(
            num_iterations=self.getNumIterations(),
            learning_rate=self.getLearningRate(),
            num_leaves=self.getNumLeaves(),
            max_depth=self.getMaxDepth(),
            max_bin=self.getMaxBin(),
            lambda_l1=self.getLambdaL1(),
            lambda_l2=self.getLambdaL2(),
            min_data_in_leaf=self.getMinDataInLeaf(),
            min_sum_hessian_in_leaf=self.getMinSumHessianInLeaf(),
            min_gain_to_split=self.getMinGainToSplit(),
            bagging_fraction=self.getBaggingFraction(),
            bagging_freq=self.getBaggingFreq(),
            feature_fraction=self.getFeatureFraction(),
            early_stopping_round=self.getEarlyStoppingRound(),
            boost_from_average=self.getBoostFromAverage(),
            seed=self.getSeed(),
            bagging_seed=self.getBaggingSeed(),
            boosting=self.getBoostingType(),
            top_rate=self.getTopRate(),
            other_rate=self.getOtherRate(),
            drop_rate=self.getDropRate(),
            max_drop=self.getMaxDrop(),
            skip_drop=self.getSkipDrop(),
            drop_seed=self.getDropSeed(),
            histogram_method=self.getHistogramMethod(),
            collective=self.getCollective(),
            quantized_grad=self.getQuantizedGrad(),
            verbosity=self.getVerbosity(),
            parallelism=self.getParallelism(),
            top_k=self.getTopK(),
            fault_tolerant_retries=self.getFaultTolerantRetries(),
            checkpoint_dir=self.getOrDefault("checkpointDir"),
            enable_bundle=self.getEnableBundle(),
            max_conflict_rate=self.getMaxConflictRate(),
            cat_smooth=self.getCatSmooth(),
            cat_l2=self.getCatL2(),
            max_cat_threshold=self.getMaxCatThreshold(),
            max_cat_to_onehot=self.getMaxCatToOnehot(),
            pass_through=pass_through,
        )


class LightGBMBase(Estimator, LightGBMParams):
    """Shared fit() orchestration for classifier/regressor/ranker."""

    __abstractstage__ = True

    _default_objective = "regression"
    _mesh = None

    def setMesh(self, mesh) -> "LightGBMBase":
        """Pin an explicit ``(data, feature)`` device mesh for training."""
        self._mesh = mesh
        return self

    def _objective_kwargs(self) -> Dict:
        return {}

    def _prepare_labels(self, y: np.ndarray) -> np.ndarray:
        return np.asarray(y, np.float64)

    def _make_model(self, booster: Booster) -> "LightGBMModelBase":
        raise NotImplementedError

    def _grad_fn_override(self, table: DataTable, train_idx, y, w):
        return None

    def _ranking_info(self, table: DataTable, train_idx):
        """Structured query info for the mesh-sharded lambdarank path
        (rankers override; see engine._train_distributed_ranking)."""
        return None

    def _val_metric(self):
        return None

    def _val_metric_fn(self, table: DataTable, val_mask):
        """Validation metric (lower is better); default ignores the table.
        Rankers override this to capture validation query structure."""
        return self._val_metric()

    def _fit(self, table: DataTable) -> "LightGBMModelBase":
        # a sparse vector column stays sparse: it is binned, and with
        # enableBundle bundled, from its entries
        X = features_matrix(table, self.getFeaturesCol(), sparse=True)
        y = self._prepare_labels(table[self.getLabelCol()])
        n = X.shape[0]
        wcol = self.getWeightCol()
        w = np.asarray(table[wcol], np.float64) if wcol else None

        vcol = self.getValidationIndicatorCol()
        if vcol:
            val_mask = np.asarray(table[vcol]).astype(bool)
            train_idx = ~val_mask
        else:
            val_mask = None
            train_idx = np.ones(n, bool)

        obj_name = getattr(self, "_resolved_objective", None) \
            or self.getObjective() or self._default_objective
        num_class = getattr(self, "_num_class", 1)
        if obj_name in ("multiclass", "softmax", "multiclassova",
                        "ova") and num_class <= 1:
            num_class = int(np.max(y)) + 1
        objective = get_objective(obj_name, num_class=num_class,
                                  **self._objective_kwargs())

        feature_names = list(
            getattr(table[self.getFeaturesCol()], "columns", [])) or None
        cat_idx = list(self.getCategoricalSlotIndexes() or [])
        for nm in self.getCategoricalSlotNames() or []:
            if not feature_names or nm not in feature_names:
                raise ValueError(
                    f"categoricalSlotNames: {nm!r} not found among feature "
                    f"columns {feature_names}")
            cat_idx.append(feature_names.index(nm))
        cat_idx = sorted(set(cat_idx))
        # materialize the train slice once (val_mask is None on the common
        # no-validation path, where X IS the train set — two boolean
        # gathers of an 80 MB matrix cost ~1s of pure copying on one core)
        X_train = X if val_mask is None else X[train_idx]
        mapper = fit_bin_mapper(X_train, max_bin=self.getMaxBin(),
                                seed=self.getSeed(),
                                categorical_features=cat_idx or None)
        y_train = y[train_idx]
        w_train = w[train_idx] if w is not None else None
        iscol = self.getInitScoreCol()
        init_scores = (np.asarray(table[iscol], np.float64)[train_idx]
                       if iscol else None)
        has_val = val_mask is not None and val_mask.any()

        params = self._train_params()
        init_booster = None
        val_init_scores = None
        imp = self.getOrDefault("initModelPath")
        if imp:
            # Continued training (LightGBM init_model): boost from the
            # saved model's margins; its trees prepend the new forest.
            # Guard on the RESOLVED boosting type — passThroughArgs keys
            # naming TrainParams fields apply in __post_init__ and must
            # not bypass this check.
            if params.boosting in ("dart", "rf"):
                raise ValueError(
                    "initModelPath requires boostingType gbdt or goss: "
                    "dart re-weights (and rf averages) the WHOLE "
                    "ensemble, which is not additive over a frozen "
                    "prefix")
            init_booster = Booster.load_native_model(imp)
            if init_booster.num_class != \
                    objective.num_model_per_iteration:
                raise ValueError(
                    f"initModelPath model has num_class="
                    f"{init_booster.num_class}, this fit trains "
                    f"{objective.num_model_per_iteration}")
            if init_booster.max_feature_idx != X.shape[1] - 1:
                raise ValueError(
                    f"initModelPath model was trained on "
                    f"{init_booster.max_feature_idx + 1} features, "
                    f"this table has {X.shape[1]}")
            margins = np.asarray(init_booster.predict_margin(
                _dense_rows(X_train)), np.float64)
            init_scores = (margins if init_scores is None
                           else init_scores + margins)
            if has_val:
                # validation margins seed the val scores too (LightGBM's
                # init_model seeds valid sets): early stopping decides on
                # the MERGED model's trajectory, not the residual's
                val_init_scores = np.asarray(init_booster.predict_margin(
                    _dense_rows(X[val_mask])), np.float64)
        ranking_info = self._ranking_info(table, train_idx)
        mesh = getattr(self, "_mesh", None)
        mesh_multi = mesh is not None and int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names])) > 1
        if mesh_multi and ranking_info is not None:
            # the mesh lambdarank path consumes ranking_info directly;
            # don't build (and device-transfer) the serial gradient
            # closure just to discard it
            grad_override = None
        else:
            grad_override = self._grad_fn_override(table, train_idx,
                                                   y_train, w_train)
        # Distributed by default when a mesh is available, like the
        # reference trains across all executors (SURVEY.md §3.1); the
        # parallelism param picks the axis layout.
        # goss stays serial unless a mesh is pinned explicitly (per-shard
        # sampling is a semantic choice); dart is host-loop only.
        # Below autoMeshMinRows the fit stays serial: sharding a few
        # thousand rows buys nothing and pays a multi-second shard_map
        # compile plus per-iteration collectives.
        if mesh is None and grad_override is None and ranking_info is None \
                and self.getBoostingType() not in ("goss", "dart") \
                and len(y_train) >= self.getAutoMeshMinRows():
            import jax
            if jax.device_count() > 1:
                from .distributed import resolve_mesh
                mesh = resolve_mesh(self.getParallelism())

        # binning, and bundling with it: once, here (gbdt/efb.py)
        from .efb import bundle_for_training, bundling_applies
        sparse = isinstance(X_train, SparseColumn)
        binned = (mapper.bin_entries(X_train) if sparse
                  else mapper.transform_packed(X_train))
        bins = None
        if bundling_applies(
                mapper, params.enable_bundle,
                ranker=grad_override is not None or ranking_info is not None,
                mesh=mesh, voting=params.parallelism == "voting",
                goss=params.boosting == "goss",
                dart=params.boosting == "dart"):
            bins = bundle_for_training(
                binned, mapper, params.max_conflict_rate, params.seed,
                params.verbosity)
        if bins is None:
            bins = binned.toarray() if sparse else binned

        val_kwargs = {}
        if has_val:
            val_kwargs = dict(
                val_bins=mapper.transform_packed(X[val_mask]),
                val_labels=y[val_mask],
                val_weights=w[val_mask] if w is not None else None,
                val_metric=self._val_metric_fn(table, val_mask),
            )
            if val_init_scores is not None:
                val_kwargs["val_init_scores"] = val_init_scores
        from ..core.profiling import maybe_trace, trace_tables
        trace_dir = self.getProfileTraceDir()
        with maybe_trace(trace_dir):
            booster = train(
                bins, y_train, w_train, mapper, objective, params,
                feature_names=feature_names,
                grad_fn_override=grad_override,
                mesh=mesh,
                init_scores=init_scores,
                ranking_info=ranking_info,
                **val_kwargs)
        if trace_dir:
            log.info("trace of the fit under %s\n%s", trace_dir,
                     trace_tables(trace_dir))
        if init_booster is not None:
            booster = init_booster.extended(booster)
        model = self._make_model(booster)
        model.setParams(**{k: v for k, v in self._iterSetParams()
                           if model.hasParam(k)})
        return model


def _dense_rows(X):
    """Raw rows as the forest walk reads them (a continued fit's init
    margins): a sparse column written out."""
    return X.toarray() if isinstance(X, SparseColumn) else X


class LightGBMModelBase(Model, HasFeaturesCol, HasPredictionCol):
    """Shared scoring transformer; holds a :class:`Booster`."""

    __abstractstage__ = True

    featuresShapCol = Param("featuresShapCol",
                            "Output column for SHAP values (empty disables)",
                            default="", typeConverter=TypeConverters.toString)

    def __init__(self, booster: Optional[Booster] = None, **kwargs):
        super().__init__(**kwargs)
        self._booster = booster

    def getModel(self) -> Booster:
        """The underlying booster (mmlspark API parity)."""
        return self._booster

    def getNativeModel(self) -> str:
        return self._booster.save_native_model_string()

    def saveNativeModel(self, path: str, overwrite: bool = True) -> None:
        """Save in LightGBM text format, loadable by stock LightGBM.

        ``overwrite=False`` refuses to clobber an existing file, matching
        the reference's ``saveNativeModel(filename, overwrite)``
        (src/main/scala LightGBMClassifier.scala model save API).
        """
        import os
        if not overwrite and os.path.exists(path):
            raise FileExistsError(
                f"{path} exists and overwrite=False")
        self._booster.save_native_model(path)

    @classmethod
    def loadNativeModel(cls, path: str) -> "LightGBMModelBase":
        return cls(booster=Booster.load_native_model(path))

    @classmethod
    def loadNativeModelFromFile(cls, path: str) -> "LightGBMModelBase":
        """Reference-parity alias (LightGBMClassificationModel.
        loadNativeModelFromFile)."""
        return cls.loadNativeModel(path)

    @classmethod
    def loadNativeModelFromString(cls, model_str: str
                                  ) -> "LightGBMModelBase":
        """Reference-parity alias: parse a LightGBM model text blob."""
        return cls(booster=Booster.load_native_model_string(model_str))

    def _with_shap(self, table, X):
        """Append the featuresShapCol column (TreeSHAP contributions) when
        the param is set — reference featuresShapCol semantics."""
        col = self.getFeaturesShapCol()
        if not col:
            return table
        contribs = self._booster.predict_contrib(X)
        arr = np.empty(len(contribs), dtype=object)
        for i, row in enumerate(contribs):
            arr[i] = row
        return table.withColumn(col, arr)

    def getFeatureImportances(self, importance_type: str = "split"):
        return list(self._booster.feature_importances(importance_type))

    def _save_extra(self, path: str) -> None:
        import os
        with open(os.path.join(path, "model.lgb.txt"), "w") as f:
            f.write(self._booster.save_native_model_string())

    def _load_extra(self, path: str) -> None:
        import os
        self._booster = Booster.load_native_model(
            os.path.join(path, "model.lgb.txt"))

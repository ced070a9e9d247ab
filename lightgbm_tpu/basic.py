"""User-facing Dataset and Booster.

Role parity with the reference Python binding python-package/lightgbm/basic.py
(Dataset at :683+, Booster at :1412+), minus the ctypes layer: the "native"
side here is the JAX engine, so handles are plain Python objects.  Lazy
construction, validation-set alignment to the training mappers, and the
update/eval/predict/save surface mirror the reference binding.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .boosting.gbdt import GBDT
from .boosting.variants import create_boosting
from .config import Config
from .io.dataset import BinnedDataset, Metadata
from .metric import create_metrics
from .models.gbdt_model import GBDTModel
from .objective import create_objective, create_objective_from_model_string
from .runtime import tracing
from .utils.log import LightGBMError, Log


def _is_dataframe(data) -> bool:
    return hasattr(data, "dtypes") and hasattr(data, "columns")


def _data_from_pandas(data, feature_name, categorical_feature,
                      pandas_categorical):
    """DataFrame -> (X f64, names, categorical indices, pandas_categorical).

    Reference basic.py _data_from_pandas semantics: category-dtype columns
    become their category CODES (-1/unseen -> NaN); the per-column category
    lists are captured on the training set and re-applied positionally to
    validation/prediction frames so codes stay consistent."""
    cat_cols = [c for c in data.columns if str(data[c].dtype) == "category"]
    if pandas_categorical is None:          # training frame defines them
        pandas_categorical = [list(data[c].cat.categories) for c in cat_cols]
    elif len(cat_cols) != len(pandas_categorical):
        raise LightGBMError(
            "train and valid dataset categorical_feature do not match")
    if cat_cols:
        data = data.copy()
        for c, cats in zip(cat_cols, pandas_categorical):
            col = data[c]
            if list(col.cat.categories) != list(cats):
                col = col.cat.set_categories(cats)
            codes = np.asarray(col.cat.codes, dtype=np.float64)
            codes = np.where(codes < 0, np.nan, codes)
            data[c] = codes
    if feature_name in ("auto", None):
        names = [str(c) for c in data.columns]
    else:
        names = list(feature_name)
    cols = [str(c) for c in data.columns]

    def _pos(name):
        # category columns are located by their DataFrame position, so a
        # user-renaming feature_name list still works; user-named
        # categorical_feature entries must exist in the names
        if name in names:
            return names.index(name)
        if name in cols:
            return cols.index(name)
        raise LightGBMError("categorical column %r not found among the "
                            "feature names %s" % (name, names))

    cat_idx = []
    if categorical_feature in ("auto", None):
        cat_idx = [_pos(str(c)) for c in cat_cols]
    else:
        for cf in categorical_feature:
            cat_idx.append(_pos(cf) if isinstance(cf, str) else int(cf))
        for c in cat_cols:
            i = _pos(str(c))
            if i not in cat_idx:
                cat_idx.append(i)
    X = data.to_numpy(dtype=np.float64)
    return X, names, sorted(set(cat_idx)), pandas_categorical


def _load_pandas_categorical(model_text: str):
    """Parse the python-binding's trailing pandas_categorical line
    (reference basic.py _load_pandas_categorical)."""
    import json as _json
    idx = model_text.rfind("\npandas_categorical:")
    if idx < 0:
        return None
    line = model_text[idx + len("\npandas_categorical:"):].split("\n")[0]
    try:
        return _json.loads(line)
    except ValueError:
        return None


def _is_scipy_sparse(data) -> bool:
    return data.__class__.__module__.startswith("scipy.sparse")


def _sparse_rows(data, idx: np.ndarray) -> np.ndarray:
    """Row-slice a scipy.sparse matrix while still sparse, densify only
    the slice (cv folds / subsets of large sparse inputs must never
    materialize the full dense matrix)."""
    return np.asarray(data.tocsr()[idx].toarray(), dtype=np.float64)


def _slice_rows(data, idx: np.ndarray) -> np.ndarray:
    """Row-slice any supported input matrix (sparse checked before the
    `.values` duck test — dok_matrix subclasses dict, whose .values method
    would otherwise win)."""
    if _is_scipy_sparse(data):
        return _sparse_rows(data, idx)
    return _to_2d_float(data)[idx]


def _to_2d_float(data, pandas_categorical=None) -> np.ndarray:
    if _is_dataframe(data):
        data, _, _, _ = _data_from_pandas(data, "auto", "auto",
                                          pandas_categorical)
    elif _is_scipy_sparse(data):
        # reference basic.py accepts csr/csc/coo/...; the binning layer is
        # dense-columnar (EFB recovers the storage win for one-hot-style
        # sparsity — docs/STORAGE.md), so densify at the boundary.  Checked
        # BEFORE the .values duck test: dok_matrix subclasses dict, whose
        # .values method would shadow this branch.
        data = data.toarray()
    elif hasattr(data, "values"):  # pandas Series
        data = data.values
    if (isinstance(data, np.ndarray) and data.ndim == 2
            and data.dtype == np.float32 and data.flags.c_contiguous):
        # a float32 table stays as it is: a float32 value widens to
        # float64 exactly, and find-bin, the native encode and the
        # predictors widen a value as they read it, so the bins and the
        # scores are those of its float64 copy, which at 10^6 rows x 10^3
        # columns is 8 GB not allocated
        return data
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


class Dataset:
    """Raw data + lazily-constructed binned form (basic.py Dataset semantics)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None, feature_name="auto",
                 categorical_feature="auto", params: Optional[Dict] = None,
                 free_raw_data: bool = False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self.pandas_categorical = None  # per-column category lists

    # -- construction --------------------------------------------------------
    def construct(self, config: Optional[Config] = None) -> "Dataset":
        if self._binned is not None:
            return self
        # the whole of ingest as one span; `BinnedDataset.from_matrix`
        # names its parts under it, what is left is this span's self time
        with tracing.span("dataset/construct"):
            return self._construct(config)

    def _construct(self, config: Optional[Config]) -> "Dataset":
        if config is None:
            config = Config(self.params)
        from .io.stream import StreamingDatasetBuilder
        if isinstance(self.data, StreamingDatasetBuilder) or \
                (hasattr(self.data, "__next__")
                 and not isinstance(self.data, np.ndarray)):
            # streaming ingest (ISSUE 8): a chunk iterator or an explicit
            # StreamingDatasetBuilder — chunks were (or are now) pushed
            # without a file detour, and finalize() produces the same
            # binned dataset the parser path would
            return self._construct_stream(config)
        if isinstance(self.data, str):
            # a path: binary dataset cache (save_binary) or a text data file.
            # A validation set given as a path still aligns to the training
            # mappers/bundles through self.reference (Dataset::CreateValid).
            ref_mappers = ref_bundle = None
            if self.reference is not None:
                self.reference.construct(config)
                ref_mappers = self.reference._binned.bin_mappers
                ref_bundle = self.reference._binned.bundle_info
            if BinnedDataset.is_binary_file(self.data):
                if ref_mappers is not None:
                    Log.fatal("A binary dataset cache carries its own bin "
                              "mappers and cannot be re-aligned to a "
                              "reference dataset; rebuild the cache from "
                              "the validation data instead")
                self._binned = BinnedDataset.load_binary(self.data)
            else:
                from .io.parser import parse_file
                X, label = parse_file(self.data)
                self._binned = BinnedDataset.from_matrix(
                    X, config, bin_mappers=ref_mappers,
                    reference_bundle=ref_bundle)
                if label is not None and self.label is None:
                    self.label = label
            md = self._binned.metadata
            if self.label is not None:
                md.set_label(np.asarray(self.label))
            if self.weight is not None:
                md.set_weight(self.weight)
            if self.init_score is not None:
                md.set_init_score(self.init_score)
            if self.group is not None:
                md.set_query(self.group)
            return self
        ref_mappers = None
        ref_bundle = None
        if self.reference is not None:
            self.reference.construct(config)
            ref_mappers = self.reference._binned.bin_mappers
            ref_bundle = self.reference._binned.bundle_info
        if _is_dataframe(self.data):
            ref_pc = (self.reference.pandas_categorical
                      if self.reference is not None else None)
            X, names, cat_idx, self.pandas_categorical = _data_from_pandas(
                self.data, self.feature_name, self.categorical_feature,
                ref_pc)
            fn = names
            cats: Sequence[int] = cat_idx
        else:
            X = _to_2d_float(self.data)
            fn = None if self.feature_name == "auto" \
                else list(self.feature_name)
            cats = ()
            if self.categorical_feature != "auto" and self.categorical_feature:
                cats = [int(c) for c in self.categorical_feature]
        self._binned = BinnedDataset.from_matrix(
            X, config, bin_mappers=ref_mappers, feature_names=fn,
            categorical_feature=cats, reference_bundle=ref_bundle)
        md = self._binned.metadata
        if self.label is not None:
            md.set_label(np.asarray(self.label))
        md.set_weight(self.weight)
        md.set_init_score(self.init_score)
        md.set_query(self.group)
        return self

    def _construct_stream(self, config: Config) -> "Dataset":
        """Construct from a StreamingDatasetBuilder or a chunk iterator
        (chunks: X, (X, y) or (X, y, w); see io/stream.py)."""
        from .io.stream import StreamingDatasetBuilder
        builder = self.data
        if not isinstance(builder, StreamingDatasetBuilder):
            it = builder
            builder = StreamingDatasetBuilder(params=self.params)
            for chunk in it:
                builder.push(chunk)
            self.data = builder
        ref_mappers = ref_bundle = None
        if self.reference is not None:
            self.reference.construct(config)
            ref_mappers = self.reference._binned.bin_mappers
            ref_bundle = self.reference._binned.bundle_info
        fn = None if self.feature_name == "auto" else list(self.feature_name)
        cats: Sequence[int] = ()
        if self.categorical_feature != "auto" and self.categorical_feature:
            cats = [int(c) for c in self.categorical_feature]
        self._binned = builder.finalize(
            config, bin_mappers=ref_mappers, reference_bundle=ref_bundle,
            feature_names=fn, categorical_feature=cats)
        if self.label is None:
            self.label = builder.labels()
        if self.weight is None:
            self.weight = builder.weights()
        md = self._binned.metadata
        if self.label is not None:
            md.set_label(np.asarray(self.label))
        md.set_weight(self.weight)
        md.set_init_score(self.init_score)
        md.set_query(self.group)
        return self

    def push_rows(self, data, start_row: int = -1) -> "Dataset":
        """Streaming row push (LGBM_DatasetPushRows): only valid on a
        Dataset whose data is a StreamingDatasetBuilder (created with one,
        or through LGBM_DatasetCreateByReference) and not yet
        constructed."""
        self._stream_builder().push_dense(np.asarray(data),
                                          start_row=start_row)
        return self

    def push_rows_csr(self, indptr, indices, values, num_col: int,
                      start_row: int = -1) -> "Dataset":
        """Streaming CSR push (LGBM_DatasetPushRowsByCSR)."""
        self._stream_builder().push_csr(indptr, indices, values, num_col,
                                        start_row=start_row)
        return self

    def _stream_builder(self):
        from .io.stream import StreamingDatasetBuilder
        if self._binned is not None:
            raise LightGBMError(
                "Cannot push rows after the dataset is constructed")
        if not isinstance(self.data, StreamingDatasetBuilder):
            raise LightGBMError(
                "push_rows needs a streaming Dataset: create it from a "
                "StreamingDatasetBuilder (or LGBM_DatasetCreateByReference)")
        return self.data

    @classmethod
    def _from_binned(cls, binned: BinnedDataset,
                     params: Optional[Dict] = None) -> "Dataset":
        """Wrap an already-binned dataset (GetSubset results, C-ABI
        plumbing) in the user-facing handle."""
        ds = cls(None, params=params)
        ds._binned = binned
        return ds

    @property
    def binned(self) -> BinnedDataset:
        if self._binned is None:
            self.construct()
        return self._binned

    def save_binary(self, filename: str) -> "Dataset":
        """Write the constructed dataset to a binary cache file that later
        Dataset(filename) calls load directly (reference save_binary)."""
        self.binned.save_binary(filename)
        return self

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params)

    # -- accessors (binding surface) -----------------------------------------
    def num_data(self) -> int:
        return self.binned.num_data

    def get_ref_chain(self, ref_limit: int = 100) -> set:
        """Chain of Dataset references: this dataset, its reference, its
        reference's reference, ... until ref_limit or a loop (basic.py
        get_ref_chain)."""
        head = self
        ref_chain: set = set()
        while len(ref_chain) < ref_limit:
            if isinstance(head, Dataset):
                ref_chain.add(head)
                if head.reference is not None and head.reference not in ref_chain:
                    head = head.reference
                else:
                    break
            else:
                break
        return ref_chain

    def num_feature(self) -> int:
        return self.binned.num_features

    def get_label(self) -> np.ndarray:
        return self.binned.metadata.label

    def get_weight(self):
        return self.binned.metadata.weight

    def get_group(self):
        qb = self.binned.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def set_label(self, label) -> None:
        self.label = label
        if self._binned is not None:
            self._binned.metadata.set_label(np.asarray(label))

    def set_weight(self, weight) -> None:
        self.weight = weight
        if self._binned is not None:
            self._binned.metadata.set_weight(weight)

    def set_group(self, group) -> None:
        self.group = group
        if self._binned is not None:
            self._binned.metadata.set_query(group)

    def set_init_score(self, init_score) -> None:
        self.init_score = init_score
        if self._binned is not None:
            self._binned.metadata.set_init_score(init_score)

    def get_init_score(self):
        return self.binned.metadata.init_score

    def get_field(self, field_name: str):
        """Generic field accessor (reference Dataset.get_field)."""
        if field_name == "label":
            return self.get_label()
        if field_name == "weight":
            return self.get_weight()
        if field_name == "init_score":
            return self.get_init_score()
        if field_name in ("group", "query"):
            return self.get_group()
        raise LightGBMError("Unknown field name: %s" % field_name)

    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name == "label":
            self.set_label(data)
        elif field_name == "weight":
            self.set_weight(data)
        elif field_name == "init_score":
            self.set_init_score(data)
        elif field_name in ("group", "query"):
            self.set_group(data)
        else:
            raise LightGBMError("Unknown field name: %s" % field_name)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if self._binned is not None and \
                categorical_feature != self.categorical_feature:
            raise LightGBMError(
                "Cannot change categorical_feature after the dataset is "
                "constructed; create a new Dataset")
        self.categorical_feature = categorical_feature
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        self.feature_name = feature_name
        if self._binned is not None and feature_name != "auto":
            if len(feature_name) != self._binned.num_features:
                raise LightGBMError(
                    "Length of feature names does not equal the number "
                    "of features")
            self._binned.feature_names = list(feature_name)
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self._binned is not None and self.reference is not reference:
            raise LightGBMError(
                "Cannot set reference after the dataset is constructed; "
                "create a new Dataset")
        self.reference = reference
        return self

    def subset(self, used_indices, params=None) -> "Dataset":
        idx = np.asarray(used_indices)
        from .io.stream import StreamingDatasetBuilder
        if self.data is None or isinstance(self.data, (str,
                                                       StreamingDatasetBuilder)) \
                or hasattr(self.data, "__next__"):
            # no raw matrix to re-bin (path-backed or streaming ingest):
            # gather the BINNED rows directly (reference GetSubset)
            self.construct()
            return Dataset._from_binned(
                self.binned.subset(np.sort(np.unique(idx))),
                params=params or self.params)
        X = _slice_rows(self.data, idx)
        y = None if self.label is None else np.asarray(self.label)[idx]
        w = None if self.weight is None else np.asarray(self.weight)[idx]
        return Dataset(X, label=y, weight=w, reference=self,
                       params=params or self.params)


class Booster:
    """Training/prediction handle (basic.py Booster; c_api.cpp Booster)."""

    def __init__(self, params: Optional[Dict] = None, train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None, model_str: Optional[str] = None,
                 init_model: Optional[GBDTModel] = None):
        params = dict(params) if params else {}
        self.params = params
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._valid_names: List[str] = ["training"]
        self._valid_data: List = []
        self._engine: Optional[GBDT] = None
        self._model: Optional[GBDTModel] = None
        self._objective = None
        self.config: Optional[Config] = None

        if train_set is not None:
            self.config = Config(params)
            self.config.warn_unimplemented()
            # reference-binding parity: a cluster config on the Booster
            # brings the network up (basic.py:1470 machines -> NetworkInit);
            # here that is jax.distributed over the same machine list
            from .parallel.launch import maybe_init_distributed
            maybe_init_distributed(self.config)
            train_set.construct(self.config)
            obj = self.config.objective
            self._objective = create_objective(obj, self.config) \
                if isinstance(obj, str) else None
            binned = train_set.binned
            if self._objective is not None and binned.metadata.label is None:
                Log.fatal("Label should not be None for training")
            metrics = create_metrics(self.config.metric, self.config)
            for m in metrics:
                m.init(binned.metadata.label, binned.metadata.weight,
                       binned.metadata.query_boundaries)
            self._engine = create_boosting(str(self.config.boosting), self.config,
                                           binned, self._objective, metrics,
                                           init_model=copy.deepcopy(init_model)
                                           if init_model is not None else None)
            self._model = self._engine.model
            self.train_set = train_set
            self.pandas_categorical = train_set.pandas_categorical
        elif model_file is not None or model_str is not None:
            text = model_str if model_str is not None else open(model_file).read()
            self.config = Config(params)
            self._load_from_string(text)
        else:
            raise LightGBMError("Booster needs train_set or model file")

    # -- pickling (reference basic.py Booster __getstate__/__setstate__:
    # serialize as the model string; the engine/device state is not portable)
    def __getstate__(self) -> Dict:
        self._drain()
        state = self.__dict__.copy()
        state.pop("_engine", None)
        state.pop("train_set", None)
        state.pop("_valid_data", None)  # holds full datasets via .reference
        state.pop("_objective", None)
        state.pop("_dev_predictor", None)   # holds device arrays
        state.pop("_dev_pred_key", None)
        if self._model is not None:
            state["_model_str"] = self._model.save_model_to_string()
        state.pop("_model", None)
        return state

    def __setstate__(self, state: Dict) -> None:
        model_str = state.pop("_model_str", None)
        self.__dict__.update(state)
        self._engine = None
        self.train_set = None
        self._valid_data = []
        if model_str is not None:
            pc = getattr(self, "pandas_categorical", None)
            self._load_from_string(model_str)
            if pc is not None:  # pickled attr wins (string has no line)
                self.pandas_categorical = pc
        else:
            self._model = None
            self._objective = None

    # -- training ------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if self._engine is None:
            raise LightGBMError("Cannot add validation data to a loaded Booster")
        # the reference MUST be attached BEFORE construct(): validation
        # bins are only meaningful against the TRAINING bin mappers (the
        # reference binding force-sets it in engine.train via
        # set_reference(train_set)).  A valid set already constructed
        # against different mappers is re-binned — scoring it would
        # traverse training split_bins over foreign bin ids.
        if data is self.train_set:
            # eval-on-train (cv eval_train_metric, add_valid(train_set)):
            # already binned with its own mappers BY DEFINITION — attaching
            # a self-reference would wipe the engine's binning and recurse
            pass
        elif data.reference is not self.train_set:
            if data._binned is not None:
                Log.warning("Validation set was constructed without "
                            "reference=train_set; re-binning with training "
                            "mappers")
                data._binned = None
            else:
                Log.warning("Validation set was not created with "
                            "reference=train_set; binning with training "
                            "mappers")
            data.reference = self.train_set
        data.construct(self.config)
        metrics = create_metrics(self.config.metric, self.config)
        self._engine.add_valid(name, data.binned, metrics)
        self._valid_names.append(name)
        self._valid_data.append((name, data))
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        if self._engine is None:
            raise LightGBMError("Cannot update a loaded Booster")
        from .runtime import resilience
        # fault-injection seam (LGBM_TPU_FAULT=die_at_iter:K /
        # sigterm_at_iter:K): the iteration boundary is where an abrupt
        # death or a preemption notice lands in testing
        resilience.maybe_die_or_preempt(self)
        self._model_version = getattr(self, "_model_version", 0) + 1
        guard = resilience.SentinelGuard(self._engine)
        try:
            # observability seam (ISSUE 9): one iteration's wall time,
            # the iteration counter, the per-iteration sync-audit gauges
            # and the LGBM_TPU_PROFILE training hook — here because this
            # is the one chokepoint EVERY boosting variant goes through
            from .runtime import telemetry
            with telemetry.train_iteration():
                if fobj is not None:
                    grad, hess = fobj(
                        self._engine.raw_train_score().reshape(-1),
                        self.train_set)
                    return self._engine.train_one_iter(grad, hess)
                return self._engine.train_one_iter()
        except resilience.NonFiniteDetected as e:
            # abort re-raises naming the iteration; rollback restores the
            # pre-iteration scores, drops the trees and reports finished
            return guard.handle(e, Log)

    def rollback_one_iter(self) -> "Booster":
        self._model_version = getattr(self, "_model_version", 0) + 1
        self._engine.rollback_one_iter()
        return self

    def _drain(self) -> None:
        """Flush the engine's async dispatch pipeline so model reads see
        every dispatched tree (no-op for loaded boosters and for an empty
        pipeline).  Every Booster entry point that observes the model
        object goes through here — `update()` may legitimately return
        with up to `pipeline_depth` tree assemblies still in flight."""
        if self._engine is not None:
            self._engine.flush()

    def current_iteration(self) -> int:
        """Number of completed iterations (reference Booster method)."""
        self._drain()
        return self._model.current_iteration

    def phase_timings(self):
        """Accumulated {phase: seconds} when tpu_profile_phases=true (the
        reference's TIMETAG counters); empty dict otherwise."""
        if self._engine is None:
            return {}
        return dict(self._engine.timer.seconds)

    # -- reference Booster surface parity ------------------------------------
    def num_model_per_iteration(self) -> int:
        return self._model.num_tree_per_iteration

    def num_feature(self) -> int:
        """Number of features the model was trained on (basic.py
        num_feature / LGBM_BoosterGetNumFeature)."""
        return self._model.max_feature_idx + 1

    def reset_parameter(self, params: Dict) -> "Booster":
        """Reset Booster parameters mid-training (basic.py reset_parameter
        -> Booster::ResetConfig): live-applied into the engine config so
        e.g. learning_rate / bagging_fraction changes take effect on the
        next iteration.  Engine-less (loaded) boosters update their
        prediction-time config."""
        if self._engine is not None:
            self._engine.reset_config(params)
        elif self.config is not None:
            self.config.set(params)
        self.params.update(params)
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        self._drain()
        return float(self._model.trees[tree_id].leaf_value[leaf_id])

    def attr(self, key: str):
        return getattr(self, "_attr", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        store = getattr(self, "_attr", None)
        if store is None:
            store = self._attr = {}
        for k, v in kwargs.items():
            if v is None:
                store.pop(k, None)
            elif isinstance(v, str):
                store[k] = v
            else:
                raise LightGBMError("Only string values are accepted")
        return self

    def _load_from_string(self, model_str: str) -> None:
        """The one load-from-string sequence shared by __init__,
        __setstate__ and model_from_string."""
        self._model = GBDTModel.load_model_from_string(model_str)
        self.pandas_categorical = _load_pandas_categorical(model_str)
        cfg = self.config if self.config is not None else Config({})
        self._objective = create_objective_from_model_string(
            self._model.objective_str, cfg)
        self._model_version = getattr(self, "_model_version", 0) + 1

    def model_from_string(self, model_str: str,
                          verbose: bool = True) -> "Booster":
        """Re-initialize from a model string (drops any training engine)."""
        self._engine = None
        self.train_set = None
        self._load_from_string(model_str)
        if verbose:
            Log.info("Finished loading model, total used %d iterations",
                     self._model.current_iteration)
        return self

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute tree order in [start, end) iterations
        (reference Booster.shuffle_models)."""
        self._drain()
        k = self._model.num_tree_per_iteration
        total = self._model.current_iteration
        end = total if end_iteration <= 0 else min(end_iteration, total)
        if not 0 <= start_iteration <= end:
            raise LightGBMError(
                "shuffle_models range [%d, %d) is invalid for a %d-iteration "
                "model" % (start_iteration, end, total))
        idx = np.arange(start_iteration, end)
        np.random.shuffle(idx)
        trees = self._model.trees
        blocks = [trees[i * k:(i + 1) * k] for i in range(total)]
        reordered = blocks[:start_iteration] + \
            [blocks[i] for i in idx] + blocks[end:]
        self._model.trees = [t for b in reordered for t in b]
        self._model_version = getattr(self, "_model_version", 0) + 1
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        self.train_set = None
        return self

    def free_network(self) -> "Booster":
        return self  # XLA owns transport; nothing to tear down

    def set_network(self, *args, **kwargs) -> "Booster":
        Log.warning("set_network is a no-op: XLA/ICI owns transport; "
                    "launch with jax.distributed for multi-host")
        return self

    def __copy__(self) -> "Booster":
        return self.__deepcopy__(None)

    def __deepcopy__(self, _) -> "Booster":
        return Booster(model_str=self.model_to_string())

    def num_trees(self) -> int:
        self._drain()
        return self._model.num_total_trees

    # -- evaluation ----------------------------------------------------------
    def eval(self, data: Dataset, name: str, feval=None) -> List:
        """Evaluate the current model on an arbitrary Dataset
        (reference Booster.eval)."""
        self._drain()
        data.construct(self.config)
        label = data.get_label()
        if isinstance(data.data, str):
            # path-backed Dataset: re-parse the raw matrix (construct()
            # keeps only the binned form)
            from .io.parser import parse_file
            X, _ = parse_file(data.data)
        else:
            X = _to_2d_float(data.data,
                             getattr(self, "pandas_categorical", None))
        raw = self._model.predict_raw(X).T                   # [K, N]
        metrics = create_metrics(self.config.metric, self.config) \
            if self.config else []
        out = []
        qb = data.binned.metadata.query_boundaries
        for m in metrics:
            m.init(label, data.get_weight(), qb)
            score = raw if getattr(m, "multiclass", False) else \
                (raw[0] if raw.shape[0] == 1 else raw.reshape(-1))
            out.append((name, m.name, float(m.eval(score, self._objective)),
                        m.is_higher_better))
        if feval is not None:
            preds = raw[0] if raw.shape[0] == 1 else raw.reshape(-1)
            mname, val, hib = feval(preds, data)
            out.append((name, mname, val, hib))
        return out

    def eval_train(self, feval=None) -> List:
        return self._wrap_eval(self._engine.eval_train(), feval, "training")

    def eval_valid(self, feval=None) -> List:
        out = self._wrap_eval(self._engine.eval_valid(), None, None)
        if feval is not None:
            # custom metric runs on every validation set too (engine.py
            # _agg_standard_result over all eval sets in the reference)
            for i, (name, ds) in enumerate(self._valid_data):
                raw = self._engine.raw_valid_score(i)
                preds = raw[0] if raw.shape[0] == 1 else raw.reshape(-1)
                mname, val, hib = feval(preds, ds)
                out.append((name, mname, val, hib))
        return out

    def eval_round(self, feval=None, include_train: bool = False):
        """One evaluation round — (train results, valid results) — off a
        SINGLE packed device fetch (engine.eval_all), so metric_freq=1
        doesn't pay one D2H round trip per dataset.  Used by the train()
        driver; eval_train/eval_valid keep the reference per-surface
        behavior for direct callers."""
        tr_res, va_res = self._engine.eval_all(include_train)
        train_out = self._wrap_eval(tr_res, feval, "training") \
            if include_train else []
        valid_out = self._wrap_eval(va_res, None, None)
        if feval is not None:
            for i, (name, ds) in enumerate(self._valid_data):
                raw = self._engine.raw_valid_score(i)
                preds = raw[0] if raw.shape[0] == 1 else raw.reshape(-1)
                mname, val, hib = feval(preds, ds)
                valid_out.append((name, mname, val, hib))
        return train_out, valid_out

    def _wrap_eval(self, results, feval, dataset_name):
        out = [(name, metric, val, hib) for (name, metric, val, hib) in results]
        if feval is not None:
            raw = self._engine.raw_train_score().reshape(-1) if dataset_name == "training" \
                else None
            if raw is not None:
                name, val, hib = feval(raw, self.train_set)
                out.append((dataset_name, name, val, hib))
        return out

    # -- prediction ----------------------------------------------------------
    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False, pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                device: bool = False, start_iteration: int = 0,
                out_dtype=None, leaf_quant: Optional[str] = None,
                **kwargs) -> np.ndarray:
        """device=True runs the jitted tree-parallel inference engine
        (models/device_predictor.py: f32 thresholds, categorical bitsets
        on device, shape-bucketed program cache, micro-batched transfer)
        instead of the exact f64 host traversal — the throughput path
        for large matrices.

        ISSUE 16 serving knobs (device path only): `out_dtype=
        np.float32` fetches and returns float32 — half the D2H bytes,
        and exactly the float64 answer `.astype(float32)` (output
        transforms still run in f64 on the exact upcast).  `leaf_quant=
        "int8"` opts into the int8-quantized leaf table; when the
        staged `device_predictor.LEAF_QUANT_VALIDATED` flag is ON it
        becomes the default (pass leaf_quant="none" to opt out)."""
        self._drain()
        X = _to_2d_float(data, getattr(self, "pandas_categorical", None))
        if pred_leaf:
            return self._model.predict_leaf_index(X, num_iteration)
        if pred_contrib:
            return self._model.predict_contrib(X, num_iteration)
        # shared NeedAccuratePrediction gating so host and device paths
        # truncate sums identically (gbdt_model.early_stop_mode)
        early = self._model.early_stop_mode(pred_early_stop)
        if device:
            from .models import device_predictor as dpr
            lq = leaf_quant
            if lq is None and dpr.LEAF_QUANT_VALIDATED:
                lq = "int8"            # staged default once validated
            if lq in ("none", "float32"):
                lq = None              # explicit opt-out of the staged flag
            end = self._model.num_prediction_iterations(start_iteration,
                                                        num_iteration)
            key = (start_iteration, end, len(self._model.trees),
                   getattr(self, "_model_version", 0), lq)
            if getattr(self, "_dev_pred_key", None) != key:
                self._dev_predictor = dpr.DevicePredictor(
                    self._model, start_iteration, num_iteration,
                    leaf_quant=lq)
                self._dev_pred_key = key
            raw = self._dev_predictor.predict_raw(
                X, early_stop=early,
                early_stop_freq=pred_early_stop_freq,
                early_stop_margin=pred_early_stop_margin,
                out_dtype=np.float32 if np.dtype(out_dtype or np.float64)
                == np.float32 else np.float64)
            return self._finish_predict(raw, raw_score, num_iteration,
                                        start_iteration)
        raw = self._model.predict_raw(X, start_iteration=start_iteration,
                                      num_iteration=num_iteration,
                                      early_stop=early,
                                      early_stop_freq=pred_early_stop_freq,
                                      early_stop_margin=pred_early_stop_margin)
        return self._finish_predict(raw, raw_score, num_iteration,
                                    start_iteration)

    def _finish_predict(self, raw: np.ndarray, raw_score: bool,
                        num_iteration: int = -1,
                        start_iteration: int = 0) -> np.ndarray:
        # f32 raw scores (ISSUE 16 out_dtype path): run the output
        # transform in f64 on the EXACT upcast, then downcast — so the
        # f32 surface equals the f64 surface .astype(float32), bit for
        # bit, and transform math never degrades
        f32 = raw.dtype == np.float32
        if f32:
            raw = raw.astype(np.float64)
        if raw.shape[1] == 1:
            raw = raw[:, 0]
        if raw_score:
            out = raw
        elif self._model.average_output:
            # averaged pre-converted outputs; no ConvertOutput on top
            # (gbdt_prediction.cpp Predict, average_output_ branch)
            out = raw / self._model.num_prediction_iterations(
                start_iteration, num_iteration)
        elif self._objective is None:
            out = raw
        else:
            out = self._objective.convert_output(raw)
        return out.astype(np.float32) if f32 else out

    def refit(self, data, label, weight=None, group=None,
              decay_rate: Optional[float] = None) -> "Booster":
        """Refit existing tree structures to new data (gbdt.cpp RefitTree
        :338-361 + FitByExistingTree, serial_tree_learner.cpp:223-248): keep
        every split, recompute leaf values from the new data's gradients with
        leaf_output = decay*old + (1-decay)*new*shrinkage, iterating so later
        trees see the refit scores of earlier ones."""
        import jax
        import jax.numpy as jnp

        if self._objective is None:
            raise LightGBMError("Cannot refit with a custom objective")
        self._drain()
        X = _to_2d_float(data, getattr(self, "pandas_categorical", None))
        label = np.asarray(label, dtype=np.float64).reshape(-1)
        n = X.shape[0]
        model = copy.deepcopy(self._model)
        cfg = self.config
        decay = float(cfg.refit_decay_rate) if decay_rate is None else float(decay_rate)
        l1, l2 = float(cfg.lambda_l1), float(cfg.lambda_l2)
        mds = float(cfg.max_delta_step)
        K = model.num_tree_per_iteration
        num_iters = model.current_iteration

        objective = create_objective(self.config.objective, self.config) \
            if isinstance(self.config.objective, str) else self._objective
        qb = None
        if group is not None:
            qb = np.concatenate([[0], np.cumsum(np.asarray(group, np.int64))])
        objective.init(label, weight, qb)
        leaf_pred = model.predict_leaf_index(X).astype(np.int64)   # [n, T]
        w_dev = jnp.asarray(np.ones(n, np.float32) if weight is None
                            else np.asarray(weight, np.float32))
        label_dev = jnp.asarray(label.astype(np.float32))
        scores = np.zeros((K, n), dtype=np.float64)

        from .runtime import syncs
        for it in range(num_iters):
            g, h = objective.get_gradients_multi(
                jnp.asarray(scores.astype(np.float32)), label_dev, w_dev)
            g, h = syncs.device_get((g, h), label="refit_fetch")
            g = np.asarray(g, np.float64)
            h = np.asarray(h, np.float64)
            for k in range(K):
                tree = model.trees[it * K + k]
                nl = tree.num_leaves
                leaves = leaf_pred[:, it * K + k]
                sum_g = np.bincount(leaves, weights=g[k], minlength=nl)[:nl]
                sum_h = np.bincount(leaves, weights=h[k], minlength=nl)[:nl] + 1e-15
                out = -np.sign(sum_g) * np.maximum(np.abs(sum_g) - l1, 0.0) / (sum_h + l2)
                if mds > 0.0:
                    out = np.clip(out, -mds, mds)
                tree.leaf_value[:nl] = decay * tree.leaf_value[:nl] + \
                    (1.0 - decay) * out * tree.shrinkage
                scores[k] += tree.leaf_value[leaves]
        new_booster = Booster(params=dict(self.params),
                              model_str=model.save_model_to_string())
        return new_booster

    # -- model IO ------------------------------------------------------------
    def _pandas_categorical_line(self) -> str:
        """The python-binding's trailing category-lists record (reference
        _save_pandas_categorical); empty when no category columns, so CLI
        byte-parity is kept for non-pandas models.  numpy scalars serialize
        as native numbers — stringified categories would never match an
        int/float categorical column again at load time."""
        if not getattr(self, "pandas_categorical", None):
            return ""
        import json as _json

        def _default(o):
            if hasattr(o, "item"):
                return o.item()
            return str(o)

        return "\npandas_categorical:%s\n" % _json.dumps(
            self.pandas_categorical, default=_default)

    def save_model(self, filename: str, num_iteration: int = -1,
                   start_iteration: int = 0) -> "Booster":
        self._drain()
        params = self.config.to_string() if self.config else ""
        self._model.save_model(filename, start_iteration, num_iteration,
                               parameters=params)
        line = self._pandas_categorical_line()
        if line:
            with open(filename, "a") as fh:
                fh.write(line)
        return self

    def model_to_string(self, num_iteration: int = -1, start_iteration: int = 0) -> str:
        self._drain()
        return self._model.save_model_to_string(start_iteration,
                                                num_iteration) + \
            self._pandas_categorical_line()

    def dump_model(self, num_iteration: int = -1) -> Dict:
        self._drain()
        return self._model.dump_model(num_iteration)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        self._drain()
        return self._model.feature_importance(iteration, importance_type)

    def feature_name(self) -> List[str]:
        return list(self._model.feature_names)

"""ML-Pipeline API layer (L4′) — the user-facing surface.

Parity target (SURVEY.md §1 L4, §2.1): the reference exposed Spark ML
``Transformer``/``Estimator`` subclasses (``DeepImageFeaturizer``,
``DeepImagePredictor``, ``KerasImageFileTransformer``, ``KerasTransformer``,
``TFImageTransformer``, ``TFTransformer``, ``KerasImageFileEstimator``).
This package rebuilds that surface on the in-repo engine with TPU-native
execution underneath (jitted Flax apply instead of TF sessions).
"""

# import_s of the start-up record: this package's first import, with what
# it pulls in (core/profiling.py; stdlib only, so it costs nothing itself)
from sparkdl_tpu.core import profiling as _profiling

_import_started = _profiling.import_begin()

from sparkdl_tpu.ml.base import (  # noqa: E402
    Estimator,
    Model,
    Pipeline,
    PipelineModel,
    Transformer,
)
from sparkdl_tpu.ml.classification import (  # noqa: E402
    LogisticRegression,
    LogisticRegressionModel,
)
from sparkdl_tpu.ml.estimator import KerasImageFileEstimator, KerasImageFileModel  # noqa: E402
from sparkdl_tpu.ml.feature import (  # noqa: E402
    Binarizer,
    Imputer,
    ImputerModel,
    IndexToString,
    MinMaxScaler,
    MinMaxScalerModel,
    Normalizer,
    OneHotEncoder,
    SQLTransformer,
    StandardScaler,
    StandardScalerModel,
    StringIndexer,
    StringIndexerModel,
    VectorAssembler,
)
from sparkdl_tpu.ml.regression import (  # noqa: E402
    LinearRegression,
    LinearRegressionModel,
)
from sparkdl_tpu.ml.evaluation import (  # noqa: E402
    BinaryClassificationEvaluator,
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from sparkdl_tpu.ml.tuning import (  # noqa: E402
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
    TrainValidationSplitModel,
)
from sparkdl_tpu.ml.image_transformer import TPUImageTransformer  # noqa: E402
from sparkdl_tpu.ml.keras_image import KerasImageFileTransformer  # noqa: E402
from sparkdl_tpu.ml.keras_tensor import KerasTransformer  # noqa: E402
from sparkdl_tpu.ml.named_image import DeepImageFeaturizer, DeepImagePredictor  # noqa: E402
from sparkdl_tpu.ml.named_sequence import DeepSequenceScorer  # noqa: E402
from sparkdl_tpu.ml.persistence import load  # noqa: E402
from sparkdl_tpu.ml.tensor_transformer import TPUTransformer  # noqa: E402

# Reference-compatible aliases: the reference's names execute TF graphs;
# here the payload is a ModelFunction, but the pipeline role is identical.
TFImageTransformer = TPUImageTransformer
TFTransformer = TPUTransformer

_profiling.import_end(_import_started)

__all__ = [
    "BinaryClassificationEvaluator",
    "CrossValidator",
    "CrossValidatorModel",
    "DeepImageFeaturizer",
    "DeepImagePredictor",
    "DeepSequenceScorer",
    "Estimator",
    "MulticlassClassificationEvaluator",
    "ParamGridBuilder",
    "RegressionEvaluator",
    "TrainValidationSplit",
    "TrainValidationSplitModel",
    "Binarizer",
    "Imputer",
    "ImputerModel",
    "Normalizer",
    "SQLTransformer",
    "IndexToString",
    "MinMaxScaler",
    "MinMaxScalerModel",
    "KerasImageFileEstimator",
    "KerasImageFileModel",
    "StringIndexer",
    "StringIndexerModel",
    "KerasImageFileTransformer",
    "KerasTransformer",
    "LinearRegression",
    "LinearRegressionModel",
    "LogisticRegression",
    "LogisticRegressionModel",
    "StandardScaler",
    "StandardScalerModel",
    "Model",
    "OneHotEncoder",
    "Pipeline",
    "load",
    "PipelineModel",
    "Transformer",
    "TPUImageTransformer",
    "TPUTransformer",
    "VectorAssembler",
    "TFImageTransformer",
    "TFTransformer",
]

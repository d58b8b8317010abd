"""ML-Pipeline API layer (L4′) — the user-facing surface.

Parity target (SURVEY.md §1 L4, §2.1): the reference exposed Spark ML
``Transformer``/``Estimator`` subclasses (``DeepImageFeaturizer``,
``DeepImagePredictor``, ``KerasImageFileTransformer``, ``KerasTransformer``,
``TFImageTransformer``, ``TFTransformer``, ``KerasImageFileEstimator``).
This package rebuilds that surface on the in-repo engine with TPU-native
execution underneath (jitted Flax apply instead of TF sessions).
"""

from sparkdl_tpu.ml.base import (
    Estimator,
    Model,
    Pipeline,
    PipelineModel,
    Transformer,
)
from sparkdl_tpu.ml.classification import (
    LogisticRegression,
    LogisticRegressionModel,
)
from sparkdl_tpu.ml.estimator import KerasImageFileEstimator, KerasImageFileModel
from sparkdl_tpu.ml.feature import (
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
from sparkdl_tpu.ml.regression import (
    LinearRegression,
    LinearRegressionModel,
)
from sparkdl_tpu.ml.evaluation import (
    BinaryClassificationEvaluator,
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from sparkdl_tpu.ml.tuning import (
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
    TrainValidationSplitModel,
)
from sparkdl_tpu.ml.image_transformer import TPUImageTransformer
from sparkdl_tpu.ml.keras_image import KerasImageFileTransformer
from sparkdl_tpu.ml.keras_tensor import KerasTransformer
from sparkdl_tpu.ml.named_image import DeepImageFeaturizer, DeepImagePredictor
from sparkdl_tpu.ml.named_sequence import DeepSequenceScorer
from sparkdl_tpu.ml.persistence import load
from sparkdl_tpu.ml.tensor_transformer import TPUTransformer

# Reference-compatible aliases: the reference's names execute TF graphs;
# here the payload is a ModelFunction, but the pipeline role is identical.
TFImageTransformer = TPUImageTransformer
TFTransformer = TPUTransformer

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

"""DeepSequenceScorer — a named sequence model over a token-id column: the
sequence analogue of ``DeepImageFeaturizer``.

``inputCol`` is a ``list<int32>`` column of fixed-length windows of token
ids. Every window is scored prefill-only — no cache, no generation — by the
named model (``models.registry.SEQUENCE_MODELS``) through ``TPUTransformer``
and the executor's choke point, like every other model, and two columns come
out per row: ``pooledCol``, the mean over positions of the final hidden
state (embedding extraction), and ``logprobsCol``, ``log p(x[t+1] | x[≤t])``
per position, the last 0 (perplexity filtering). ``expertCountsCol``, when
set, adds the tokens routed to each expert per expert layer, flattened; on a
model whose weights hold no expert layer it is refused where the model is
built.

The weights are a variables dict, taken as given: for a model of this size
they are made or loaded in bfloat16 on the device, and what a chip holds of
the published model — layers and their mixers (a gated short convolution,
attention or a state-space mixer) and ffns (a gated MLP or, optionally, an
expert layer), the leading ones where the config names a kind of attention
for each position, experts (``expertsHeld``), vocabulary slice — is read off
them
(``registry.build_sequence_scorer``).
"""

from __future__ import annotations

from typing import Optional, Sequence

from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.ml.tensor_transformer import TPUTransformer
from sparkdl_tpu.models import registry
from sparkdl_tpu.param.base import Param, keyword_only
from sparkdl_tpu.param.converters import TypeConverters
from sparkdl_tpu.param.shared_params import HasBatchSize, HasInputCol, HasMesh


class DeepSequenceScorer(Transformer, HasInputCol, HasBatchSize, HasMesh):
    """Named sequence model → ``pooled`` and ``logprobs`` columns."""

    modelName = Param(
        "DeepSequenceScorer", "modelName",
        f"one of {sorted(registry.SEQUENCE_MODELS)}, or a config of one's own "
        "of either sequence stack's type (LatentMoEConfig, or "
        "ShortConvMoEConfig: three mixers and an optional expert layer)",
        typeConverter=TypeConverters.identity)
    weights = Param(
        "DeepSequenceScorer", "weights",
        "the variables dict the model runs with (embed, layers, final_norm, "
        "head), as held on the device",
        typeConverter=TypeConverters.identity)
    expertsHeld = Param(
        "DeepSequenceScorer", "expertsHeld",
        "ids of the experts whose weights the expert layers hold (None: all)",
        typeConverter=TypeConverters.identity)
    window = Param("DeepSequenceScorer", "window",
                   "token ids per row", typeConverter=TypeConverters.toInt)
    pooledCol = Param("DeepSequenceScorer", "pooledCol",
                      "output column: mean final hidden state",
                      typeConverter=TypeConverters.toString)
    logprobsCol = Param("DeepSequenceScorer", "logprobsCol",
                        "output column: next-token log-probabilities",
                        typeConverter=TypeConverters.toString)
    expertCountsCol = Param(
        "DeepSequenceScorer", "expertCountsCol",
        "optional output column: tokens per expert layer and expert",
        typeConverter=TypeConverters.identity)

    @keyword_only
    def __init__(self, *, inputCol: Optional[str] = None,
                 pooledCol: str = "pooled", logprobsCol: str = "logprobs",
                 expertCountsCol: Optional[str] = None,
                 modelName=None, weights=None,
                 expertsHeld: Optional[Sequence[int]] = None,
                 window: Optional[int] = None, batchSize: int = 4,
                 mesh=None) -> None:
        super().__init__()
        self._setDefault(batchSize=4, pooledCol="pooled",
                         logprobsCol="logprobs", expertCountsCol=None,
                         expertsHeld=None)
        self._model = None      # (weights the model was built with, model)
        self.setParams(**self._input_kwargs)

    @keyword_only
    def setParams(self, *, inputCol: Optional[str] = None,
                  pooledCol: str = "pooled", logprobsCol: str = "logprobs",
                  expertCountsCol: Optional[str] = None,
                  modelName=None, weights=None,
                  expertsHeld: Optional[Sequence[int]] = None,
                  window: Optional[int] = None, batchSize: int = 4,
                  mesh=None) -> "DeepSequenceScorer":
        return self._set(**self._input_kwargs)

    def _model_function(self):
        weights = self.getOrDefault(self.weights)
        if self._model is None or self._model[0] is not weights:
            self._model = (weights, registry.build_sequence_scorer(
                self.getOrDefault(self.modelName), weights,
                self.getOrDefault(self.window),
                self.getOrDefault(self.expertsHeld)))
        return self._model[1]

    def copy(self, extra=None):
        # a paramMap copy keeps the built model (and its compiled program)
        that = super().copy(extra)
        that._model = self._model
        return that

    def _transform(self, dataset):
        outputs = {"pooled": self.getOrDefault(self.pooledCol),
                   "logprobs": self.getOrDefault(self.logprobsCol)}
        counts = self.getOrDefault(self.expertCountsCol)
        if counts:
            if not any("moe" in layer for layer in self.getOrDefault(
                    self.weights)["layers"]):
                raise ValueError(
                    f"expertCountsCol={counts!r}: the weights of "
                    f"{self.getOrDefault(self.modelName)!r} hold no expert "
                    "layer, so the model has no expert counts to give")
            outputs["expert_counts"] = counts
        return TPUTransformer(
            inputCol=self.getInputCol(), outputMapping=outputs,
            modelFunction=self._model_function(),
            batchSize=self.getBatchSize(), mesh=self.getMesh(),
        ).transform(dataset)

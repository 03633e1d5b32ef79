"""Flagship consumers: the sparse/dense linear learner, the factorization
machine (the libfm lane's canonical model), and the DPxSP transformer
(ring attention)."""

from dmlc_core_tpu.models.fm import (FMLearner, FMParams, FMSlots,  # noqa: F401
                                     FMState)
from dmlc_core_tpu.models.linear import LinearLearner  # noqa: F401
from dmlc_core_tpu.models.tp_transformer import (  # noqa: F401
    TPTransformerConfig, TPTransformerLM)
from dmlc_core_tpu.models.transformer import (TransformerConfig,  # noqa: F401
                                              TransformerLM)

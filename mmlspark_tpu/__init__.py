"""mmlspark_tpu — a TPU-native ML framework with the capabilities of
MMLSpark (bebr-msft/mmlspark), rebuilt on JAX/XLA/pjit/Pallas.

Importing the root package imports every stage module so the stage registry
(mmlspark_tpu.core.pipeline.STAGE_REGISTRY) is fully populated — the analog of
the reference's jar-reflection discovery (JarLoadingUtils.scala:18-60).
"""

__version__ = "0.1.0"

from . import core
from .core import (DataFrame, Estimator, Model, Pipeline, PipelineModel,
                   PipelineStage, Transformer)

# stage modules (populate the registry); extended as layers land
_STAGE_MODULES = [
    "mmlspark_tpu.stages",
    "mmlspark_tpu.ops",
    "mmlspark_tpu.models",
    "mmlspark_tpu.automl",
    "mmlspark_tpu.io",
    "mmlspark_tpu.parallel",
]

import importlib as _importlib

for _m in _STAGE_MODULES:
    _importlib.import_module(_m)

# one persistent compile cache for every entry point (fit, transform,
# serving): a config write, no backend is initialized by importing
from .parallel.distributed import configure_compile_cache as _cfg_cache

_cfg_cache()

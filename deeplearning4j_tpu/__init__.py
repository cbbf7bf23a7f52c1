"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up rebuild of the capabilities of Deeplearning4j (reference:
``jasonj99/deeplearning4j``; see ``SURVEY.md``) on JAX/XLA/Pallas/pjit:

- ``ops``       — ND4J-equivalent tensor layer: :class:`NDArray` facade over
                  ``jax.Array``, dtype rules, op library, counter-based RNG,
                  numpy serde.  (reference: nd4j/nd4j-backends/nd4j-api-parent/
                  nd4j-api — ``Nd4j``, ``INDArray``)
- ``learning``  — updaters/optimizers + schedules + regularization
                  (reference: org/nd4j/linalg/learning).
- ``nn``        — declarative config DSL + layer library
                  (reference: deeplearning4j-nn org/deeplearning4j/nn/conf).
- ``models``    — ``MultiLayerNetwork`` / ``ComputationGraph`` equivalents and
                  the model zoo, each compiling to a SINGLE fused XLA train
                  step instead of op-by-op JNI dispatch.
- ``datasets``  — DataSet/iterators/normalizers (reference: org/nd4j/linalg/
                  dataset + deeplearning4j-data).
- ``eval``      — evaluation suite (reference: org/nd4j/evaluation).
- ``optimize``  — training listeners (reference: org/deeplearning4j/optimize).
- ``parallel``  — device-mesh data/model parallelism over ICI via
                  ``jax.sharding`` (replaces ParallelWrapper / Spark
                  SharedTrainingMaster / Aeron mesh).
- ``autodiff``  — SameDiff-style define-by-graph API lowered through JAX
                  tracing; gradient-check utility.
- ``utils``     — model serialization (zip checkpoint format parity).
"""

__version__ = "0.1.0"

import jax as _jax

# ND4J supports DOUBLE end-to-end and its gradient checks are double-precision
# (SURVEY.md §4); JAX disables x64 by default.  Enable it — creation defaults
# stay float32 (see ops.dtype.default_float), so TPU hot paths are unaffected.
_jax.config.update("jax_enable_x64", True)

from deeplearning4j_tpu.ops import Nd4j, NDArray, DataType  # noqa: F401

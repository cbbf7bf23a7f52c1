"""Remote inference serving (reference: deeplearning4j-remote —
JsonModelServer / SameDiffJsonModelServer, SURVEY.md §2.5) plus the
serving tier (``serving.py``: bucketed warm executables for
single-step models, multi-model hosting, admission control) and the
iteration-level scheduler that serves language models
(``scheduler.py``: paged KV pool, admit/retire between decode steps,
token streaming, replica fan-out)."""
from deeplearning4j_tpu.remote.scheduler import (  # noqa: F401
    ContinuousBatcher, KVCachePool, ReplicaSet)
from deeplearning4j_tpu.remote.server import (  # noqa: F401
    JsonModelServer, JsonRemoteInference, SameDiffJsonModelServer)
from deeplearning4j_tpu.remote.serving import (  # noqa: F401
    AdmissionControl, BucketedExecutor, BucketLadder, ForwardServing,
    InferenceServer, ModelRegistry, ServiceOverloaded)

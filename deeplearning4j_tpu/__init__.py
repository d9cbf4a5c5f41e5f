"""deeplearning4j_tpu — a TPU-native deep learning framework.

A from-scratch JAX/XLA/Pallas re-realization of the capabilities of
Deeplearning4j 0.8.x (reference: seetharamireddy540/deeplearning4j).  Instead of the
reference's eager per-op JVM dispatch over libnd4j/cuDNN
(ref: deeplearning4j-nn/.../nn/multilayer/MultiLayerNetwork.java), every
training update step is traced once and compiled into a single XLA program,
parameters live in pytrees (with a flat-view adapter for checkpoint parity
with the reference's 1xN param row vector, ref: nn/api/Model.java:128),
and multi-device training is expressed as shardings over a
``jax.sharding.Mesh`` with XLA collectives instead of parameter averaging
over threads/Aeron/Spark (ref: parallelism/ParallelWrapper.java:218).
"""

__version__ = "0.1.0"

from deeplearning4j_tpu.ops import bucketing as _bucketing

_bucketing.configure_compile_cache()  # before anything here can compile

from deeplearning4j_tpu.nn.conf.network import (  # noqa: F401
    NeuralNetConfiguration,
    MultiLayerConfiguration,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: F401

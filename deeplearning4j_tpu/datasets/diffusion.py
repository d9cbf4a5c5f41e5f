"""The input side of block-diffusion training (Arriola et al. 2025,
arXiv:2503.09573; SDAR, arXiv:2510.06303): a seeded pre-processor that
turns a batch of clean token ids into what a block-diffusion model is
fitted on, on the host, where the input pipeline's phases time it.

For clean ids ``x0 [B, L]`` in blocks of ``block_length`` consecutive
tokens:

    per sequence and block j   t_j ~ U[t_min, 1]
    per token of block j       masked with probability t_j, independently
    x_t                        x0 with the masked tokens replaced by mask_id
    features  [x_t ; x0]       [B, 2L] int32, noised copy then clean copy
    labels    x0               [B, L]  int32
    labels mask (weights) w    [B, L]  float32: 1 / t_j on masked tokens, 0 elsewhere

which ``RnnOutputLayer(time_reduction="steps")`` scores as
``(1 / L) sum_i w_i CE_i``: the linear schedule's weighting with the
usual lower clip on t.  The noise of batch n is a function of ``(seed,
n)`` alone, n counted from the pre-processor's first batch: the same
seed gives the same batches, and a batch seen again is noised anew.
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DataSetIterator


class BlockDiffusionNoiser:
    """``pre_process(DataSet(features=x0 [B, L] ids))`` -> the DataSet
    above.  Its time a batch is the span ``pipeline/batch``, phase
    ``noise``."""

    def __init__(self, block_length: int, mask_id: int, seed: int,
                 t_min: float = 1e-3):
        if block_length < 1 or not 0.0 < t_min <= 1.0:
            raise ValueError(f"block_length={block_length}, t_min={t_min}")
        self.block_length, self.mask_id = int(block_length), int(mask_id)
        self.seed, self.t_min = int(seed), float(t_min)
        self.batches = 0

    def noise(self, x0: np.ndarray, n: int):
        """(features [B, 2L], labels [B, L], weights [B, L], t [B, L / b])
        of clean ids ``x0`` as batch number ``n``."""
        x0 = np.ascontiguousarray(x0, dtype=np.int32)
        B, L = x0.shape
        b = self.block_length
        if L % b:
            raise ValueError(f"sequences of {L} tokens are not a whole "
                             f"number of blocks of {b}")
        if (x0 == self.mask_id).any():
            raise ValueError(f"clean ids hold the mask id {self.mask_id}")
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     self.seed >> 32, n])
        t = rng.uniform(self.t_min, 1.0, (B, L // b))
        per_token = np.repeat(t, b, axis=1)
        masked = rng.random((B, L)) < per_token
        x_t = np.where(masked, np.int32(self.mask_id), x0)
        w = np.where(masked, 1.0 / per_token, 0.0).astype(np.float32)
        return np.concatenate([x_t, x0], axis=1), x0, w, t

    def pre_process(self, ds: DataSet) -> DataSet:
        with monitor.span("pipeline/batch", phase="noise"):
            features, labels, w, _ = self.noise(ds.features, self.batches)
            self.batches += 1
        return DataSet(features, labels, None, w)


class PreProcessingIterator(DataSetIterator):
    """``underlying``'s batches through ``pre_processor.pre_process``, one
    at a time and in order (the reference's
    ``DataSetIterator.setPreProcessor``): under ``fit()`` that is the
    input pipeline's feeder thread, ahead of the workers."""

    def __init__(self, underlying: DataSetIterator, pre_processor):
        self.underlying, self.pre_processor = underlying, pre_processor

    def next(self) -> DataSet:
        return self.pre_processor.pre_process(self.underlying.next())

    def has_next(self) -> bool:
        return self.underlying.has_next()

    def reset(self) -> None:
        self.underlying.reset()

    def batch_size(self) -> int:
        return self.underlying.batch_size()

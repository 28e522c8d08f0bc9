"""Data loading: staged dataset + per-iteration sharded batches.

TPU re-design of the reference's SingleDataLoader
(python/flexflow_dataloader.{h,cc,cu}, flexflow_cffi.py:2433): the
reference stages the entire dataset into zero-copy host memory once, then
per iteration an index-task copies each shard's batch slice to GPU
framebuffer. Here the dataset is staged once as a device array sharded
over the data axis (HBM-resident when it fits, host-resident otherwise),
and ``next_batch`` slices the staged array on device — no host→device
traffic in steady state, which is exactly the role the reference's
PY_DL_*_LOAD_BATCH_GPU tasks play.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def block_diffusion_batch(x0, block_length: int, mask_id: int, rng,
                          t_min: float = 1e-3):
    """Samples x0 [n, L] int -> (ids [n, 2L] int32, labels [n, L, 2]
    float32) of the block-diffusion objective, on the host.

    One noise level t a block of ``block_length`` tokens, uniform on
    [t_min, 1]; every token of the block becomes ``mask_id`` with
    probability t, independently. ``ids`` lays the noised copy before
    the clean one. ``labels[..., 0]`` is the clean token and
    ``labels[..., 1]`` its weight, 1/t of its block where the token was
    masked and 0 elsewhere: what
    ``LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY`` takes. ``rng``
    is a ``numpy.random.Generator`` the caller seeds."""
    x0 = np.asarray(x0)
    n, length = x0.shape
    if length % block_length:
        raise ValueError(f"block_diffusion_batch: {length} tokens are not "
                         f"whole blocks of {block_length}")
    if max(int(x0.max(initial=0)), mask_id) >= 1 << 24:
        raise ValueError("block_diffusion_batch: ids past 2^24 do not "
                         "survive the labels' float32")
    t = rng.uniform(t_min, 1.0, size=(n, length // block_length))
    t = np.repeat(t, block_length, axis=1)
    masked = rng.random((n, length)) < t
    ids = np.concatenate([np.where(masked, mask_id, x0), x0], axis=1)
    labels = np.stack([x0.astype(np.float32),
                       np.where(masked, 1.0 / t, 0.0).astype(np.float32)],
                      axis=-1)
    return ids.astype(np.int32), labels


class SingleDataLoader:
    """One input (or label) tensor's loader.

    ``num_samples`` must be a multiple of the batch size for the staged
    path (the reference truncates the same way).
    """

    def __init__(self, ffmodel, input_name: Optional[str], full_array,
                 batch_size: Optional[int] = None, stage_on_device: bool = True):
        self.ff = ffmodel
        self.input_name = input_name  # None => label loader
        arr = np.asarray(full_array)
        bs = batch_size or ffmodel.input_tensors[0].shape[0]
        self.batch_size = bs  # global batch
        # labels stage on the loss-boundary layout (data-sharded), inputs
        # on the executor's batch layout (pipe-sharded under the
        # pipeline's sharded microbatch queue) — same contract as
        # model._shard_batch, which stages the host-resident batches
        # below. What differs from `fit`: a loader never casts (both its
        # paths hand the step the dataset's own dtype)
        sharding = (ffmodel.executor.batch_sharding()
                    if input_name is not None
                    else ffmodel.executor.label_sharding())
        # multi-host: `full_array` is this process's dataset shard; each
        # batch consumes the local block of the global batch and the rows
        # assemble via make_array_from_process_local_data (host-resident —
        # the on-device staged path needs single-controller addressing)
        self._multihost = jax.process_count() > 1
        if self._multihost:
            from flexflow_tpu import distributed as _dist
            self._local_bs, _ = _dist.local_batch_rows(sharding, bs)
            stage_on_device = False
        else:
            self._local_bs = bs
        usable = (arr.shape[0] // self._local_bs) * self._local_bs
        if usable == 0:
            raise ValueError(
                f"dataset of {arr.shape[0]} samples < (local) batch size "
                f"{self._local_bs}")
        arr = arr[:usable]
        self.num_batches = usable // self._local_bs
        self.num_samples = self.num_batches * bs  # global count
        if self._multihost:
            # agree on num_batches ONCE, up front: unequal per-host dataset
            # shards would otherwise make ranks issue different numbers of
            # per-batch collectives and deadlock with no diagnostic
            # (ADVICE r5). One allgather at construction, zero steady-state
            # cost.
            from flexflow_tpu import distributed as _dist
            counts = _dist.allgather_value(self.num_batches)
            if len(set(counts)) != 1:
                raise ValueError(
                    f"multihost dataloader: per-host num_batches disagree "
                    f"{counts} (process {_dist.process_index()} computed "
                    f"{self.num_batches}) — every process must feed "
                    f"equal-length dataset shards; pad or truncate before "
                    f"constructing the loader")
        if stage_on_device:
            self.data = jax.device_put(jnp.asarray(arr), sharding)
        else:
            self.data = arr
        self._sharding = sharding
        self.next_index = 0

    def reset(self) -> None:
        self.next_index = 0

    def seek(self, batch_index: int) -> None:
        """Position the loader AT ``batch_index`` (0-based within the
        epoch) so the next ``next_batch`` returns that batch — the
        resume path's O(1) reposition, replacing fetch-and-discard of
        every checkpoint-covered batch."""
        b = int(batch_index)
        if not (0 <= b < self.num_batches):
            raise ValueError(
                f"seek({batch_index}) out of range for a loader with "
                f"{self.num_batches} batches per epoch")
        self.next_index = b * self._local_bs

    def next_batch(self, _ff=None):
        """Return the next batch, wrapping around (reference semantics:
        the C++ loader reloads from the start each epoch)."""
        n_local = self.num_batches * self._local_bs
        if self.next_index + self._local_bs > n_local:
            self.next_index = 0
        start = self.next_index
        self.next_index += self._local_bs
        if self._multihost:
            from flexflow_tpu import distributed as _dist
            return _dist.stage_local_batch(
                self.data[start:start + self._local_bs], self._sharding,
                global_rows=self.batch_size)
        if isinstance(self.data, np.ndarray):
            # the model's own staging: raw bytes straight onto the batch
            # sharding, shaped on the device
            return self.ff._shard_batch(
                self.data[start:start + self.batch_size],
                inputs=self.input_name is not None)
        return jax.lax.dynamic_slice_in_dim(self.data, start, self.batch_size,
                                            axis=0)


class DataLoaderSet:
    """All input + label loaders for a model; drives fit-style loops
    (the reference's ``dataloaders.next_batch`` list in fit,
    flexflow_cffi.py:2080)."""

    def __init__(self, ffmodel, xs: Sequence, y, batch_size: Optional[int] = None,
                 stage_on_device: bool = True):
        names = ffmodel.executor.input_names
        xs = xs if isinstance(xs, (list, tuple)) else [xs]
        if len(xs) != len(names):
            raise ValueError(f"model has {len(names)} inputs, got {len(xs)}")
        self.input_loaders = [
            SingleDataLoader(ffmodel, n, x, batch_size, stage_on_device)
            for n, x in zip(names, xs)
        ]
        self.label_loader = SingleDataLoader(ffmodel, None, y, batch_size,
                                             stage_on_device)
        counts = {l.num_samples for l in self.input_loaders + [self.label_loader]}
        if len(counts) != 1:
            raise ValueError(
                f"input/label loaders disagree on usable sample count "
                f"{sorted(counts)} — all arrays must have the same length")
        self.ff = ffmodel

    @property
    def num_batches(self) -> int:
        return self.input_loaders[0].num_batches

    def reset(self) -> None:
        for l in self.input_loaders:
            l.reset()
        self.label_loader.reset()

    def seek(self, batch_index: int) -> None:
        """Reposition every loader at ``batch_index`` within the epoch
        (fit_loader's resume seam)."""
        for l in self.input_loaders:
            l.seek(batch_index)
        self.label_loader.seek(batch_index)

    def next_batch(self):
        inputs = {l.input_name: l.next_batch() for l in self.input_loaders}
        labels = self.label_loader.next_batch()
        return inputs, labels


def create_data_loaders(ffmodel, x, y, batch_size: Optional[int] = None,
                        stage_on_device: bool = True) -> DataLoaderSet:
    """Sugar matching ffmodel.create_data_loader (flexflow_cffi.py:2178)."""
    return DataLoaderSet(ffmodel, x, y, batch_size, stage_on_device)

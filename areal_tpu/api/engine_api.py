"""Abstract train/inference engine contracts.

Role of reference areal/api/engine_api.py:39-227: algorithms talk to these
interfaces, never to device code directly, so FSDP↔Megatron (reference) or
single-host↔pod SPMD (here) swaps are config changes.
"""

import abc
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from areal_tpu.api.io_struct import (
    FinetuneSpec,
    ModelRequest,
    ModelResponse,
    SaveLoadMeta,
    WeightUpdateMeta,
)


class TrainEngine(abc.ABC):
    """A sharded train state + jitted update functions on a device mesh
    (reference engine_api.py:39 `TrainEngine`)."""

    def initialize(self, ft_spec: Optional[FinetuneSpec] = None):
        raise NotImplementedError()

    def destroy(self):
        pass

    def train(self, mode: bool = True):
        return self

    @property
    def data_parallel_rank(self) -> int:
        raise NotImplementedError()

    @property
    def data_parallel_world_size(self) -> int:
        raise NotImplementedError()

    def is_data_parallel_head(self) -> bool:
        return self.data_parallel_rank == 0

    def current_data_parallel_head(self) -> int:
        return 0

    def get_version(self) -> int:
        raise NotImplementedError()

    def set_version(self, version: int):
        raise NotImplementedError()

    def save(self, meta: SaveLoadMeta):
        raise NotImplementedError()

    def load(self, meta: SaveLoadMeta):
        raise NotImplementedError()

    def upload_weights(self, meta: WeightUpdateMeta):
        """Push current weights to inference engines."""
        raise NotImplementedError()

    def train_batch(
        self,
        input_: Dict[str, Any],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> Dict[str, float]:
        raise NotImplementedError()

    def train_batches(
        self,
        inputs: Iterable[Dict[str, Any]],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> List[Dict[str, float]]:
        """``train_batch`` over the minibatches of a step, in order. An
        engine may draw and dispatch a later one before it has the stats of
        an earlier one; every one's are there when this returns."""
        return [
            self.train_batch(input_, loss_fn, loss_weight_fn)
            for input_ in inputs
        ]

    def eval_batch(
        self,
        input_: Dict[str, Any],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> Dict[str, float]:
        raise NotImplementedError()

    def forward(
        self,
        input_: Dict[str, Any],
        post_hook: Optional[Callable] = None,
        rows: Optional[Sequence[int]] = None,
    ):
        """A per-token result over ``input_`` ([B, L], the input's order);
        ``rows``: over those sequences only, the others read 0."""
        raise NotImplementedError()


class InferenceEngine(abc.ABC):
    """Rollout-side contract (reference engine_api.py:158)."""

    def initialize(self, *args, **kwargs):
        raise NotImplementedError()

    def destroy(self):
        pass

    async def agenerate(self, req: ModelRequest) -> ModelResponse:
        raise NotImplementedError()

    def update_weights(self, meta: WeightUpdateMeta):
        raise NotImplementedError()

    def get_version(self) -> int:
        raise NotImplementedError()

    def set_version(self, version: int):
        raise NotImplementedError()

    def submit(self, data: Dict[str, Any], workflow) -> bool:
        """Queue one episode; False when refused (quarantined sample)."""
        raise NotImplementedError()

    def wait(self, count: int, timeout: Optional[float] = None):
        raise NotImplementedError()

    def rollout_batch(self, data: List[Dict[str, Any]], workflow):
        raise NotImplementedError()

    def prepare_batch(self, dataloader, workflow):
        raise NotImplementedError()

    def pause(self):
        """Pause issuing new requests (weight update window)."""
        raise NotImplementedError()

    def resume(self):
        raise NotImplementedError()

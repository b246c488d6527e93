"""Streaming data-plane execution (reference: ray.data's streaming
executor, _internal/execution/streaming_executor.py — the Dataset layer
of the Ray paper, arXiv:1712.05889, with the "keep the chips busy"
discipline of arXiv:2011.03641).

`Dataset.iter_batches` and `DatasetPipeline.iter_batches` ride this.
"""
from ray_tpu.data._internal.streaming.executor import (  # noqa: F401
    StreamingExecutor,
    last_executor,
    prefetch_budget,
)

"""Serving substrate: request-level dedup, dynamic batching, response cache."""

from .cache import ResponseCache
from .engine import ServeSession
from .frontend import (DEFAULT_BUCKETS, MicroBatchExecutor, ServeFrontend,
                       ServeResult, VERDICT_OK, VERDICT_RETRY,
                       replay_schedule, verdict_digest)

__all__ = [
    "ServeSession", "ResponseCache", "MicroBatchExecutor", "ServeFrontend",
    "ServeResult", "DEFAULT_BUCKETS", "VERDICT_OK", "VERDICT_RETRY",
    "replay_schedule", "verdict_digest",
]

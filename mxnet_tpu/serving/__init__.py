"""Continuous-batching inference serving on the compiled artifact cache.

The production serving path between a single ``predict.Predictor`` call and
millions-of-users traffic (ROADMAP item 3; the capability the reference
covers with c_predict_api + the model-server ecosystem, rebuilt TPU-native
around fixed-shape XLA artifacts, arXiv:1810.09868):

  - **ModelRegistry / RegisteredModel** (`registry.py`) — exported
    symbol+params load once; each configured batch bucket (e.g. 1/8/64)
    eagerly acquires a compiled artifact through the process-wide engine
    cache under pinned ``("predict", graph_fp, config_fingerprint)`` keys,
    warm-started from ``JAX_COMPILATION_CACHE_DIR`` so a restarted
    replica does not recompile.
  - **ContinuousBatcher** (`batcher.py`) — thread-safe request queue with
    continuous batch formation: requests aggregate into the smallest
    covering bucket, padded rows are sliced back per request, a
    ``max_wait_ms`` deadline bounds p99, and a ``DispatchWindow`` keeps K
    batches in flight (explicit ``device_put`` feeding, no host sync on
    the dispatch path).
  - **Server** (`server.py`) — multi-model front door: in-process
    ``submit()/result()`` futures plus a stdlib HTTP JSON API and the
    Prometheus ``/metrics`` endpoint.

SLO observability rides the unified telemetry layer: request-latency
histograms on ``telemetry.DEFAULT_LATENCY_BUCKETS`` (p50/p99 from the
cumulative ``_bucket`` exposition), queue depth, batch occupancy, and
per-model throughput — see docs/serving.md and docs/observability.md.

Like ``mxnet_tpu.predict``, this package stays off the training stack: it
imports only the symbolic core, the engine, and telemetry.
"""
from __future__ import annotations

from .registry import ModelRegistry, RegisteredModel
from .batcher import (ContinuousBatcher, DeadlineExceeded, PRIORITIES,
                      ServerOverloaded, ServingFuture)
from .server import Server

__all__ = ["ModelRegistry", "RegisteredModel", "ContinuousBatcher",
           "ServingFuture", "Server", "ServerOverloaded",
           "DeadlineExceeded", "PRIORITIES"]

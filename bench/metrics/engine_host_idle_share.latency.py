"""``engine_host_idle_share`` (``bench/metrics/engine_host_idle_share.py``) in the cells that report
``latency_p50_s`` and not ``itl_p99_ms``: one quantity, named by the
end-to-end metric it moves there."""

from bench.metrics.engine_host_idle_share import read  # noqa: F401

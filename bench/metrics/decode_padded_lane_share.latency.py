"""``decode_padded_lane_share`` (``bench/metrics/decode_padded_lane_share.py``) in the cells that report
``latency_p50_s`` and not ``itl_p99_ms``: one quantity, named by the
end-to-end metric it moves there."""

from bench.metrics.decode_padded_lane_share import read  # noqa: F401

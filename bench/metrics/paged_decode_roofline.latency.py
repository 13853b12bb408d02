"""``paged_decode_roofline`` (``bench/metrics/paged_decode_roofline.py``) in the cells that report
``latency_p50_s`` and not ``itl_p99_ms``: one quantity, named by the
end-to-end metric it moves there."""

from bench.metrics.paged_decode_roofline import read  # noqa: F401

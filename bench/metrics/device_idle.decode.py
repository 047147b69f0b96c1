"""``device_idle`` in the decode cells, where it moves ``tpot_p95_ms``: the
share of the traced window in which no op ran on the device."""

from bench.metrics.device_idle import read  # noqa: F401

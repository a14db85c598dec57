"""``prefill_ms`` as defined there, in the bursty cells, where it moves
``itl_p95_ms.burst``."""

from harness.registry import metric_reader

read = metric_reader("prefill_ms").read

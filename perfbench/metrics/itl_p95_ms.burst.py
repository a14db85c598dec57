"""``itl_p95_ms`` as defined there, under a name and bound of its own for
the bursty cells, whose gaps spread far less than the Poisson chat cell's."""

from harness.registry import metric_reader

read = metric_reader("itl_p95_ms").read

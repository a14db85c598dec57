"""The benchmark's own machinery: discovery of cells by name, traffic
generation, counters of work, the table of peaks, the comparison with the
plain references, and the reduction from a profiler trace to device
metrics.  Only ``sparse_head`` (and the runners) touch the program under
test."""

"""The benchmark's own yardstick: loading, traffic, weights, costs, peaks
and the reduction of a profiler trace to metrics."""

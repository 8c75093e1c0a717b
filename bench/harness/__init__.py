"""The benchmark's own machinery: traffic, weights, counts, trace reduction,
the correctness comparison and the per-metric registry. Nothing here is
imported by the program under test."""

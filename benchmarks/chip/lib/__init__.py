"""The benchmark's yardstick: peaks, counts, generators, references, trace reduction."""

"""The benchmark's yardstick: loaders, traffic, arithmetic, reference, trace
reduction.  Nothing here is imported by the program under test."""

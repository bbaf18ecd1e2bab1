"""The benchmark's own yardstick: generator, driver, reducer, reference, checks."""

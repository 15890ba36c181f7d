"""The benchmark's harness: lookup by name, traffic, the engine binding, trace reduction, checks."""

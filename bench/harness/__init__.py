"""The chip benchmark's harness: spec loading, traffic, weights, the
engine driver, the plain reference, trace reduction and work counts."""

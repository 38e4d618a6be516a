"""The benchmark's own yardstick: traffic, weights, the plain reference, the
reduction from traces and spans to metrics, the peaks and the arithmetic of
needed work. Nothing here is imported by the program under test."""

"""repro: Sawtooth Wavefront Reordering as a first-class feature of a
JAX/TPU training+serving framework. See DESIGN.md."""

__version__ = "1.0.0"

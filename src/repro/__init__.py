"""repro: SASG (sparse + adaptive stochastic gradient) distributed-training
framework in JAX. See DESIGN.md for the system inventory."""
__version__ = "0.1.0"

"""repro: DistCLUB (Fast Distributed Bandits for Online Recommendation
Systems) as a production-grade JAX/TPU framework."""
from .launch import compile_events

__version__ = "1.0.0"

compile_events.install()

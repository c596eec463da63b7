"""Bismarck-JAX: a unified IGD architecture for analytics + LM training.

JAX reproduction and TPU-scale extension of
"Towards a Unified Architecture for in-RDBMS Analytics" (Feng, Kumar,
Recht, Ré; 2012).
"""

__version__ = "1.0.0"

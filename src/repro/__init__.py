"""repro: FAVOR (filter-agnostic vector ANNS) as a filtered vector-search
service in JAX/Pallas."""
__version__ = "1.1.0"

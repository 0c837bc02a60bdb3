"""Parallelism tier: mesh/sharding specs, collectives, ring attention,
pipeline and multi-host glue."""

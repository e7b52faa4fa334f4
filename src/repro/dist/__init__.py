"""Distributed substrate: sharding specs, mesh context, checkpointing,
pipeline parallelism, the stacked partition probe and the cluster tier."""

"""Reproduction of "Over-the-Air Computation Aided Federated Learning with
the Aggregation of Normalized Gradient" as a production-scale jax system.
"""

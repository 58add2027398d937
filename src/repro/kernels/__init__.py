"""Rank-and-select match kernels: the Pallas kernel, its op wrappers, and a
plain-jnp reference."""

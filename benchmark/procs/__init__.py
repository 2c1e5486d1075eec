"""Helper processes of the open-loop cells; they never import JAX."""

"""Plain references of the configurations' forward passes, and the
comparison of the served engine with them."""

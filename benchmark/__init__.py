"""The benchmark: the yardstick every later PR is measured with.

Nothing here imports JAX at import time: the load generator runs as a
child process that must never touch the chip.
"""

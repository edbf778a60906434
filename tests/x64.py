"""64-bit scope for the oracle-equivalence tests.

Inside ``enable_x64()`` the batched router runs in float64, so its
latencies can be compared bit for bit with the scalar Python oracle
(``core/router.py``), which computes in Python floats.
"""
import jax


def enable_x64():
    """Context manager that turns on 64-bit types for its block."""
    return jax.enable_x64(True)

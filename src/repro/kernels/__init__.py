"""Pallas TPU kernels for the compute hot spots: blockwise flash attention
and fused RMSNorm.  Each kernel ships with a jit wrapper (ops.py) and a
pure-jnp oracle (ref.py).  The ops.py wrappers interpret a kernel when
the program is lowered for the CPU and compile it through Mosaic
otherwise."""
from . import ops, ref  # noqa: F401

# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax
import jax.numpy as jnp


def mxu_precision(dtype):
    """Precision for a Pallas kernel's `jnp.dot`: full f32 products for f32
    operands, which Mosaic otherwise rounds to bf16 (2e-3 relative error
    on a v5e); narrower operands keep the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

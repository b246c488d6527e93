"""One product on the MXU, its operands rounded to the compute dtype, and
the same product to float32 accuracy for the callers that need it."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def einsum(eq, x, w, out_dtype, *, cd, three_pass):
    """einsum(x, w) on the MXU, operands rounded to `cd`. `three_pass` adds,
    to the FORWARD value only, the two products with the operands' rounding
    errors (x_hi·w_lo + x_lo·w_hi, the `lo` parts themselves in `cd`): the
    result is the float32 product to ~2^-16. The backward pass is the
    single product's, as without it — unless a checkpoint has to rebuild
    the forward value first, which costs the three passes again: a layer's
    products go through `models.layers.core.project`, which names the result
    so that `layers.remat` keeps it; a caller here keeps nothing."""
    hi_x, hi_w = x.astype(cd), w.astype(cd)
    out = jnp.einsum(eq, hi_x, hi_w, preferred_element_type=out_dtype)
    if not three_pass:
        return out

    def lo(a):
        # `reduce_precision`, not a cast there and back: the compiler may
        # drop such a pair (`xla_allow_excess_precision`), and with it `lo`
        info = jnp.finfo(cd)
        a = a.astype(jnp.float32)
        return (a - jax.lax.reduce_precision(a, info.nexp, info.nmant)
                ).astype(cd)

    more = jnp.einsum(eq, hi_x, lo(w), preferred_element_type=jnp.float32)
    if x.dtype != cd:
        more += jnp.einsum(eq, lo(x), hi_w,
                           preferred_element_type=jnp.float32)
    return (out.astype(jnp.float32)
            + jax.lax.stop_gradient(more)).astype(out.dtype)

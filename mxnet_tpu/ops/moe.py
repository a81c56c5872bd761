"""The dropless held-experts layer (`parallel/moe.py: held_moe_ffn`) as an
op, so that a `HybridBlock` on the normal path can call it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

# what the op's second output holds, in order
HELD_REPORT = ("kept", "max_load", "mean_load", "exact")


@register("_contrib_held_moe_ffn", multi_output=True)
def held_moe_ffn_op(x, router_w, w_gate_up, w_down, *, top_k,
                    published_experts, first_held=0, scaling=1.0):
    """x (..., D) -> (the same shape, float32 (4,)): the part of a top-k
    expert layer that the experts held here give (`held_moe_ffn` has the
    equations), and the call's report in the order of `HELD_REPORT`:
    assignments kept here, the largest and the mean load of a held expert,
    and 1 where the exact dense path ran. The report is a device value; the
    block that calls the op keeps it as state (`HeldExpertsFFN.routing`)."""
    from ..parallel import moe
    y, aux = moe.held_moe_ffn(
        x.reshape(-1, x.shape[-1]), router_w, w_gate_up, w_down, top_k=top_k,
        published_experts=published_experts, first_held=first_held,
        scaling=scaling, return_aux=True)
    report = jnp.stack([aux[k].astype(jnp.float32) for k in HELD_REPORT])
    return y.reshape(x.shape), jax.lax.stop_gradient(report)

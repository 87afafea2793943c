"""Pure-jnp oracle for the segment_aggregate kernel."""

import jax.numpy as jnp


def segment_aggregate_ref(keys, slots, vals, acc):
    # out-of-range keys or slots (either side) are dead lanes, exactly as
    # the Pallas kernel drops them — the backends must never diverge (a
    # negative slot would otherwise wrap to the last slot).
    k, s = acc.shape[:2]
    ok = (keys >= 0) & (keys < k) & (slots >= 0) & (slots < s)
    safe_k = jnp.clip(keys, 0, k - 1)
    upd = jnp.where(ok[:, None], vals, 0.0)
    return acc.at[safe_k, slots].add(upd, mode="drop")

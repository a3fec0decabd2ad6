"""Pure-jnp oracle for the SCAR schedule-evaluation kernel."""
from __future__ import annotations

import jax.numpy as jnp

NEG = -1e30


def scar_eval_ref(lat_tab, e_tab, layer_cls, seg_id, comm_lat, comm_e,
                  seg_valid, pipe):
    """``kernel.scar_eval`` semantics, candidate-major, as dense one-hots."""
    C = lat_tab.shape[1]
    S = comm_lat.shape[0]
    cls_oh = (layer_cls[..., None] == jnp.arange(C)).astype(jnp.float32)
    seg_oh = (seg_id[..., None] == jnp.arange(S)).astype(jnp.float32)
    lat_layer = jnp.einsum("lbc,lc->lb", cls_oh, lat_tab)
    e_layer = jnp.einsum("lbc,lc->lb", cls_oh, e_tab)
    seg_lat = jnp.einsum("lb,lbs->sb", lat_layer, seg_oh) + comm_lat
    seg_e = (jnp.einsum("lb,lbs->sb", e_layer, seg_oh) + comm_e) * seg_valid
    lat_max = jnp.max(jnp.where(seg_valid > 0, seg_lat, NEG), axis=0)
    lat_sum = jnp.sum(seg_lat * seg_valid, axis=0)
    n_seg = seg_valid.sum(axis=0)
    p = pipe[0] * (n_seg > 1)
    lat = jnp.where(p > 0, lat_max, lat_sum)
    return jnp.stack([lat, seg_e.sum(axis=0)])

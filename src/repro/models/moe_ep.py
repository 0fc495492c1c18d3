"""Expert-parallel MoE dispatch via shard_map (the hillclimbed path).

The GSPMD baseline (repro.models.moe) expresses dispatch as a global gather
``x[table]`` over a token-sharded operand; the partitioner resolves it by
all-gathering the token buffer per layer (observed: arctic-480b train_4k is
collective-bound, t_coll ~ 97 s/step, with 'involuntary full
rematerialization' warnings).

This implementation exploits the layout we already chose: activations are
replicated over 'model' and experts are sharded over 'model' — so every
model-shard can gather ITS experts' tokens from its local token slice with
ZERO dispatch communication; the only collective left is the (T_local, d)
psum that merges expert contributions (which Megatron-TP pays anyway).

Trade-off vs the baseline (documented): capacity is enforced PER DATA SHARD
(C_local = ceil(k * T_local / E * cf)), the standard EP approximation; with
a generous capacity factor the two implementations agree exactly
(tests/test_moe_ep.py).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.moe import capacity


def _ep_local(xl, rw, up, gate, down, *, cfg, model_axis: str,
              batch_axes: Tuple[str, ...], dtype):
    m = cfg.moe
    B_l, S, d = xl.shape
    T = B_l * S
    E = m.num_experts
    E_l = up.shape[0]
    K = m.top_k
    midx = jax.lax.axis_index(model_axis)
    xf = xl.reshape(T, d)

    # router (fp32), identical on every model shard (x replicated there)
    logits = xf.astype(jnp.float32) @ rw.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32).sum(1), axis=0)
    aux_loss = E * jnp.sum(me * ce) / K
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    if batch_axes:
        aux_loss = jax.lax.pmean(aux_loss, batch_axes)
        z_loss = jax.lax.pmean(z_loss, batch_axes)

    # --- dispatch restricted to MY experts (zero communication) -----------
    lo = midx * E_l
    flat_e = gate_idx.reshape(-1)
    flat_w = gate_w.reshape(-1).astype(dtype)
    local_e = flat_e - lo
    mine = (local_e >= 0) & (local_e < E_l)
    local_e = jnp.where(mine, local_e, E_l)              # E_l = drop bucket
    C = capacity(m, T)
    sort_idx = jnp.argsort(local_e, stable=True)
    sorted_e = local_e[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E_l), side="left")
    pos = jnp.arange(T * K) - seg_start[jnp.minimum(sorted_e, E_l - 1)]
    keep = (sorted_e < E_l) & (pos < C)
    slot = jnp.where(keep, sorted_e * C + pos, E_l * C)
    table = jnp.full((E_l * C + 1,), T * K, jnp.int32)
    table = table.at[slot].set(sort_idx.astype(jnp.int32), mode="drop")
    table = table[: E_l * C].reshape(E_l, C)

    # OOB-fill gathers / OOB-drop scatter, mirroring repro.models.moe
    # (no pad-row concats; sentinel slots read zeros, scatter nowhere)
    tok_of = table // K
    w_of = jnp.take(flat_w, table, axis=0, mode="fill", fill_value=0)
    gx = jnp.take(xf.astype(dtype), tok_of, axis=0, mode="fill",
                  fill_value=0)                          # (E_l, C, d) LOCAL

    up_h = jnp.einsum("ecd,edf->ecf", gx, up.astype(dtype))
    if gate is not None:
        up_h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", gx, gate.astype(dtype))) * up_h
    else:
        up_h = jax.nn.gelu(up_h)
    out_e = jnp.einsum("ecf,efd->ecd", up_h, down.astype(dtype))

    out = jnp.zeros((T, d), dtype)
    out = out.at[tok_of].add(out_e * w_of[..., None], mode="drop")
    # merge expert contributions across the model axis (the ONLY collective)
    out = jax.lax.psum(out, model_axis)
    return out.reshape(B_l, S, d), aux_loss, z_loss


def moe_ffn_ep(p, cfg, x, dtype, mesh: Mesh):
    """shard_map expert-parallel MoE.  x (B, S, d) -> (B, S, d), aux dict."""
    from repro.distributed.sharding import fit_batch_axes

    b_axes = fit_batch_axes(mesh, x.shape[0])
    bspec = (b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None))
    gate = p.get("gate")

    fn = functools.partial(_ep_local, cfg=cfg, model_axis="model",
                           batch_axes=b_axes, dtype=dtype)
    gate_spec = P("model", None, None) if gate is not None else None
    args = (x, p["router"]["w"], p["up"], gate, p["down"])
    in_specs = (P(bspec, None, None), P(None, None),
                P("model", None, None), gate_spec, P("model", None, None))
    if gate is None:
        fn2 = lambda xl, rw, up, down: fn(xl, rw, up, None, down)
        args = (x, p["router"]["w"], p["up"], p["down"])
        in_specs = (P(bspec, None, None), P(None, None),
                    P("model", None, None), P("model", None, None))
    else:
        fn2 = fn
    out, aux, z = jax.shard_map(
        fn2, mesh=mesh, in_specs=in_specs,
        out_specs=(P(bspec, None, None), P(), P()), check_vma=False)(*args)
    return out, {"moe_aux": aux, "moe_z": z}

"""Mixture-of-Experts FFN with capacity-based sort dispatch.

Sort-based dispatch (argsort token-slots by expert, scatter into a fixed
(E, C, d) buffer) keeps memory at E*C*d instead of the T*E*C one-hot blowup,
and the (E, C) buffer shards cleanly over the 'model' mesh axis (expert
parallelism); GSPMD inserts the token all-to-all at the data->expert sharding
boundary. Tokens beyond capacity are dropped (standard capacity semantics);
the router aux loss keeps the load balanced so drops stay rare.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.common import activation


def capacity(T: int, moe: MoEConfig) -> int:
    c = int(moe.capacity_factor * T * moe.top_k / moe.num_experts)
    return max(8, -(-c // 8) * 8)                       # round up to 8


def route(x, router_w, moe: MoEConfig):
    """x: (T, d) -> gates (T, k), expert ids (T, k), aux loss."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, moe.top_k)        # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss.
    me = probs.mean(axis=0)                                         # (E,)
    ce = jnp.zeros((moe.num_experts,)).at[idx.reshape(-1)].add(1.0) \
        / (idx.size)
    aux = moe.num_experts * jnp.sum(me * ce) * moe.aux_loss_coef
    return gates, idx, aux


def dispatch_combine(x, gates, idx, moe: MoEConfig, expert_fn,
                     n_buckets: int = 0, cap: int = 0):
    """Run expert_fn over a capacity-bounded (E, C, d) buffer.

    x: (T, d); gates/idx: (T, k); expert_fn: (E, C, d) -> (E, C, d_out).
    n_buckets/cap override the bucket count and per-bucket capacity (used by
    the shard_map dispatch where the last bucket is a drop bucket).
    """
    T, d = x.shape
    k, E = moe.top_k, n_buckets or moe.num_experts
    C = cap or capacity(T, moe)

    slot_expert = idx.reshape(T * k)                    # (T*k,)
    slot_token = jnp.repeat(jnp.arange(T), k)
    slot_gate = gates.reshape(T * k)

    order = jnp.argsort(slot_expert, stable=True)       # group by expert
    se, st, sg = slot_expert[order], slot_token[order], slot_gate[order]
    # position within expert group = rank - first_rank_of_expert
    ranks = jnp.arange(T * k, dtype=jnp.int32)
    group_start = jnp.full((E,), T * k, jnp.int32).at[se].min(ranks)
    pos = ranks - group_start[se]
    keep = pos < C

    buf = jnp.zeros((E, C, d), dtype=x.dtype)
    buf = buf.at[jnp.where(keep, se, E - 1),
                 jnp.where(keep, pos, C - 1)].add(
        jnp.where(keep[:, None], x[st], 0).astype(x.dtype))

    import os as _os
    if _os.environ.get("REPRO_MOE_SHARD_CAP", "0") == "1":
        # shard the capacity dim over the data axes too: the (E, C, d) buffer
        # otherwise replicates over 'data' and blows temp memory (§Perf)
        from repro.distributed.ctx import shard
        buf = shard(buf, "experts", "batch", None)

    out_buf = expert_fn(buf)                            # (E, C, d_out)

    gathered = out_buf[se, jnp.minimum(pos, C - 1)]     # (T*k, d_out)
    gathered = jnp.where(keep[:, None], gathered, 0)
    out = jnp.zeros((T, out_buf.shape[-1]), dtype=jnp.float32)
    out = out.at[st].add(gathered.astype(jnp.float32) * sg[:, None])
    return out.astype(x.dtype)


def moe_ffn(x, p, moe: MoEConfig, act_name: str = "silu", act_tau=None):
    """x: (T, d). p: {'router': (d,E), 'w_gate','w_up': (E,d,f), 'w_down': (E,f,d),
    optional 'shared_*' dense expert}."""
    from repro.models.common import act_clip
    act = activation(act_name)
    gates, idx, aux = route(x, p["router"], moe)

    import os as _os
    if _os.environ.get("REPRO_MOE_SHARDMAP", "0") == "1":
        y = _shard_map_dispatch(act_clip(x, act_tau), gates, idx, p, moe,
                                act, act_tau)
        if y is not None:
            if "shared_w_gate" in p:
                h = act(x @ p["shared_w_gate"]) * (x @ p["shared_w_up"])
                y = y + act_clip(h, act_tau) @ p["shared_w_down"]
            return y, aux

    def experts(buf):                                   # (E, C, d)
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        h = act_clip(h, act_tau)
        return jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    y = dispatch_combine(act_clip(x, act_tau), gates, idx, moe, experts)
    if "shared_w_gate" in p:
        h = act(x @ p["shared_w_gate"]) * (x @ p["shared_w_up"])
        y = y + act_clip(h, act_tau) @ p["shared_w_down"]
    return y, aux


def _shard_map_dispatch(x, gates, idx, p, moe: MoEConfig, act, act_tau):
    """Expert-parallel dispatch without the GSPMD scatter blow-up (§Perf).

    Activations are replicated over the 'model' axis (batch shards over
    'data'), so each model column can *locally* select the tokens routed to
    its own E/n experts — no token all-to-all exists in this layout at all.
    GSPMD cannot see that from a global scatter (it replicates the (E, C, d)
    buffer; measured 14.7 TB/device of all-gather on deepseek-v3 train), so
    the dispatch is expressed explicitly with shard_map:
      * expert weights arrive ('model', fsdp)-sharded; the fsdp dim is
        all-gathered inside (the ordinary FSDP cost),
      * tokens with experts outside the column fall into a drop bucket,
      * partial outputs psum over 'model' (the same collective a dense TP
        FFN pays).
    Returns None when the layout does not apply (no ctx / E % model != 0).
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import ctx as _ctx

    c = _ctx.current()
    if c is None or "model" not in c.mesh.axis_names:
        return None
    n_model = dict(zip(c.mesh.axis_names,
                       c.mesh.devices.shape)).get("model", 1)
    E = moe.num_experts
    if n_model <= 1 or E % n_model:
        return None
    dp = tuple(a for a in ("pod", "data") if a in c.mesh.axis_names)
    T, d = x.shape
    ndp = 1
    for a in dp:
        ndp *= dict(zip(c.mesh.axis_names, c.mesh.devices.shape))[a]
    if T % ndp:
        return None
    E_loc = E // n_model
    T_loc = T // ndp
    C = capacity_for(T_loc, moe)

    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    fsdp_w = dp if (dp and wg.shape[1] % ndp == 0) else ()
    wspec_in = P("model", fsdp_w if fsdp_w else None, None)
    wdspec_in = P("model", None, fsdp_w if fsdp_w else None)

    def body(x_l, g_l, i_l, wg_l, wu_l, wd_l):
        j = jax.lax.axis_index("model")
        if fsdp_w:
            wg_l = jax.lax.all_gather(wg_l, fsdp_w, axis=1, tiled=True)
            wu_l = jax.lax.all_gather(wu_l, fsdp_w, axis=1, tiled=True)
            wd_l = jax.lax.all_gather(wd_l, fsdp_w, axis=2, tiled=True)
        il = i_l - j * E_loc
        valid = (il >= 0) & (il < E_loc)
        il = jnp.where(valid, il, E_loc)              # drop bucket
        gl = jnp.where(valid, g_l, 0.0)

        def experts(buf):                              # (E_loc+1, C, d)
            h = act(jnp.einsum("ecd,edf->ecf", buf[:E_loc], wg_l)) * \
                jnp.einsum("ecd,edf->ecf", buf[:E_loc], wu_l)
            from repro.models.common import act_clip as _ac
            h = _ac(h, act_tau)
            out = jnp.einsum("ecf,efd->ecd", h, wd_l)
            return jnp.concatenate(
                [out, jnp.zeros((1,) + out.shape[1:], out.dtype)], axis=0)

        y_part = dispatch_combine(x_l, gl, il, moe, experts,
                                  n_buckets=E_loc + 1, cap=C)
        return jax.lax.psum(y_part, "model")

    xspec = P(dp if dp else None, None)
    return jax.shard_map(
        body, mesh=c.mesh,
        in_specs=(xspec, xspec, xspec, wspec_in, wspec_in, wdspec_in),
        out_specs=xspec, check_vma=False)(x, gates, idx, wg, wu, wd)


def capacity_for(T_local: int, moe: MoEConfig) -> int:
    c = int(moe.capacity_factor * T_local * moe.top_k / moe.num_experts)
    return max(8, -(-c // 8) * 8)

"""Plain reference of a Zamba2 hybrid's logits.

Written from transformers' ``modeling_zamba2.py`` (``Zamba2MambaMixer``'s
torch path, ``Zamba2MambaDecoderLayer``, ``Zamba2HybridLayer``,
``Zamba2AttentionDecoderLayer``, ``Zamba2Attention``, ``Zamba2MLP``,
``Zamba2RMSNormGated``). For one sequence:

  h = embed[tokens] (not scaled); e = h
  for layer i: if i is site j of ``hybrid_layer_ids``, shared block
    b = j % num_mem_blocks runs, with no residual of its own:
      x = RMSNorm(concat(h, e));  q, k, v = x Wq, x Wk, x Wv
      rotary positions on q and k (rotate-half, base ``rope_theta``, over
      ``attention_head_dim``); causal softmax at (head_dim / 2)^-0.5
      o = attn Wo;  x = RMSNorm(o);  [g | u] = x Wgu + (x A_j) B_j
      t = ((gelu(g) * u) Wdown) Wlin_j      (exact GELU; site j's A, B, lin)
    else t = 0; then
      x = RMSNorm(h + t);  [z | xBC | dt] = x Win
      xBC = silu(depthwise causal conv(xBC) + bias)   (width ``mamba_d_conv``)
      dt = max(softplus(dt + dt_bias), time_step_min); A = -exp(A_log)
      per head h (B and C of group h // (heads / ngroups)):
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
      y = RMSNorm_per_group(y * silu(z)) (groups of d_inner / ngroups,
      eps 1e-5);  h = h + y Wout
  logits = RMSNorm(h) embed^T  (the head is tied)

Float32 at ``highest`` matmul precision, weights upcast from their stored
dtype a layer at a time inside the scans. The recurrence is stepped over
the positions one by one, with no chunking and no cache. For the control
every matmul operand is rounded to float8 (e4m3), as in
``reference/qwen3.py``; the recurrence stays float32. Imports nothing of
the program.

Departures from ``modeling_zamba2.py``: dt is clamped below at
``time_step_min``, as its torch path does in prefill and decode; its fused
CUDA path would apply ``time_step_limit`` instead, which is null here. The
recurrence is sequential where the torch path's prefill chunks it (the
same sums in another order). Every intermediate stays float32 where the
published code casts some back to the weights' dtype. The attention
adapters of ``use_shared_attention_adapter`` are absent (false for
Zamba2-7B; the family refuses a file that sets it).

``params(cfg, key)`` gives the seeded weights in the layout the program's
entry points take (``repro.models.ssm``), in the file's ``torch_dtype``
(bfloat16 for the chip: no float32 copy of the whole model is made),
in one jitted call, leaf by leaf. A_log and dt_bias are drawn as
``Zamba2PreTrainedModel._init_weights`` draws them (A = 1..heads; dt
log-uniform in [time_step_min, time_step_max], floored at
``time_step_floor``), so the recurrence neither dies nor explodes over a
long context.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    G, N = cfg["mamba_ngroups"], cfg["mamba_d_state"]
    return {"d": d, "d_in": d_in, "H": cfg["n_mamba_heads"],
            "P": cfg["mamba_headdim"], "N": N, "G": G,
            "K": cfg["mamba_d_conv"], "C": d_in + 2 * G * N,
            "f": cfg["intermediate_size"], "r": cfg["adapter_rank"],
            "L": cfg["num_hidden_layers"], "M": cfg["num_mem_blocks"],
            "sites": len(cfg["hybrid_layer_ids"]),
            "AH": cfg["num_attention_heads"],
            "AKV": cfg["num_key_value_heads"],
            "hd": cfg["attention_head_dim"], "V": cfg["vocab_size"]}


# --------------------------------------------------------------- weights
def params(cfg: dict, key):
    """Seeded weights in the program's layout and the file's dtype:
    LeCun-scaled projections, norm scales near 1, embedding at 0.02, conv
    taps at 1/sqrt(width), A_log and dt_bias as published."""
    m = _dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, d_in, H, C, f, r = m["d"], m["d_in"], m["H"], m["C"], m["f"], m["r"]
    L, M, n, AH, AKV, hd = m["L"], m["M"], m["sites"], m["AH"], m["AKV"], \
        m["hd"]

    def make(key):
        k = iter(jax.random.split(key, 32))

        def normal(shape, std):
            return (jax.random.normal(next(k), shape, F32) * std).astype(dtype)

        def norm(shape):
            return (1.0 + jax.random.normal(next(k), shape, F32) * 0.1
                    ).astype(dtype)

        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(next(k), (L, H), F32)
                                 * (hi - lo) + lo), cfg["time_step_floor"])
        p = {
            "embed": normal((m["V"], d), 0.02),
            "final_norm": norm((d,)),
            "mamba": {
                "ln": norm((L, d)),
                "in_proj": normal((L, d, d_in + C + H), d ** -0.5),
                "conv_w": normal((L, m["K"], C), m["K"] ** -0.5),
                "conv_b": normal((L, C), 0.1),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, H + 1, dtype=F32)), (L, H)
                ).astype(dtype),
                "D": jnp.ones((L, H), dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "out_norm": norm((L, d_in)),
                "out_proj": normal((L, d_in, d), d_in ** -0.5),
            },
            "shared": {
                "ln1": norm((M, 2 * d)),
                "attn": {"wq": normal((M, 2 * d, AH * hd), (2 * d) ** -0.5),
                         "wk": normal((M, 2 * d, AKV * hd), (2 * d) ** -0.5),
                         "wv": normal((M, 2 * d, AKV * hd), (2 * d) ** -0.5),
                         "wo": normal((M, AH * hd, d), (AH * hd) ** -0.5)},
                "ln2": norm((M, d)),
                "w_gate_up": normal((M, d, 2 * f), d ** -0.5),
                "w_down": normal((M, f, d), f ** -0.5),
            },
            "sites": {"adapter_a": normal((n, d, r), d ** -0.5),
                      "adapter_b": normal((n, r, 2 * f), r ** -0.5),
                      "linear": normal((n, d, d), d ** -0.5)},
        }
        if not cfg["tie_word_embeddings"]:
            p["lm_head"] = normal((d, m["V"]), d ** -0.5)
        return p

    return jax.jit(make)(key)


# ------------------------------------------------------------- reference
def _fp8(a, per_row):
    axes = -1 if per_row else None
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axes, keepdims=per_row),
                        1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); position of row s is s."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def make_logits(cfg: dict, low_precision: bool = False):
    """Returns a jitted ``logits(params, tokens (S,), first, count)`` that
    gives the (count, vocab) logits predicting positions ``first + 1`` to
    ``first + count`` (static ``count``)."""
    m = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, P, N, G, K = m["H"], m["P"], m["N"], m["G"], m["K"]
    AH, AKV, hd = m["AH"], m["AKV"], m["hd"]
    ids = list(cfg["hybrid_layer_ids"])
    tied = bool(cfg["tie_word_embeddings"])

    if low_precision:
        def mm(x, w):
            return jnp.matmul(_fp8(x, True), _fp8(w.astype(F32), False),
                              precision=HIGHEST)

        def act(a):
            return _fp8(a, True)
    else:
        def mm(x, w):
            return jnp.matmul(x, w.astype(F32), precision=HIGHEST)

        def act(a):
            return a

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)

    def mamba(carry, p):
        h, t = carry
        p = f32(p)
        S = h.shape[0]
        x = _rms(h + t, p["ln"], eps)
        zxbcdt = mm(x, p["in_proj"])
        z, xbc, dt = (zxbcdt[:, :m["d_in"]],
                      zxbcdt[:, m["d_in"]:m["d_in"] + m["C"]],
                      zxbcdt[:, m["d_in"] + m["C"]:])
        xp = jnp.concatenate([jnp.zeros((K - 1, m["C"]), F32), xbc])
        xbc = jax.nn.silu(sum(xp[i:i + S] * p["conv_w"][i] for i in range(K))
                          + p["conv_b"])
        xs = xbc[:, :m["d_in"]].reshape(S, H, P)
        Bm = jnp.repeat(xbc[:, m["d_in"]:m["d_in"] + G * N]
                        .reshape(S, G, N), H // G, axis=1)          # (S,H,N)
        Cm = jnp.repeat(xbc[:, m["d_in"] + G * N:].reshape(S, G, N),
                        H // G, axis=1)
        dt = jnp.maximum(jax.nn.softplus(dt + p["dt_bias"]),
                         cfg["time_step_min"])                      # (S,H)
        A = -jnp.exp(p["A_log"])

        def step(st, inp):                  # st: (H, P, N)
            dt_t, x_t, B_t, C_t = inp
            st = jnp.exp(dt_t * A)[:, None, None] * st \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
            return st, jnp.einsum("hpn,hn->hp", st, C_t, precision=HIGHEST)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                            (dt, xs, Bm, Cm))
        y = (y + p["D"][:, None] * xs).reshape(S, m["d_in"])
        g = (y * jax.nn.silu(z)).reshape(S, G, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + 1e-5)
        y = g.reshape(S, m["d_in"]) * p["out_norm"]
        return (h + mm(y, p["out_proj"]), jnp.zeros_like(t)), None

    def shared(h, e, blk, site):
        blk, site = f32(blk), f32(site)
        S = h.shape[0]
        a = blk["attn"]
        x = _rms(jnp.concatenate([h, e], axis=-1), blk["ln1"], eps)
        q = _rope(mm(x, a["wq"]).reshape(S, AH, hd), theta)
        k = _rope(mm(x, a["wk"]).reshape(S, AKV, hd), theta)
        v = mm(x, a["wv"]).reshape(S, AKV, hd)
        q = q.reshape(S, AKV, AH // AKV, hd)
        s = jnp.einsum("skgd,tkd->kgst", act(q), act(k),
                       precision=HIGHEST) * (hd / 2) ** -0.5
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        vt = jnp.swapaxes(v, 0, 2)                          # (hd, KV, S)
        o = jnp.einsum("kgst,dkt->skgd", act(pr), act(vt), precision=HIGHEST)
        x = _rms(mm(o.reshape(S, AH * hd), a["wo"]), blk["ln2"], eps)
        gu = mm(x, blk["w_gate_up"]) \
            + mm(mm(x, site["adapter_a"]), site["adapter_b"])
        g, u = gu[:, :m["f"]], gu[:, m["f"]:]
        y = mm(jax.nn.gelu(g, approximate=False) * u, blk["w_down"])
        return mm(y, site["linear"])

    cuts = sorted({0, m["L"], *ids})

    @functools.partial(jax.jit, static_argnums=(3,))
    def logits(params, tokens, first, count):
        h = params["embed"][tokens].astype(F32)
        e = h
        for lo, hi in zip(cuts, cuts[1:]):
            t = jnp.zeros_like(h)
            if lo in ids:
                j = ids.index(lo)
                t = shared(h, e,
                           jax.tree_util.tree_map(
                               lambda a: a[j % m["M"]], params["shared"]),
                           jax.tree_util.tree_map(lambda a: a[j],
                                                  params["sites"]))
            layers = jax.tree_util.tree_map(lambda a: a[lo:hi],
                                            params["mamba"])
            (h, _), _ = jax.lax.scan(mamba, (h, t), layers)
        h = jax.lax.dynamic_slice_in_dim(h, first, count, axis=0)
        h = _rms(h, params["final_norm"].astype(F32), eps)
        head = params["embed"].T if tied else params["lm_head"]
        return mm(h, head)

    return logits

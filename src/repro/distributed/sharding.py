"""Parameter / state / batch sharding rules, rank-polymorphic in axis names.

Strategy (2D "hybrid FSDP x TP", extended by a pure-DP 'pod' axis):
  * TP ('model'): attention heads, FFN hidden, vocab, experts.
  * FSDP ('pod','data'): the non-TP matrix dimension of every large weight,
    plus optimizer moments — ZeRO-3-style, parameters are all-gathered on use
    by GSPMD and gradients reduce-scattered.
  * Activations: batch over ('pod','data'); heads/ff/vocab over 'model'.

Rules are *patterns over flattened param paths*, so one table covers every
architecture in the pool. Dims that do not divide the axis size fall back to
replication for that dim (GSPMD would pad; we prefer predictable layouts).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (regex over path, spec template) — template entries name *logical* axes:
#   "tp" -> 'model';  "fsdp" -> ('pod','data');  None -> replicated
# Templates are right-aligned to the array rank (leading dims replicated), so
# stacked-layer arrays (leading L) need no special casing.
RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # embeddings / heads
    (r"embed$", ("tp", "fsdp")),
    (r"(lm_head|unembed)$", ("fsdp", "tp")),
    (r"(enc_pos|dec_pos)$", (None, None)),
    # attention (GQA + cross): column-parallel in, row-parallel out
    (r"attn/w[qkv]$|cross/w[qkv]$", ("fsdp", "tp")),
    (r"attn/wo$|cross/wo$", ("tp", "fsdp")),
    (r"attn/b[qkv]$", ("tp",)),
    # MLA
    (r"wq_a$|wkv_a$", ("fsdp", None)),
    (r"wq_b$|wkv_b$", (None, "tp")),
    # dense FFN
    (r"ffn/w_gate$|ffn/w_up$|shared_w_gate$|shared_w_up$", ("fsdp", "tp")),
    (r"ffn/w_down$|shared_w_down$", ("tp", "fsdp")),
    # MoE experts: shard experts when divisible (checked at apply time),
    # otherwise shard the hidden dim
    (r"ffn/(w_gate|w_up)$", ("experts", "fsdp", "tp")),      # 4D case (L,E,d,f)
    (r"ffn/w_down$", ("experts", "tp", "fsdp")),             # 4D case (L,E,f,d)
    (r"router$", ("fsdp", None)),
    # rwkv
    (r"blocks/(wr|wk|wv|wg)$", ("fsdp", "tp")),
    (r"blocks/wo$", ("tp", "fsdp")),
    (r"cm_wk$", ("fsdp", "tp")),
    (r"cm_wv$", ("tp", "fsdp")),
    (r"cm_wr$", ("fsdp", "tp")),
    (r"mix_w1$", ("fsdp", None)),
    (r"mix_w2$", (None, None, "fsdp")),
    (r"decay_a$", ("fsdp", None)),
    (r"decay_b$", (None, "fsdp")),
    # mamba
    (r"in_proj$", ("fsdp", "tp")),
    (r"out_proj$", ("tp", "fsdp")),
    (r"conv_w$", (None, "tp")),
    (r"conv_b$", ("tp",)),
    (r"(A_log|D|dt_bias)$", ("tp",)),
    (r"out_norm$", ("tp",)),
    # zamba2 shared blocks (their attention matches the rules above) and
    # each site's adapter and linear
    (r"shared/w_gate_up$", ("fsdp", "tp")),
    (r"shared/w_down$", ("tp", "fsdp")),
    (r"sites/adapter_a$", ("fsdp", None)),
    (r"sites/adapter_b$", (None, "tp")),
    (r"sites/linear$", ("fsdp", "tp")),
    # mtp
    (r"mtp/proj$", ("fsdp", "tp")),
    # cnn
    (r"/w$", (None, None, None, "tp")),
    (r"/w1$", (None, "tp")),
    (r"/w2$", ("tp", None)),
)


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, a) for a in name]))
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _resolve(mesh: Mesh, logical: Optional[str], no_fsdp: bool = False):
    if logical is None:
        return None
    if logical in ("tp", "experts"):
        return "model" if "model" in mesh.axis_names else None
    if logical == "fsdp":
        if no_fsdp:
            return None
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return axes if axes else None
    return logical if logical in mesh.axis_names else None


def spec_for(mesh: Mesh, path: str, shape: Tuple[int, ...],
             no_fsdp: bool = False) -> P:
    """Right-align the first matching rule template; drop non-divisible axes."""
    ndim = len(shape)
    for pat, template in RULES:
        if not re.search(pat, path):
            continue
        if len(template) > ndim:
            continue
        # 4D expert rule must not hijack 3D dense ffn (and vice versa): take
        # the first template whose length <= ndim AND which, right-aligned,
        # divides. Expert rules are listed after dense so 3D matches dense.
        axes = [None] * (ndim - len(template)) + list(template)
        spec = []
        for dim, logical in zip(shape, axes):
            phys = _resolve(mesh, logical, no_fsdp)
            if phys is None or dim % _axis_size(mesh, phys) != 0:
                spec.append(None)
            else:
                spec.append(phys)
        # avoid duplicate mesh axes in one spec (illegal): keep first use
        used = set()
        clean = []
        for s in spec:
            flat = s if isinstance(s, tuple) else (s,) if s else ()
            if any(a in used for a in flat):
                clean.append(None)
            else:
                used.update(flat)
                clean.append(s)
        return P(*clean)
    return P(*([None] * ndim))


def _moe_aware_path_fix(path: str, shape) -> str:
    return path


def param_specs(mesh: Mesh, params_shape: Any, *, no_fsdp: bool = False,
                embed_tp: bool = False) -> Any:
    """Pytree of PartitionSpec matching a (possibly eval_shape'd) params tree.

    no_fsdp: replicate the data axes (TP-only / pure-DP) — serving layouts
    and small models where per-step weight all-gathers dominate (§Perf).
    embed_tp: shard the embedding table on d_model over 'model' instead of
    vocab — avoids GSPMD's replicate-fallback on the token gather (§Perf).
    """
    from repro.core.pruning import _flatten, _unflatten
    from repro.train.optimizer import Packed8
    flat = _flatten(params_shape)
    specs = {}
    for path, leaf in flat.items():
        if isinstance(leaf, Packed8):
            # int8 block-quantized moment: children q (nblk, blk), s (nblk, 1)
            # — moments join no matmul, so shard the block dim over EVERY
            # mesh axis (fsdp-only sharding left 1.35 TB spread 16-way; §Perf)
            all_axes = tuple(mesh.axis_names) if not no_fsdp else \
                tuple(a for a in mesh.axis_names if a == "model")
            nblk = leaf.q.shape[0]
            if all_axes and nblk % _axis_size(mesh, all_axes) == 0:
                specs[path] = P(all_axes)
            else:
                specs[path] = P()
            continue
        shape = tuple(leaf.shape)
        if embed_tp and re.search(r"(^|/)embed$", path) and len(shape) == 2:
            tp = "model" if "model" in mesh.axis_names else None
            ok = tp and shape[1] % _axis_size(mesh, tp) == 0
            specs[path] = P(None, tp if ok else None)
            continue
        # disambiguate 3D dense-FFN vs 4D expert weights: both match
        # r"ffn/w_gate$" — the template is right-aligned, so the 3-entry
        # expert template on a 3D (L,d,f) dense weight would wrongly shard L.
        if re.search(r"ffn/(w_gate|w_up|w_down)$", path) and len(shape) == 4:
            tmpl = ("experts", "fsdp", "tp") if path.endswith(("w_gate", "w_up")) \
                else ("experts", "tp", "fsdp")
            axes = [None] * (len(shape) - 3) + list(tmpl)
            spec = []
            used = set()
            for dim, logical in zip(shape, axes):
                phys = _resolve(mesh, logical, no_fsdp)
                flat_axes = phys if isinstance(phys, tuple) else \
                    (phys,) if phys else ()
                if phys is None or dim % _axis_size(mesh, phys) != 0 or \
                        any(a in used for a in flat_axes):
                    spec.append(None)
                else:
                    used.update(flat_axes)
                    spec.append(phys)
            specs[path] = P(*spec)
        else:
            specs[path] = spec_for(mesh, path, shape, no_fsdp)
    return _unflatten(specs)


def shardings_for(mesh: Mesh, tree_shape: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(mesh, tree_shape),
        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh: Mesh, batch_shape: Any, dp_axes=None) -> Any:
    """tokens/images/labels: batch dim over ('pod','data') when divisible.
    dp_axes overrides the data-parallel axes (dp_all layouts)."""
    dp = dp_axes or tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = _axis_size(mesh, dp)

    def one(leaf):
        shape = tuple(leaf.shape)
        if shape and shape[0] % dp_size == 0 and dp_size > 1:
            return P(dp, *([None] * (len(shape) - 1)))
        return P(*([None] * len(shape)))
    return jax.tree_util.tree_map(one, batch_shape)


def cache_spec(mesh: Mesh, cache_shape: Any, batch_axis: int = 1) -> Any:
    """KV caches / recurrent states: shard batch if divisible, else the
    longest remaining dim that divides (sequence for long-context B=1)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = _axis_size(mesh, dp)
    tp_size = _axis_size(mesh, "model")

    def one(leaf):
        shape = tuple(leaf.shape)
        spec = [None] * len(shape)
        if not shape:
            return P()
        used_dp = False
        for i, dim in enumerate(shape):
            if not used_dp and dim % dp_size == 0 and dp_size > 1 and \
                    i >= min(batch_axis, len(shape) - 1) and dim >= dp_size:
                spec[i] = dp
                used_dp = True
                break
        if "model" in mesh.axis_names and tp_size > 1:
            # shard the largest not-yet-sharded trailing dim divisible by tp
            # (sequence for long caches). NOTE §Perf: sharding head_dim instead
            # was tried and refuted — it makes every decode attention contract
            # over a sharded axis (psum of scores per layer per token).
            cands = [(dim, i) for i, dim in enumerate(shape)
                     if spec[i] is None and dim % tp_size == 0
                     and dim >= tp_size and i > 0]
            if cands:
                _, i = max(cands)
                spec[i] = "model"
        return P(*spec)
    return jax.tree_util.tree_map(one, cache_shape)

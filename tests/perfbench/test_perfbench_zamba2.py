"""The Zamba2 family on the CPU at a tiny size with seeded float32 weights:
the served path (``ServeSession`` prefill, then decode through its caches)
agrees with the family's plain reference on every decoded position; the
reference agrees with transformers' ``Zamba2ForCausalLM`` on the same
weights, and a reference with one planted departure does not; the
family's byte counts are the pytree's and the cache counters'; the serve
job runs the family end to end."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
from perfbench import harness, weights

DECODE = "serve.zamba2-7b.decode"
SEED = 2 ** 40 + 21


def tiny(dtype="float32", **kw) -> dict:
    """Zamba2-7B's file at tiny widths: 8 layers, sites 1, 3, 5, 7 (blocks
    A, B, A, B), two B/C groups, MHA over the 2 * hidden concatenation."""
    cfg = cells._json("perfbench/configs/zamba2-7b.json")
    cfg.update(hidden_size=32, intermediate_size=48, num_hidden_layers=8,
               hybrid_layer_ids=[1, 3, 5, 7], n_mamba_heads=8,
               mamba_headdim=8, mamba_d_state=4, attention_head_dim=16,
               num_attention_heads=4, num_key_value_heads=4, adapter_rank=3,
               vocab_size=101, torch_dtype=dtype)
    cfg["layers_block_type"] = [
        "hybrid" if i in cfg["hybrid_layer_ids"] else "mamba"
        for i in range(cfg["num_hidden_layers"])]
    cfg.update(kw)
    return cfg


def _family():
    return harness.load_module("families", "zamba2")


def _reference():
    return harness.load_module("reference", "zamba2")


def _served_logits(cfg, prompts, max_new):
    """Greedy tokens and the logits each was sampled from, through
    ``ServeSession`` (prefill, then the jitted decode step with its state
    donated)."""
    from repro.models import build_model
    from repro.serve.serve_loop import ServeSession

    class Session(ServeSession):
        def _sample(self, logits):
            seen.append(np.asarray(logits[:, -1]))
            return super()._sample(logits)

    seen = []
    api = build_model(_family().program_config(cfg))
    params = _reference().params(cfg, weights.seed_key(SEED))
    sess = Session(api, params, batch_slots=len(prompts),
                   S_max=prompts.shape[1] + max_new)
    outs = np.array(sess.generate(list(prompts), max_new=max_new))
    return params, outs, np.stack(seen, axis=1)       # (B, max_new, V)


def test_serving_agrees_with_the_reference_at_every_decoded_position():
    cfg = tiny()
    P, N = 9, 7
    prompts = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], (2, P)).astype(np.int32)
    params, outs, seen = _served_logits(cfg, prompts, N)
    ref = _reference().make_logits(cfg)
    for b in range(len(prompts)):
        toks = jnp.asarray(np.concatenate([prompts[b], outs[b, :-1]]))
        want = np.asarray(ref(params, toks, P - 1, N))
        # float32 on both sides, differing only in the order of sums
        # (one recurrence per position against a scan, fused projections):
        # about 2e-6 at a logit scale of 0.4 here
        tol = 1e-4 * max(float(np.abs(want).max()), 1.0)
        assert float(np.abs(seen[b] - want).max()) <= tol


def _transformers_logits(cfg, params, tokens):
    """``Zamba2ForCausalLM`` of transformers, float32, with ``params``
    (the program's layout) copied into its modules, fed one token at a
    time through its own cache (``Zamba2HybridDynamicCache``): its
    recurrent decode path at every position.

    Its prefill path is not used: in transformers 4.57.6 the chunked SSD
    of ``Zamba2MambaMixer.torch_forward`` sums the inter-chunk decay over
    the wrong axis (``.sum(dim=2)`` where ``modeling_mamba2.py`` sums the
    same product over the chunks), so past one chunk it departs from its
    own decode (by 0.25 in a logit at 13 positions in chunks of 4)."""
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")
    hf = tfm.Zamba2ForCausalLM(tfm.Zamba2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layers_block_type=cfg["layers_block_type"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_ngroups=cfg["mamba_ngroups"],
        n_mamba_heads=cfg["n_mamba_heads"],
        intermediate_size=cfg["intermediate_size"],
        hidden_act=cfg["hidden_act"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        num_mem_blocks=cfg["num_mem_blocks"],
        use_shared_attention_adapter=False,
        adapter_rank=cfg["adapter_rank"], use_mem_rope=True,
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        chunk_size=cfg["chunk_size"], tie_word_embeddings=True,
        attn_implementation="eager")).eval()
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)

    def put(module, value):
        assert tuple(module.shape) == value.shape, (module.shape, value.shape)
        module.data.copy_(torch.from_numpy(np.array(value)))

    m, ids = hf.model, cfg["hybrid_layer_ids"]
    put(m.embed_tokens.weight, p["embed"])
    put(m.final_layernorm.weight, p["final_norm"])
    for i, layer in enumerate(m.layers):
        mam = p["mamba"]
        dec = layer.mamba_decoder if i in ids else layer
        put(dec.input_layernorm.weight, mam["ln"][i])
        mx = dec.mamba
        put(mx.in_proj.weight, mam["in_proj"][i].T)
        put(mx.conv1d.weight, mam["conv_w"][i].T[:, None, :])
        put(mx.conv1d.bias, mam["conv_b"][i])
        for n in ("A_log", "D", "dt_bias"):
            put(getattr(mx, n), mam[n][i])
        put(mx.norm.weight, mam["out_norm"][i])
        put(mx.out_proj.weight, mam["out_proj"][i].T)
        if i not in ids:
            continue
        j = ids.index(i)
        b = j % cfg["num_mem_blocks"]
        sh, st = p["shared"], p["sites"]
        blk = layer.shared_transformer
        put(blk.input_layernorm.weight, sh["ln1"][b])
        put(blk.pre_ff_layernorm.weight, sh["ln2"][b])
        for n in ("q", "k", "v", "o"):
            put(getattr(blk.self_attn, f"{n}_proj").weight,
                sh["attn"][f"w{n}"][b].T)
        ff = blk.feed_forward
        put(ff.gate_up_proj.weight, sh["w_gate_up"][b].T)
        put(ff.down_proj.weight, sh["w_down"][b].T)
        put(ff.gate_up_proj_adapter_list[j][0].weight, st["adapter_a"][j].T)
        put(ff.gate_up_proj_adapter_list[j][1].weight, st["adapter_b"][j].T)
        put(layer.linear.weight, st["linear"][j].T)
    from transformers.models.zamba2.modeling_zamba2 import \
        Zamba2HybridDynamicCache
    cache = Zamba2HybridDynamicCache(hf.config, 1, dtype=torch.float32)
    ids = torch.from_numpy(np.asarray(tokens, np.int64))[None]
    out = []
    with torch.no_grad():
        for t in range(ids.shape[1]):
            lg = hf(ids[:, t:t + 1], past_key_values=cache, use_cache=True,
                    cache_position=torch.tensor([t])).logits
            out.append(lg[0, -1].numpy())
    return np.stack(out)


def _planted(change):
    """The reference module with one departure written into its source."""
    src = open(cells.ROOT + "/perfbench/reference/zamba2.py").read()
    new = change(src)
    assert new != src
    mod = types.ModuleType("planted_zamba2_reference")
    exec(compile(new, "planted_zamba2_reference", "exec"), mod.__dict__)
    return mod


PLANTED = {
    "scale 1/sqrt(head_dim)": lambda s: s.replace("(hd / 2) ** -0.5",
                                                  "hd ** -0.5"),
    "ungrouped gated norm": lambda s: s.replace(
        ".reshape(S, G, -1)", ".reshape(S, 1, -1)"),
}


@pytest.fixture(scope="module")
def published():
    """Seeded weights, a prompt, and transformers' logits on them."""
    cfg = tiny()
    params = _reference().params(cfg, weights.seed_key(SEED + 1))
    tokens = np.random.default_rng(4).integers(0, cfg["vocab_size"], 13)
    return cfg, params, tokens, _transformers_logits(cfg, params, tokens)


def _gap(module, published):
    cfg, params, tokens, want = published
    got = module.make_logits(cfg)(params, jnp.asarray(tokens, jnp.int32), 0,
                                  len(tokens))
    # float32 on both sides, both stepping the recurrence; they differ in
    # the order of sums alone: about 1e-6 apart at a logit scale of 0.4
    tol = 1e-4 * max(float(np.abs(want).max()), 1.0)
    return float(np.abs(np.asarray(got) - want).max()), tol


def test_the_reference_agrees_with_transformers(published):
    gap, tol = _gap(_reference(), published)
    assert gap <= tol, (gap, tol)


@pytest.mark.parametrize("departure", list(PLANTED))
def test_a_planted_departure_fails_the_comparison(published, departure):
    gap, tol = _gap(_planted(PLANTED[departure]), published)
    assert gap > 10 * tol, (gap, tol)


def test_the_family_counts_the_pytree_and_the_cache_counters():
    from repro.models import build_model
    from repro.obs import Tracer, use_tracer
    from repro.serve.serve_loop import ServeSession
    cfg = tiny("bfloat16")
    fam = _family()
    params = _reference().params(cfg, weights.seed_key(SEED))
    leaves = jax.tree_util.tree_leaves(params)
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert fam.weight_bytes(cfg) == sum(a.size * 2 for a in leaves)
    B, P, N = 3, 5, 3
    sess = ServeSession(build_model(fam.program_config(cfg)), params,
                        batch_slots=B, S_max=P + N)
    tr = Tracer()
    with use_tracer(tr):
        sess.generate([np.arange(P)] * B, max_new=N)
    c = tr.counters
    assert c["serve.cache_bytes:recurrent"] == fam.recurrent_state_bytes(cfg,
                                                                         B)
    assert c["serve.cache_bytes:kv"] == B * (P + N) * fam.kv_bytes_per_token(
        cfg)


def test_the_family_refuses_what_the_program_does_not_compute():
    with pytest.raises(ValueError, match="use_shared_attention_adapter"):
        _family().program_config(tiny(use_shared_attention_adapter=True))


def test_the_serve_job_runs_zamba2_end_to_end():
    traffic = dict(cells.SERVE_TRAFFIC, prompt_len=12, max_new=5)
    checks = cells.committed_checks(DECODE)
    cell = cells.cell(DECODE, tiny("bfloat16"), traffic, checks)
    out = cells.run(cell, seconds=0.3)
    assert harness.is_correct(out), out.checks
    assert out.record["flops"] == out.record["calls"] * \
        traffic["batch_slots"] * _family().request_flops(
            cell.config, traffic["prompt_len"], traffic["max_new"])
    # bfloat16 weights and activations at tiny widths: the served tokens'
    # gap is a small share of the chip's limit
    assert out.values["served_logit_gap"] < \
        checks["served_logit_gap"]["limit"] / 4


def test_the_committed_configuration_is_cut_in_depth_only():
    cfg = cells._json("perfbench/configs/zamba2-7b.json")
    bench = harness.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["zamba2-7b"]
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["num_hidden_layers"] == 24
    assert cfg["hybrid_layer_ids"] == [6, 11, 17, 23] == [
        i for i, t in enumerate(cfg["layers_block_type"]) if t == "hybrid"]
    pc = _family().program_config(cfg)
    assert (pc.d_model, pc.head_dim, pc.ssm.ngroups, pc.num_mem_blocks,
            pc.adapter_rank) == (3584, 224, 2, 2, 128)
    assert cfg["torch_dtype"] == "bfloat16" and \
        any("bfloat16" in a for a in cfg["assumed"])

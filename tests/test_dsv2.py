"""DeepSeek-V2-kind serving on seeded random weights, at a small size,
against the plain float32 reference (``repro.models.dsv2_reference``):
latent attention with YaRN rope, a leading dense layer, and a dropless MoE
layer of shared experts and the routed experts a device holds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite as dsv
from repro.configs import get_config
from repro.env.serve_engine import ExpertServer, Request, _bucket
from repro.models import dsv2_reference as ref_lib
from repro.models import mla, model
from repro.models import moe as moe_lib

# 1 dense and 1 MoE layer, 8 routed experts top-3, 1 shared expert, small
# latent, rope and head sizes; YaRN as published
HF = dict(dsv.PUBLISHED, hidden_size=64, num_attention_heads=4,
          kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
          n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=1,
          num_hidden_layers=2, vocab_size=256)
# float32 weights and activations: the program and the reference differ
# only in the order of float32 sums
TOL = 1e-4


def small_cfg(**kw):
    return dsv.from_hf(HF, "dsv2-small", param_dtype="float32",
                       compute_dtype="float32", **kw)


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.sqrt(np.mean(want ** 2)))


@pytest.fixture(scope="module")
def cfg():
    return small_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return model.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def reference(cfg):
    return ref_lib.Reference(HF, cfg.held)


def test_config_counts_the_published_model():
    cfg = get_config("deepseek-v2-lite")
    assert cfg.attention == "mla" and cfg.held == tuple(range(64))
    # 15.7B parameters, 2.4B active, the input embedding left out
    assert abs(cfg.n_params() / 15.7e9 - 1) < 0.01
    embed = cfg.vocab * cfg.d_model
    assert abs((cfg.n_active_params() - embed) / 2.4e9 - 1) < 0.03
    # the latent cache: 576 values a token a layer, 8.9x fewer than the
    # expanded keys and values
    per_token = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    expanded = cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                              + cfg.v_head_dim)
    assert per_token == 576 and expanded == 5120


def test_yarn_frequencies_and_scale_match_the_source_formula():
    cfg = get_config("deepseek-v2-lite")
    got = mla.inv_freq(cfg)
    want = ref_lib.yarn_inv_freq(dsv.PUBLISHED)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # correction range [10, 23] of the 32 frequencies: below it the
    # original frequencies, above it those divided by the factor 40
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-5)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-5)
    ramp = (np.arange(10, 23) - 10) / 13
    np.testing.assert_allclose(
        got[10:23], base[10:23] / 40 * ramp + base[10:23] * (1 - ramp),
        rtol=1e-5)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert ref_lib.softmax_scale(dsv.PUBLISHED) == pytest.approx(
        192 ** -0.5 * m * m)
    assert mla.rope_mscale(cfg) == pytest.approx(1.0)


def test_rope_uses_the_source_pair_order(cfg):
    x = jax.random.normal(jax.random.PRNGKey(3), (7, 4, 8))
    pos = jnp.arange(7) * 300
    got = mla.rope(x[None], pos[None], cfg)[0]
    want = ref_lib._rope(x, pos, jnp.asarray(mla.inv_freq(cfg)), 1.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # pairs (0, 1), (2, 3), ... rotate together: the result's halves hold
    # the even and the odd members of each pair
    pair = x[..., 0::2] ** 2 + x[..., 1::2] ** 2
    out = got[..., :4] ** 2 + got[..., 4:] ** 2
    np.testing.assert_allclose(out, pair, rtol=1e-4)


def test_forward_matches_reference(cfg, params, reference):
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, HF["vocab_size"])
    logits, _ = mla.forward(params, cfg, toks)
    want = reference.logits(params, toks[0], np.arange(24))
    assert rel_err(logits[0, :, :HF["vocab_size"]], want) < TOL


def test_absorbed_decode_equals_expanded_attention(cfg, params):
    p = jax.tree.map(lambda a: a[0], params["moe_layers"])["attn"]
    S = 13
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    full, c, k_pe = mla.attention_expanded(p, cfg, x, pos)
    q_nope, q_pe, _, _ = mla.attn_inputs(p, cfg, x, pos)
    last = jnp.full((2,), S - 1)
    got = mla.attention_absorbed(p, cfg, q_nope[:, -1], q_pe[:, -1], c,
                                 k_pe, last)
    np.testing.assert_allclose(got, full[:, -1], rtol=1e-4, atol=1e-5)
    # positions past ``pos`` are not read
    junk = c.at[:, S - 4:].set(1e3)
    got = mla.attention_absorbed(p, cfg, q_nope[:, S - 5], q_pe[:, S - 5],
                                 junk, k_pe, last - 4)
    np.testing.assert_allclose(got, full[:, S - 5], rtol=1e-4, atol=1e-5)


def serve(cfg, params, prompts, outputs, *, slots=2, max_len=64):
    """Serve the requests through ``ExpertServer``; returns, per request,
    its tokens and the logits of each iteration that produced one."""
    srv = ExpertServer("dsv2", cfg, params, slots=slots, max_len=max_len)
    for rid, (p, n) in enumerate(zip(prompts, outputs)):
        srv.submit(Request(rid=rid, tokens=p, max_new=n, ignore_eos=True))
    logits = {rid: [] for rid in range(len(prompts))}
    done = {}
    while srv.has_work():
        before = dict(srv.active)
        finished = srv.step()
        last = srv.iteration_log[-1]
        if last["kind"] == "prefill":
            logits[last["rid"]].append(np.asarray(srv.logits))
        else:
            for rid, req in before.items():
                logits[rid].append(np.asarray(srv.logits[req.slot]))
        done.update({r.rid: r for r in finished})
    return done, logits, srv


def test_prefill_then_decode_through_the_server_matches_reference(
        cfg, params, reference):
    rng = np.random.default_rng(0)
    lens, outs = [5, 17, 9, 30], [6, 3, 9, 4]
    prompts = [rng.integers(0, HF["vocab_size"], n) for n in lens]
    done, logits, srv = serve(cfg, params, prompts, outs)
    assert sorted(done) == [0, 1, 2, 3]
    for rid, req in done.items():
        # each request runs to its length, whatever tokens come
        assert len(req.generated) == outs[rid]
        seq = np.concatenate([prompts[rid], req.generated[:-1]])
        p = lens[rid]
        want = reference.logits(params, seq, np.arange(p - 1, len(seq)))
        got = np.stack(logits[rid])[:, :HF["vocab_size"]]
        assert rel_err(got, want) < TOL, rid
    # the host mirrors the slots' positions: no device read per request
    np.testing.assert_array_equal(srv.pos, np.asarray(srv.cache["pos"]))
    # every held expert's decode tokens were counted
    assert int(jnp.sum(srv.cache["load"])) > 0


def test_prompt_longer_than_256_tokens_is_served(cfg, params, reference):
    assert _bucket(300, 512) == 512 and _bucket(5, 512) == 16
    with pytest.raises(ValueError):
        _bucket(513, 512)
    prompt = np.random.default_rng(1).integers(0, HF["vocab_size"], 300)
    done, logits, _ = serve(cfg, params, [prompt], [3], slots=1, max_len=512)
    seq = np.concatenate([prompt, done[0].generated[:-1]])
    want = reference.logits(params, seq, np.arange(299, 302))
    got = np.stack(logits[0])[:, :HF["vocab_size"]]
    assert rel_err(got, want) < TOL


def moe_layer(cfg, held=()):
    c = dataclasses.replace(cfg, held_experts=tuple(held))
    p = moe_lib.init_held_moe(jax.random.PRNGKey(7), c, jnp.float32)
    return c, p


def test_four_shares_add_up_to_the_uncut_layer(cfg):
    x = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.d_model))
    full_cfg, full = moe_layer(cfg)
    want = ref_lib.Reference(HF, full_cfg.held)._moe(x, full)
    shares = []
    for i in range(4):
        c, p = moe_layer(cfg, (2 * i, 2 * i + 1))
        # an expert's weights are the same whichever share holds it
        np.testing.assert_array_equal(p["routed"]["w_up"],
                                      full["routed"]["w_up"][2 * i:2 * i + 2])
        out, sizes = moe_lib.held_moe_block(p, x, c)
        shares.append(out)
        # the reference given the same held ids computes the same share
        part = ref_lib.Reference(HF, c.held)._moe(x, p)
        assert rel_err(out, part) < TOL
    shared = mla.layers.swiglu(x, full["shared"]["w_gate"],
                               full["shared"]["w_up"], full["shared"]["w_down"])
    # the shared expert, which every share computes, counts once
    total = sum(shares) - 3 * shared
    assert rel_err(total, want) < TOL


def test_no_token_is_dropped_under_skewed_routing(cfg):
    c, p = moe_layer(cfg)
    # every token prefers experts 5, 6 and 7
    bias = jnp.zeros((cfg.d_model, cfg.n_experts)).at[0, 5:].set(50.0)
    p = dict(p, gate=p["gate"] + bias)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)
    out, sizes = moe_lib.held_moe_block(p, x, c)
    assert list(np.asarray(sizes)) == [0] * 5 + [64] * 3
    want = ref_lib.Reference(HF, c.held)._moe(x, p)
    assert rel_err(out, want) < TOL
    # the capacity-factor layer would drop most of these tokens
    cap = moe_lib._capacity(64, dataclasses.replace(c, top_k=3))
    assert cap < 64

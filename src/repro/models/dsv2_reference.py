"""Plain reference of the DeepSeek-V2 decoder, for checking the served model.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching, and the expanded (not absorbed) latent attention.  It reads the
served model's weights (``repro.models.mla``'s layout), widened to float32
one layer at a time as it runs, so that it fits beside them on a device,
and is configured by the source's ``config.json`` keys, not by this
package's config classes.  Given the ids of the routed experts the weights
hold, it computes the same share of the routed experts: for each held
expert, its SwiGLU over every token, times the token's gate for it (zero
where the expert is not among the token's top k).

``rnd``, where given, rounds both operands of every product (a lower
precision control, such as float8).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(hf: dict) -> np.ndarray:
    """The source's ``DeepseekV2YarnRotaryEmbedding`` frequencies."""
    dim, base = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    rs = hf["rope_scaling"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if not rs:
        return freq_extra.astype(np.float32)
    freq_inter = 1.0 / (rs["factor"]
                        * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def softmax_scale(hf: dict) -> float:
    s = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    rs = hf["rope_scaling"]
    if rs and rs.get("mscale_all_dim"):
        m = _mscale(rs["factor"], rs["mscale_all_dim"])
        s = s * m * m
    return s


def _rope(x, pos, inv, m):
    """The source's ``apply_rotary_pos_emb``: view the last dim as pairs,
    gather them into halves, then ``x*cos + rotate_half(x)*sin``."""
    freqs = pos[:, None].astype(F32) * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    if x.ndim == 3:                      # (S, H, r)
        cos, sin = cos[:, None], sin[:, None]
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


class Reference:
    """``logits(params, tokens, at)``: float32 logits of the whole forward
    over ``tokens`` (S,), at the positions ``at``, over the vocabulary."""

    def __init__(self, hf: dict, held, rnd=None):
        self.hf = hf
        self.held = tuple(int(e) for e in held)
        self.rnd = rnd or (lambda a: a)
        self.inv = jnp.asarray(yarn_inv_freq(hf))
        rs = hf["rope_scaling"]
        self.rope_m = (_mscale(rs["factor"], rs["mscale"])
                       / _mscale(rs["factor"], rs["mscale_all_dim"])
                       if rs else 1.0)
        self.scale = softmax_scale(hf)
        self._layer = jax.jit(self._layer_fn, static_argnames=("kind",))
        self._head = jax.jit(self._head_fn)

    def mm(self, spec, a, b):
        return jnp.einsum(spec, self.rnd(a), self.rnd(b))

    def _mlp(self, x, p):
        g = self.mm("sd,df->sf", x, p["w_gate"])
        u = self.mm("sd,df->sf", x, p["w_up"])
        return self.mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"])

    def _attention(self, x, p, pos):
        hf = self.hf
        nope, r = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        q = self.mm("sd,dhe->she", x, p["wq"])
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kv = self.mm("sd,de->se", x, p["wkv_a"])
        c = _norm(kv[:, :hf["kv_lora_rank"]], p["kv_norm"], hf["rms_norm_eps"])
        k_pe = kv[:, hf["kv_lora_rank"]:]
        q_pe = _rope(q_pe, pos, self.inv, self.rope_m)
        k_pe = _rope(k_pe, pos, self.inv, self.rope_m)
        k_nope = self.mm("tc,chn->thn", c, p["w_uk"])
        v = self.mm("tc,chv->thv", c, p["w_uv"])
        S, H = x.shape[0], q.shape[1]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None],
                                                      (S, H, r))], -1)
        qq = jnp.concatenate([q_nope, q_pe], -1)
        s = self.mm("shd,thd->hst", qq, k) * self.scale
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = self.mm("hst,thv->shv", w, v)
        return self.mm("shv,hvd->sd", o, p["wo"])

    def _moe(self, x, p):
        hf = self.hf
        scores = jax.nn.softmax(self.mm("sd,de->se", x, p["gate"]), axis=-1)
        w, idx = jax.lax.top_k(scores, hf["num_experts_per_tok"])
        if hf["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * hf["routed_scaling_factor"]
        out = jnp.zeros_like(x)
        for j, e in enumerate(self.held):
            gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            ex = jax.tree.map(lambda a: a[j], p["routed"])
            out = out + gate[:, None] * self._mlp(x, ex)
        if "shared" in p:
            out = out + self._mlp(x, p["shared"])
        return out

    def _layer_fn(self, stacked, i, h, kind):
        p = jax.tree.map(lambda a: a[i].astype(F32), stacked)
        eps = self.hf["rms_norm_eps"]
        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(h.shape[0])
            h = h + self._attention(_norm(h, p["attn_norm"], eps),
                                    p["attn"], pos)
            y = _norm(h, p["mlp_norm"], eps)
            ff = self._moe(y, p["moe"]) if kind == "moe" else self._mlp(
                y, p["mlp"])
            return h + ff

    def _head_fn(self, h, final_norm, lm_head):
        with jax.default_matmul_precision("highest"):
            x = _norm(h, final_norm.astype(F32), self.hf["rms_norm_eps"])
            return self.mm("sd,dv->sv", x,
                           lm_head[:, :self.hf["vocab_size"]].astype(F32))

    def logits(self, params, tokens, at) -> jax.Array:
        h = params["embed"][jnp.asarray(tokens)].astype(F32)
        stacks = [("dense", params.get("dense_layers")),
                  ("moe", params["moe_layers"])]
        for kind, stacked in stacks:
            if stacked is None:
                continue
            n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
            for i in range(n):
                h = self._layer(stacked, i, h, kind=kind)
        return self._head(h[jnp.asarray(at)], params["final_norm"],
                          params["lm_head"])


def float8(a):
    """Round to float8_e4m3fn and back: the control's operands."""
    return a.astype(jnp.float8_e4m3fn).astype(F32)

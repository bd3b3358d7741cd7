"""Plain reference of the routing simulator and the HAN/SAC router.

The benchmark's correctness check replays what the timed path did through
this module and compares.  It is written from the paper's equations and
the simulator's documented semantics, in straightforward ``jax.numpy``,
and imports nothing of the program under test:

* the fleet (quality, length, latency gradients, memory), drawn from the
  configuration's fleet seed;
* one routing step: the Eq. 15 impact penalty, the push into the chosen
  expert's waiting queue, the next arrival (Poisson or the bursty
  diurnal process) and an iteration-level advance of every expert to it,
  written per expert (admit > decode > idle, FIFO admission);
* the heterogeneous-graph observation (Eq. 6) in the padded, masked
  layout;
* the HAN (node-level GAT + semantic attention) and the discrete SAC
  actor, critics, losses, Adam with global-norm clipping and Polyak
  averaging.

Every float runs in ``ft``: float32 with matrix products at ``HIGHEST``
precision is the reference; bfloat16 is the control, the precision below
the configuration's float32.  Random draws are made in float32 from the
same keys as the simulator's and then cast.

The simulator's queue state is four packed tensors (``run_i``, ``run_f``,
``wait_i``, ``wait_f``; channel order below); the reference works on
named fields and converts at its boundary.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
I_RUN = ("valid", "p", "d_true", "d_cur", "retry")
F_RUN = ("score", "pred_s", "pred_d", "t_arrive", "t_admit")
I_WAIT = ("valid", "p", "d_true", "retry")
F_WAIT = ("score", "pred_s", "pred_d", "t_arrive")
STATS = ("phi", "lat", "score", "wait", "done", "viol", "dropped", "routed",
         "evicted")
ACC = ("phi", "lat", "score", "wait", "done", "viol")
MAX_TRIPS = 1 << 16  # bounds the bfloat16 control's loop; float32 never nears it
_BUILT = {}


def built(key, make):
    """``make()`` once per ``key`` in this process: the reference's jitted
    programs outlive the entries that use them."""
    if key not in _BUILT:
        _BUILT[key] = make()
    return _BUILT[key]


# ---------------------------------------------------------------------------
# Fleet
# ---------------------------------------------------------------------------


def make_pool(n: int, n_types: int, seed: int,
              speed_spread: float = 2.5) -> dict:
    """The heterogeneous fleet: per-expert quality and length by task
    type, prefill/decode latency gradients and KV memory."""
    rng = np.random.default_rng(seed)
    base_q = rng.uniform(0.58, 0.72, size=(n, 1))
    spec = np.zeros((n, n_types))
    for e in range(n):
        strong = rng.choice(n_types, size=max(1, n_types // 3), replace=False)
        spec[e, strong] += rng.uniform(0.12, 0.22)
        weak = rng.choice(n_types, size=max(1, n_types // 4), replace=False)
        spec[e, weak] -= rng.uniform(0.10, 0.20)
    quality = np.clip(base_q + spec + rng.normal(0, 0.01, spec.shape),
                      0.2, 0.97)
    verbosity = rng.uniform(np.log(60.0), np.log(220.0), size=(n, 1))
    type_len = rng.uniform(-0.35, 0.35, size=(1, n_types))
    log_len_std = rng.uniform(0.12, 0.28, size=(n, n_types))
    speed = np.exp(rng.uniform(0.0, np.log(speed_spread), size=n))
    mem_capacity = rng.uniform(1.0e9, 2.0e9, size=n)
    mem_per_token = np.full(n, 0.8e6) * rng.uniform(0.8, 1.2, n)
    f32 = lambda x: np.asarray(x, np.float32)
    return {"quality_mean": f32(quality),
            "quality_std": f32(np.full_like(quality, 0.05)),
            "log_len_mean": f32(verbosity + type_len),
            "log_len_std": f32(log_len_std),
            "k1": f32(0.00025 / speed), "k2": f32(0.000032 / speed),
            "mem_capacity": f32(mem_capacity),
            "mem_per_token": f32(mem_per_token)}


def memory_caps(pool: dict, run_cap: int, wait_cap: int):
    """Per-expert queue capacities in proportion to KV memory (the largest
    expert keeps the full widths, every expert keeps at least one)."""
    mem = np.asarray(pool["mem_capacity"], np.float64)
    frac = mem / mem.max()
    rc = np.clip(np.ceil(frac * run_cap), 1, run_cap).astype(np.int32)
    wc = np.clip(np.ceil(frac * wait_cap), 1, wait_cap).astype(np.int32)
    return rc, wc


class Sim:
    """A configuration of the simulator and router, in precision ``ft``.

    ``cfg`` is the benchmark configuration file's content, ``traffic`` the
    traffic mix's."""

    def __init__(self, cfg: dict, traffic: dict, ft=jnp.float32):
        self.ft = ft
        self.n = cfg["n_experts"]
        self.R, self.W = cfg["run_cap"], cfg["wait_cap"]
        self.L = cfg["latency_L"]
        self.n_types, self.n_buckets = cfg["n_types"], cfg["n_buckets"]
        self.max_output, self.max_prompt = cfg["max_output"], cfg["max_prompt"]
        self.s_noise = cfg["score_pred_noise"]
        self.d_noise = cfg["len_pred_noise"]
        self.drop_penalty = cfg["drop_penalty"]
        self.han_cfg = cfg["han"]
        self.sac_cfg = cfg["sac"]
        self.n_actions = self.n + 1
        pool = make_pool(self.n, self.n_types, cfg["pool_seed"])
        self.ragged = cfg["queue_caps"] == "memory"
        if self.ragged:
            rc, wc = memory_caps(pool, self.R, self.W)
        else:
            rc = np.full(self.n, self.R, np.int32)
            wc = np.full(self.n, self.W, np.int32)
        self.run_caps, self.wait_caps = rc, wc
        self.pool = {k: jnp.asarray(v) for k, v in pool.items()}
        wl = dict(traffic["arrivals"])
        if "rate_per_expert" in wl:
            wl["rate"] = wl.pop("rate_per_expert") * self.n
        self.wl = wl

    def f(self, x):
        return jnp.asarray(x).astype(self.ft)

    def mm(self, a, b):
        return jnp.matmul(a, b, precision=HI)

    def es(self, spec, *xs):
        return jnp.einsum(spec, *xs, precision=HI)

    # -- state conversion ----------------------------------------------------

    def from_program(self, s: dict) -> dict:
        """Named-field copy of a simulator state, floats in ``ft``."""
        q = s["queues"]
        queues = {}
        for side, ic, fc in (("run", I_RUN, F_RUN), ("wait", I_WAIT, F_WAIT)):
            for c, name in enumerate(ic):
                queues[f"{side}_{name}"] = q[f"{side}_i"][..., c]
            for c, name in enumerate(fc):
                queues[f"{side}_{name}"] = self.f(q[f"{side}_f"][..., c])
        pend = dict(s["pending"])
        for k in ("score", "pred_s", "pred_d"):
            pend[k] = self.f(pend[k])
        return {"key": s["key"], "clock": self.f(s["clock"]),
                "expert_clock": self.f(s["expert_clock"]), "queues": queues,
                "burst": s["wl"]["burst"], "pending": pend,
                "stats": {k: self.f(s["stats"][k]) for k in STATS}}

    def reset(self, key) -> dict:
        k1, k2 = jax.random.split(key)
        n, R, W = self.n, self.R, self.W
        queues = {}
        for side, w, ic, fc in (("run", R, I_RUN, F_RUN),
                                ("wait", W, I_WAIT, F_WAIT)):
            for name in ic:
                queues[f"{side}_{name}"] = jnp.zeros((n, w), jnp.int32)
            for name in fc:
                queues[f"{side}_{name}"] = jnp.zeros((n, w), self.ft)
        return {"key": k1, "clock": self.f(0.0),
                "expert_clock": jnp.zeros((n,), self.ft), "queues": queues,
                "burst": jnp.zeros((), jnp.bool_),
                "pending": self.new_request(k2),
                "stats": {k: self.f(0.0) for k in STATS}}

    # -- requests and arrivals -----------------------------------------------

    def new_request(self, key) -> dict:
        k_req, k_pred = jax.random.split(key)
        k1, k2, k3, k4 = jax.random.split(k_req, 4)
        pool, ft = self.pool, self.ft
        ttype = jax.random.randint(k1, (), 0, self.n_types)
        z = self.f(jax.random.normal(k2, ()))
        p_len = jnp.clip(jnp.exp(z * 0.7 + 4.5), 16.0, 512.0).astype(jnp.int32)
        q = (self.f(pool["quality_mean"][:, ttype])
             + self.f(pool["quality_std"][:, ttype])
             * self.f(jax.random.normal(k3, (self.n,))))
        score = jnp.clip(q, 0.0, 1.0)
        ln = (self.f(pool["log_len_mean"][:, ttype])
              + self.f(pool["log_len_std"][:, ttype])
              * self.f(jax.random.normal(k4, (self.n,))))
        out_len = jnp.clip(jnp.exp(ln), 8.0,
                           float(self.max_output)).astype(jnp.int32)
        # predictions: noisy, then quantized to the paper's 10 buckets
        k5, k6 = jax.random.split(k_pred)
        s_noisy = jnp.clip(score + self.s_noise
                           * self.f(jax.random.normal(k5, (self.n,))), 0.0, 1.0)
        d_noisy = jnp.clip(out_len.astype(ft) * jnp.exp(
            self.d_noise * self.f(jax.random.normal(k6, (self.n,)))),
            1.0, float(self.max_output))
        nb = self.n_buckets
        bs = jnp.clip((s_noisy * nb).astype(jnp.int32), 0, nb - 1)
        width = self.max_output / nb
        bd = jnp.clip((d_noisy / width).astype(jnp.int32), 0, nb - 1)
        return {"type": ttype, "p_len": p_len, "score": score,
                "out_len": out_len,
                "pred_s": (bs.astype(ft) + 0.5) / nb,
                "pred_d": (bd.astype(ft) + 0.5) * width}

    def next_arrival(self, burst, t, key):
        k1, k2 = jax.random.split(key)
        wl = self.wl
        if wl["kind"] == "poisson":
            rate = self.f(wl["rate"])
        else:
            diurnal = 1.0 + wl["diurnal_amp"] * jnp.sin(
                2.0 * math.pi * t / wl["diurnal_period"])
            mult = jnp.where(burst, wl["burst_rate_mult"], 1.0).astype(self.ft)
            # long-run mean rate = wl["rate"]: the chain flips per arrival,
            # and a burst arrival lasts 1/mult as long as a calm one
            p_on = wl["burst_on_prob"] / (wl["burst_on_prob"]
                                          + wl["burst_off_prob"])
            t_burst = p_on / wl["burst_rate_mult"]
            frac = t_burst / (t_burst + (1.0 - p_on))
            norm = 1.0 + frac * (wl["burst_rate_mult"] - 1.0)
            rate = wl["rate"] * diurnal * mult / norm
        rate = jnp.maximum(rate, 1e-3)
        dt = self.f(jax.random.exponential(k1)) / rate
        if wl["kind"] == "poisson":
            return dt, burst
        u = jax.random.uniform(k2)
        on = (~burst) & (u < wl["burst_on_prob"])
        off = burst & (u < wl["burst_off_prob"])
        return dt, jnp.where(on, True, jnp.where(off, False, burst))

    # -- the engine: every expert advances to t_next ---------------------------

    def advance(self, queues: dict, clocks, t_next):
        pool, L = self.pool, self.L
        per_expert = {"k1": self.f(pool["k1"]), "k2": self.f(pool["k2"]),
                      "cap": self.f(pool["mem_capacity"]),
                      "mpt": self.f(pool["mem_per_token"]),
                      "rc": jnp.asarray(self.run_caps),
                      "wc": jnp.asarray(self.wait_caps)}
        R, W, ft = self.R, self.W, self.ft

        def one(e, q, clock):
            run_ok = jnp.arange(R) < e["rc"]
            wait_ok = jnp.arange(W) < e["wc"]
            acc0 = {k: self.f(0.0) for k in ACC}

            def cond(c):
                q, clock, _, trips = c
                work = jnp.any(q["run_valid"] > 0) | jnp.any(q["wait_valid"] > 0)
                return (clock < t_next) & work & (trips < MAX_TRIPS)

            def body(c):
                q, clock, acc, trips = c
                valid = q["run_valid"] > 0
                tokens = jnp.sum(jnp.where(valid, q["run_p"] + q["run_d_cur"], 0))
                mem = tokens * e["mpt"]
                live = (q["wait_valid"] > 0) & wait_ok
                w = jnp.argmin(jnp.where(live, q["wait_t_arrive"],
                                         jnp.asarray(jnp.inf, ft)))
                free = ~valid & run_ok
                r = jnp.argmax(free)
                head_p = q["wait_p"][w]
                fits = mem + e["mpt"] * (head_p.astype(ft) + 1.0) <= e["cap"]
                if_admit = jnp.any(live) & jnp.any(free) & fits
                if_decode = ~if_admit & jnp.any(valid)

                # admit: the oldest waiter takes the first free slot
                qa = dict(q)
                for name in ("p", "d_true", "retry"):
                    qa[f"run_{name}"] = q[f"run_{name}"].at[r].set(
                        q[f"wait_{name}"][w])
                for name in ("score", "pred_s", "pred_d", "t_arrive"):
                    qa[f"run_{name}"] = q[f"run_{name}"].at[r].set(
                        q[f"wait_{name}"][w])
                qa["run_valid"] = q["run_valid"].at[r].set(1)
                qa["run_d_cur"] = q["run_d_cur"].at[r].set(1)
                qa["run_t_admit"] = q["run_t_admit"].at[r].set(clock)
                qa["wait_valid"] = q["wait_valid"].at[w].set(0)
                clock_a = clock + e["k1"] * head_p.astype(ft)

                # decode: every running request emits one token
                clock_d = clock + e["k2"] * tokens.astype(ft)
                d_new = q["run_d_cur"] + valid.astype(jnp.int32)
                done = valid & (d_new >= q["run_d_true"])
                lat = (clock_d - q["run_t_arrive"]) / jnp.maximum(
                    q["run_d_true"].astype(ft), 1.0)
                ok = lat <= L
                fin = lambda x: jnp.sum(jnp.where(done, x, 0.0)).astype(ft)
                acc_d = {"phi": acc["phi"] + fin(jnp.where(ok, q["run_score"], 0.0)),
                         "lat": acc["lat"] + fin(lat),
                         "score": acc["score"] + fin(q["run_score"]),
                         "wait": acc["wait"] + fin(q["run_t_admit"]
                                                   - q["run_t_arrive"]),
                         "done": acc["done"] + fin(jnp.ones_like(lat)),
                         "viol": acc["viol"] + fin(jnp.where(ok, 0.0, 1.0))}
                qd = dict(q)
                qd["run_d_cur"] = d_new
                qd["run_valid"] = (valid & ~done).astype(jnp.int32)

                pick = lambda a, d, base: jnp.where(
                    if_admit, a, jnp.where(if_decode, d, base))
                q = jax.tree.map(pick, qa, qd, q)
                acc = jax.tree.map(lambda d, base: jnp.where(if_decode, d, base),
                                   acc_d, acc)
                clock = pick(clock_a, clock_d, t_next)
                return q, clock, acc, trips + 1

            q, clock, acc, _ = jax.lax.while_loop(
                cond, body, (q, clock, acc0, jnp.int32(0)))
            return q, jnp.maximum(clock, t_next), acc

        return jax.vmap(one, in_axes=(0, 0, 0))(per_expert, queues, clocks)

    # -- one routing decision ------------------------------------------------

    def impact_penalty(self, s, a):
        """Eq. 15/16: predicted QoS of the chosen expert's running requests
        that the new request would push over the latency limit."""
        q, ft = s["queues"], self.ft
        e = jnp.clip(a - 1, 0, self.n - 1)
        k1, k2 = self.f(self.pool["k1"][e]), self.f(self.pool["k2"][e])
        p_j = s["pending"]["p_len"].astype(ft)
        d_j = s["pending"]["pred_d"][e]
        valid = q["run_valid"][e] > 0
        d_cur = q["run_d_cur"][e].astype(ft)
        d_hat = jnp.maximum(q["run_pred_d"][e], d_cur + 1.0)
        K = jnp.minimum(jnp.maximum(d_hat - d_cur, 0.0), d_j)
        extra = k1 * p_j + k2 * (K * p_j + 0.5 * K * (K + 1.0))
        l_est = ((s["clock"] - q["run_t_arrive"][e]) / jnp.maximum(d_cur, 1.0)
                 + extra / jnp.maximum(d_hat, 1.0))
        hit = valid & (l_est >= self.L)
        pen = jnp.sum(jnp.where(hit, q["run_pred_s"][e], 0.0)).astype(ft)
        return jnp.where(a > 0, pen, 0.0).astype(ft)

    def step(self, s: dict, a):
        """Returns (state, reward)."""
        ft, q = self.ft, dict(s["queues"])
        penalty = self.impact_penalty(s, a)
        # admission into the chosen expert's waiting queue
        r = s["pending"]
        e = jnp.clip(a - 1, 0, self.n - 1)
        free = ((q["wait_valid"][e] == 0)
                & (jnp.arange(self.W) < jnp.asarray(self.wait_caps)[e]))
        pushed = (a > 0) & jnp.any(free)
        slot = jnp.argmax(free)
        new = {"valid": 1, "p": r["p_len"], "d_true": r["out_len"][e],
               "retry": 0, "score": r["score"][e], "pred_s": r["pred_s"][e],
               "pred_d": r["pred_d"][e], "t_arrive": s["clock"]}
        for name, v in new.items():
            x = q[f"wait_{name}"]
            q[f"wait_{name}"] = x.at[e, slot].set(
                jnp.where(pushed, jnp.asarray(v).astype(x.dtype), x[e, slot]))
        dropped = ((a == 0) | ~pushed).astype(ft)

        key, k_arr, k_req = jax.random.split(s["key"], 3)
        dt, burst = self.next_arrival(s["burst"], s["clock"], k_arr)
        t_next = s["clock"] + dt
        q, clocks, acc = self.advance(q, s["expert_clock"], t_next)
        acc = {k: jnp.sum(v).astype(ft) for k, v in acc.items()}
        reward = acc["phi"] - penalty - self.drop_penalty * dropped
        stats = dict(s["stats"])
        for k in ACC:
            stats[k] = stats[k] + acc[k]
        stats["dropped"] = stats["dropped"] + dropped
        stats["routed"] = stats["routed"] + (a > 0).astype(ft)
        return ({"key": key, "clock": t_next, "expert_clock": clocks,
                 "queues": q, "burst": burst,
                 "pending": self.new_request(k_req), "stats": stats},
                reward.astype(ft))

    # -- observation (Eq. 6), padded and masked ------------------------------

    def obs(self, s: dict) -> dict:
        q, t, ft = s["queues"], s["clock"], self.ft
        L, mo, mp = self.L, float(self.max_output), float(self.max_prompt)
        r = s["pending"]
        mem_frac = self.f(self.pool["mem_per_token"]) / self.f(
            self.pool["mem_capacity"])
        rv, wv = q["run_valid"] > 0, q["wait_valid"] > 0
        d_cur = q["run_d_cur"].astype(ft)
        run = jnp.stack([
            q["run_p"].astype(ft) / mp, q["run_pred_s"], q["run_pred_d"] / mo,
            (q["run_p"] + q["run_d_cur"]).astype(ft) * mem_frac[:, None],
            d_cur / mo,
            (t - q["run_t_arrive"]) / jnp.maximum(d_cur, 1.0) / L,
            q["run_retry"].astype(ft)], axis=-1)
        zero = jnp.zeros_like(q["wait_pred_d"])
        wait = jnp.stack([
            q["wait_p"].astype(ft) / mp, q["wait_pred_s"], q["wait_pred_d"] / mo,
            zero, zero,
            (t - q["wait_t_arrive"]) / jnp.maximum(q["wait_pred_d"], 1.0) / L,
            q["wait_retry"].astype(ft)], axis=-1)
        run = jnp.where(rv[..., None], run, 0.0)
        wait = jnp.where(wv[..., None], wait, 0.0)
        tok = jnp.sum(jnp.where(rv, q["run_p"] + q["run_d_cur"], 0), -1)
        if self.ragged:
            occ_r = jnp.sum(rv, -1).astype(ft) / self.f(self.run_caps)
            occ_w = jnp.sum(wv, -1).astype(ft) / self.f(self.wait_caps)
        else:
            occ_r = jnp.mean(rv.astype(ft), -1)
            occ_w = jnp.mean(wv.astype(ft), -1)
        one = jnp.ones((self.n,), ft)
        expert = jnp.stack([
            tok.astype(ft) * mem_frac, occ_r, occ_w, r["pred_s"],
            r["pred_d"] / mo, self.f(self.pool["k1"]) * 1e3,
            self.f(self.pool["k2"]) * 1e4, one, one], axis=-1)
        z = self.f(0.0)
        arrived = jnp.stack([r["p_len"].astype(ft) / mp, jnp.mean(r["pred_s"]),
                             jnp.mean(r["pred_d"]) / mo, z, z, z, z])
        return {"expert": expert, "run": run, "wait": wait, "run_mask": rv,
                "wait_mask": wv, "arrived": arrived}

    # -- HAN + SAC ---------------------------------------------------------------

    def gat(self, p, target, neigh, mask):
        h = self.han_cfg["heads"]
        d = self.han_cfg["hidden"]
        tgt = self.mm(target, p["w"]).reshape(*target.shape[:-1], h, d // h)
        nb = self.mm(neigh, p["w"]).reshape(*neigh.shape[:-1], h, d // h)
        s_dst = self.es("...hd,hd->...h", tgt, p["a_dst"])
        s_src = self.es("...mhd,hd->...mh", nb, p["a_src"])
        e = jax.nn.leaky_relu(s_src + s_dst[..., None, :],
                              self.han_cfg["leaky_slope"])
        e = jnp.where(mask[..., None], e, -1e9)
        alpha = jnp.where(mask[..., None], jax.nn.softmax(e, axis=-2), 0.0)
        out = self.es("...mh,...mhd->...hd", alpha, nb)
        return jax.nn.elu(out.reshape(*target.shape[:-1], d))

    def semantic(self, p, embeds):
        w = self.es("...pd,d->...p",
                    jnp.tanh(self.mm(embeds, p["w"]) + p["b"]), p["q"])
        return self.es("...p,...pd->...d", jax.nn.softmax(w, axis=-1), embeds)

    def han(self, hp, o):
        """The arrived request's embedding after the HAN's layers."""
        mm = self.mm
        exp_h = jnp.tanh(mm(o["expert"], hp["proj_expert"]))
        run_h = jnp.tanh(mm(o["run"], hp["proj_req"]))
        wait_h = jnp.tanh(mm(o["wait"], hp["proj_req"]))
        arr_h = jnp.tanh(mm(o["arrived"], hp["proj_arrived"]))
        all_experts = jnp.ones((self.n,), bool)
        for lp in hp["layers"]:
            e_run = self.gat(lp["e_run"], exp_h, run_h, o["run_mask"])
            e_wait = self.gat(lp["e_wait"], exp_h, wait_h, o["wait_mask"])
            e_self = jax.nn.elu(mm(exp_h, lp["e_self"]))
            exp_new = self.semantic(lp["e_sem"],
                                    jnp.stack([e_self, e_run, e_wait], -2))
            a_exp = self.gat(lp["a_exp"], arr_h, exp_h, all_experts)
            a_self = jax.nn.elu(mm(arr_h, lp["a_self"]))
            arr_new = self.semantic(lp["a_sem"], jnp.stack([a_self, a_exp], -2))
            from_exp = mm(exp_h, lp["r_exp"])[:, None, :]
            run_h = jax.nn.elu(mm(run_h, lp["r_self"]) + from_exp)
            wait_h = jax.nn.elu(mm(wait_h, lp["r_self"]) + from_exp)
            exp_h, arr_h = exp_new, arr_new
        return arr_h

    def mlp(self, layers, x):
        for i, lyr in enumerate(layers):
            x = self.mm(x, lyr["w"]) + lyr["b"]
            if i + 1 < len(layers):
                x = jax.nn.relu(x)
        return x

    def embed(self, hp, obs):
        if obs["arrived"].ndim == 2:
            return jax.vmap(lambda o: self.han(hp, o))(obs)
        return self.han(hp, obs)

    def logits(self, params, obs):
        return self.mlp(params["actor"], self.embed(params["han"], obs))

    def init_params(self, key) -> dict:
        """The router's initial weights from ``key``."""
        d, h = self.han_cfg["hidden"], self.han_cfg["heads"]

        def glorot(k, shape):
            fan = sum(shape[-2:]) if len(shape) >= 2 else shape[-1] * 2
            return jax.random.normal(k, shape, jnp.float32) * jnp.sqrt(2.0 / fan)

        def gat(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"w": glorot(k1, (d, d)), "a_src": glorot(k2, (h, d // h)),
                    "a_dst": glorot(k3, (h, d // h))}

        def sem(k):
            k1, k2 = jax.random.split(k)
            return {"w": glorot(k1, (d, d)), "b": jnp.zeros((d,), jnp.float32),
                    "q": glorot(k2, (d,))}

        def layer(k):
            ks = jax.random.split(k, 10)
            return {"e_run": gat(ks[0]), "e_wait": gat(ks[1]),
                    "e_self": glorot(ks[2], (d, d)), "e_sem": sem(ks[3]),
                    "a_exp": gat(ks[4]), "a_self": glorot(ks[5], (d, d)),
                    "a_sem": sem(ks[6]), "r_exp": glorot(ks[7], (d, d)),
                    "r_self": glorot(ks[8], (d, d))}

        def han(k):
            k0, k1, k2, k3, _ = jax.random.split(k, 5)
            return {"proj_expert": glorot(k0, (9, d)),
                    "proj_req": glorot(k1, (7, d)),
                    "proj_arrived": glorot(k2, (7, d)),
                    "layers": [layer(jax.random.fold_in(k3, i))
                               for i in range(self.han_cfg["layers"])]}

        def mlp(k, dims):
            out = []
            for a, b in zip(dims[:-1], dims[1:]):
                k1, k = jax.random.split(k)
                out.append({"w": jax.random.normal(k1, (a, b), jnp.float32)
                            * jnp.sqrt(2.0 / a),
                            "b": jnp.zeros((b,), jnp.float32)})
            return out

        ks = jax.random.split(key, 6)
        dims = (d, self.sac_cfg["hidden"], self.n_actions)
        p = {"actor": mlp(ks[0], dims), "q1": mlp(ks[1], dims),
             "q2": mlp(ks[2], dims),
             "log_alpha": jnp.log(jnp.float32(self.sac_cfg["init_alpha"])),
             "han": han(ks[3]), "han_critic": han(ks[4])}
        p["q1_target"], p["q2_target"] = p["q1"], p["q2"]
        p["han_critic_target"] = p["han_critic"]
        return jax.tree.map(self.f, p)

    def losses(self, params, batch):
        """Discrete SAC: twin-critic TD loss, actor loss, temperature loss."""
        sc = self.sac_cfg
        alpha = jnp.exp(params["log_alpha"])
        target_h = sc["entropy_target_frac"] * math.log(self.n_actions)
        sg = jax.lax.stop_gradient
        nl = self.logits(params, batch["next_obs"])
        z_t = self.embed(params["han_critic_target"], batch["next_obs"])
        q_t = jnp.minimum(self.mlp(params["q1_target"], z_t),
                          self.mlp(params["q2_target"], z_t))
        v_next = jnp.sum(jax.nn.softmax(nl) * (q_t - alpha
                                               * jax.nn.log_softmax(nl)), -1)
        y = sg(batch["reward"] + sc["gamma"] * batch["discount"] * v_next)
        z = self.embed(params["han_critic"], batch["obs"])
        q1, q2 = self.mlp(params["q1"], z), self.mlp(params["q2"], z)
        a = batch["action"][:, None]
        q1_a = jnp.take_along_axis(q1, a, -1)[:, 0]
        q2_a = jnp.take_along_axis(q2, a, -1)[:, 0]
        critic = jnp.mean(jnp.square(q1_a - y) + jnp.square(q2_a - y))
        lg = self.logits(params, batch["obs"])
        pi, logpi = jax.nn.softmax(lg), jax.nn.log_softmax(lg)
        actor = jnp.mean(jnp.sum(pi * (sg(alpha) * logpi
                                       - sg(jnp.minimum(q1, q2))), -1))
        entropy = -jnp.sum(pi * logpi, -1)
        temp = params["log_alpha"] * jnp.mean(sg(entropy - target_h))
        return critic + actor + temp, {"critic_loss": critic,
                                       "actor_loss": actor}


TARGETS = ("q1_target", "q2_target", "han_critic_target")


def trainable(params: dict) -> dict:
    return {k: v for k, v in params.items() if k not in TARGETS}


def adam_step(sim: Sim, tc: dict, grads, m, v, params, step):
    """Adam with global-norm clipping, a linear warm-up and cosine decay
    to a tenth; matrices and vectors alike, no weight decay."""
    ft = sim.ft
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, tc["grad_clip"] / jnp.maximum(norm, 1e-9))
    step = jnp.asarray(step, jnp.float32)
    warm, total = tc["lr_warmup_steps"], tc["lr_total_steps"]
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    lr = tc["lr"] * jnp.where(step < warm, step / warm,
                              0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    t = step + 1.0
    b1, b2 = tc["adam_b1"], tc["adam_b2"]
    bc1, bc2 = (1 - b1 ** t).astype(ft), (1 - b2 ** t).astype(ft)
    lr = lr.astype(ft)

    def upd(g, m, v, p):
        g = g * scale.astype(ft)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        p = p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + tc["adam_eps"])
        return p, m, v

    out = jax.tree.map(upd, grads, m, v, params)
    is_t = lambda x: isinstance(x, tuple)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_t)
    return pick(0), pick(1), pick(2)


def polyak(sim: Sim, params: dict) -> dict:
    tau = sim.sac_cfg["tau"]
    params = dict(params)
    for tgt, src in (("q1_target", "q1"), ("q2_target", "q2"),
                     ("han_critic_target", "han_critic")):
        params[tgt] = jax.tree.map(lambda a, b: (1 - tau) * a + tau * b,
                                   params[tgt], params[src])
    return params

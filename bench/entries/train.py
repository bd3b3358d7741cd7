"""Entry ``train``: train the HAN/SAC router against the simulator.

Set-up builds the training state on the device in one jitted call
(``training.init_train_state``) and the jitted, donated iteration
(``training.make_iteration``), and drives that one object from the seed:
the replay warm-up (iterations whose collect fills the buffer and that
skip the SAC update), then ``checked_iterations`` full iterations that the
check compares with the reference.  The window continues the same object
with full iterations, one in flight while the host waits on the previous
one, as ``training.train_router`` loops without logging.

The check follows the same iterations from the seed through the plain
reference, with the actions the program took in its collect: the gap of
each action below the reference's best sampled logit, the replay rows
inserted, each checked iteration's critic and actor loss, the optimizer's
first moment after the first update iteration, and each parameter's
change over the checked iterations.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, counts, reference, system

# the replay rows the check compares: observation leaves of the padded layout
OBS_KEYS = ("expert", "run", "wait", "run_mask", "wait_mask", "arrived")


class Entry:
    def __init__(self, cfg: dict, traffic: dict):
        from repro.core import training

        self.cfg, self.traffic = cfg, traffic
        self.env_cfg, self.pool, self.sac_cfg = system.program_configs(
            cfg, traffic)
        t = traffic
        self.tc = training.TrainConfig(
            n_envs=t["n_envs"], collect_steps=t["collect_steps"],
            updates_per_iter=t["updates_per_iter"], batch_size=t["batch_size"],
            buffer_capacity=t["buffer_capacity"],
            warmup_transitions=t["warmup_transitions"],
            iterations=t["iterations"], lr=t["lr"], obs_fmt=cfg["obs_fmt"])
        per_iter = t["n_envs"] * t["collect_steps"]
        self.per_iter = per_iter
        # iterations whose collect leaves the buffer under the warm-up
        # threshold skip the SAC update
        self.warmup_iters = -(-t["warmup_transitions"] // per_iter) - 1
        self.checked = t["checked_iterations"]
        made = {}

        def init(key):
            params, opt, opt_state, env_states, buf = training.init_train_state(
                self.env_cfg, self.sac_cfg, self.tc, self.pool, key)
            made["opt"] = opt
            buf = dict(buf, capacity=jnp.asarray(buf["capacity"], jnp.int32))
            return params, opt_state, env_states, buf

        self._init = jax.jit(init)
        jax.eval_shape(init, jax.random.PRNGKey(0))
        self._iteration = training.make_iteration(
            self.env_cfg, self.sac_cfg, self.tc, self.pool, made["opt"])
        rows = (self.warmup_iters + self.checked) * per_iter
        self._head = jax.jit(lambda buf: {
            k: jax.tree.map(lambda x: x[:rows], buf[k])
            for k in ("obs", "next_obs", "action", "reward")})

    # -- run ---------------------------------------------------------------

    def _step(self, it: int):
        step = jnp.asarray(it * self.tc.updates_per_iter, jnp.int32)
        (self.params, self.opt_state, self.env_states, self.buf, self.key,
         aux) = self._iteration(self.params, self.opt_state, self.env_states,
                                self.buf, self.key, step)
        return aux

    def setup(self, seed: int) -> None:
        self.seed = seed
        # the key schedule of training.train_router
        k_state, self.key = jax.random.split(jax.random.PRNGKey(seed))
        self.params, self.opt_state, self.env_states, self.buf = self._init(
            k_state)
        snap = {"aux": []}
        t_collect = None
        for it in range(self.warmup_iters + self.checked):
            aux = self._step(it)
            if it == 0:
                jax.block_until_ready(aux)
                t_collect = time.perf_counter()
            if it == self.warmup_iters - 1:
                jax.block_until_ready(aux)
                self.collect_s = (time.perf_counter() - t_collect) / (
                    self.warmup_iters - 1)
                snap["params0"] = jax.device_get(self.params)
            if it == self.warmup_iters:
                snap["m1"] = jax.device_get(self.opt_state["m"])
            if it >= self.warmup_iters:
                snap["aux"].append(jax.device_get(aux))
        snap["params"] = jax.device_get(self.params)
        snap["buf"] = jax.device_get(self._head(self.buf))
        self.snap = snap
        self.it = self.warmup_iters + self.checked

    def window(self, seconds: float) -> dict:
        ann = jax.profiler.TraceAnnotation
        auxs, prev, iters = [], None, 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            with ann("bench.train.dispatch"):
                aux = self._step(self.it)
            self.it += 1
            auxs.append(aux)
            with ann("bench.train.wait"):
                if prev is not None:
                    jax.block_until_ready(prev)
            prev = aux
            iters += 1
            if time.perf_counter() >= end:
                break
        with ann("bench.train.wait"):
            jax.block_until_ready(prev)
        wall = time.perf_counter() - t0
        bad = sum(not all(np.isfinite(float(v)) for v in a.values())
                  for a in auxs)
        n = iters * self.per_iter
        return {"wall_s": wall, "iterations": iters, "transitions": n,
                "attempted": n, "failed": bad * self.per_iter,
                "iteration_s": wall / iters, "collect_s": self.collect_s}

    def traced_stretch(self, seconds: float) -> None:
        self.window(seconds)

    def trace_prepare(self) -> None:
        pass

    def trace_extras(self) -> dict:
        return {}

    def release(self) -> None:
        self.params = self.opt_state = self.env_states = self.buf = None

    def episode_counts(self) -> dict:
        return {"replay_rows_checked": int(self.snap["buf"]["action"].shape[0])}

    # -- check ---------------------------------------------------------------

    def check(self, control: bool = False) -> dict:
        snap = self.snap
        ref = self._follow("ref")
        out = _numbers(snap, ref)
        if control:
            ctl = self._follow("ctl")
            for k, v in _numbers(_as_snap(ctl), ref).items():
                out[f"control.{k}"] = v
        return out

    def _follow(self, name: str) -> dict:
        """The reference (``ref``, float32) or the control (``ctl``,
        bfloat16) through the set-up's iterations, from the seed, with the
        program's collect actions."""
        ft = jnp.float32 if name == "ref" else jnp.bfloat16
        fns = reference.built(
            ("train", json.dumps(self.cfg), json.dumps(self.traffic), name),
            lambda: _make_follow(reference.Sim(self.cfg, self.traffic, ft),
                                 self.traffic))
        t = self.traffic
        n_it = self.warmup_iters + self.checked
        acts = jnp.asarray(self.snap["buf"]["action"]).reshape(
            n_it, t["collect_steps"], t["n_envs"])
        params, opt, states, key, buf = fns["init"](
            jax.random.PRNGKey(self.seed), n_it * self.per_iter)
        res = {"aux": [], "gap": [], "first": [], "pert": []}
        for it in range(n_it):
            states, key, buf, ys = fns["collect"](params, states, key, buf,
                                                  acts[it], it)
            for k in ("gap", "first", "pert"):
                res[k].append(ys[k])
            if it == self.warmup_iters - 1:
                res["params0"] = params
            if it >= self.warmup_iters:
                params, opt, key, aux = fns["update"](
                    params, opt, key, buf, (it + 1) * self.per_iter,
                    it * t["updates_per_iter"])
                res["aux"].append(aux)
                if it == self.warmup_iters:
                    res["m1"] = opt["m"]
        res["params"], res["buf"] = params, buf
        return jax.device_get(res)

    def counts(self) -> dict:
        cfg, t = self.cfg, self.traffic
        n = cfg["n_experts"]
        return {"flops_per_iteration": counts.train_iteration_flops(
            n, n * cfg["run_cap"], n * cfg["wait_cap"], cfg["han"],
            cfg["sac"]["hidden"], n + 1, t["n_envs"], t["collect_steps"],
            t["updates_per_iter"], t["batch_size"])}


def _make_follow(sim, t: dict) -> dict:
    n_envs, steps = t["n_envs"], t["collect_steps"]
    per_iter = n_envs * steps

    def init(key, rows):
        k_state, key = jax.random.split(key)
        k_init, k_env = jax.random.split(k_state)
        params = sim.init_params(k_init)
        zeros = jax.tree.map(jnp.zeros_like, reference.trainable(params))
        states = jax.vmap(sim.reset)(jax.random.split(k_env, n_envs))
        o = sim.obs(sim.reset(jax.random.PRNGKey(0)))
        rows_of = lambda x: jnp.zeros((rows,) + x.shape, x.dtype)
        buf = {"obs": jax.tree.map(rows_of, o),
               "next_obs": jax.tree.map(rows_of, o),
               "action": jnp.zeros((rows,), jnp.int32),
               "reward": jnp.zeros((rows,), sim.ft)}
        return params, {"m": zeros, "v": zeros}, states, key, buf

    def collect(params, states, key, buf, acts, it):
        obs = jax.vmap(sim.obs)(states)

        def body(carry, a):
            states, obs, key = carry
            key, k_act = jax.random.split(key)
            lg = sim.logits(params, obs).astype(jnp.float32)
            pert = lg + jax.random.gumbel(k_act, lg.shape, jnp.float32)
            gap = jnp.max(pert, -1) - jnp.take_along_axis(
                pert, a[:, None], -1)[:, 0]
            states, r = jax.vmap(sim.step)(states, a)
            nxt = jax.vmap(sim.obs)(states)
            row = {"obs": obs, "next_obs": nxt, "action": a, "reward": r}
            return (states, nxt, key), (row, {"gap": gap,
                                              "first": jnp.argmax(pert, -1),
                                              "pert": pert})

        (states, _, key), (rows, ys) = jax.lax.scan(
            body, (states, obs, key), acts)
        start = it * per_iter
        put = lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
            dst, src.reshape((per_iter,) + src.shape[2:]), start, 0)
        buf = jax.tree.map(put, buf, rows)
        return states, key, buf, ys

    def update(params, opt, key, buf, size, step):
        def one(carry, _):
            params, opt, key = carry
            key, k_s = jax.random.split(key)
            idx = jax.random.randint(k_s, (t["batch_size"],), 0,
                                     jnp.maximum(size, 1))
            batch = jax.tree.map(lambda x: x[idx], buf)
            batch["discount"] = jnp.ones_like(batch["reward"])

            def loss(tr):
                return sim.losses({**params, **tr}, batch)

            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(
                reference.trainable(params))
            new, m, v = reference.adam_step(sim, t, g, opt["m"], opt["v"],
                                            reference.trainable(params), step)
            params = reference.polyak(sim, {**params, **new})
            return (params, {"m": m, "v": v}, key), aux

        (params, opt, key), aux = jax.lax.scan(
            one, (params, opt, key), None, length=t["updates_per_iter"])
        return params, opt, key, jax.tree.map(jnp.mean, aux)

    return {"init": jax.jit(init, static_argnums=1),
            "collect": jax.jit(collect),
            "update": jax.jit(update)}


def _as_snap(res: dict) -> dict:
    """The control's readings in the program snapshot's form."""
    acts = np.concatenate([np.asarray(f).reshape(-1) for f in res["first"]])
    return {"params0": res["params0"], "params": res["params"],
            "m1": res["m1"], "aux": res["aux"],
            "buf": dict(res["buf"], action=acts)}


def _numbers(snap: dict, ref: dict) -> dict:
    """Compared numbers of ``snap`` (the program's, or the control's in
    that form) against the reference's ``ref``."""
    f64 = lambda x: np.asarray(x, np.float64)
    out = {}
    # collect: the gap of each action taken below the reference's best
    pert = np.concatenate([np.asarray(p).reshape(-1, p.shape[-1])
                           for p in ref["pert"]])
    acts = np.asarray(snap["buf"]["action"]).reshape(-1)
    out["action_gap"] = float(np.max(
        pert.max(-1) - np.take_along_axis(pert, acts[:, None], -1)[:, 0]))
    # replay insert: the rows the collect wrote
    err = check.scaled_err(f64(snap["buf"]["reward"]), f64(ref["buf"]["reward"]))
    for part in ("obs", "next_obs"):
        for k in OBS_KEYS:
            err = max(err, check.scaled_err(f64(snap["buf"][part][k]),
                                            f64(ref["buf"][part][k])))
    out["replay_err"] = err
    # SAC update: losses of each checked iteration
    gaps = []
    for got, want in zip(snap["aux"], ref["aux"]):
        for k in ("critic_loss", "actor_loss"):
            gaps.append(abs(float(got[k]) - float(want[k]))
                        / max(abs(float(want[k])), 1e-30))
    out["loss_gap"] = max(gaps) if gaps else float("inf")
    # the optimizer's first moment after the first update iteration, and
    # each parameter's change over the checked iterations
    tr = lambda p: jax.tree.leaves(reference.trainable(p))
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(reference.trainable(ref["params"]))]
    m_ref = [f64(x) for x in jax.tree.leaves(ref["m1"])]
    m_got = [f64(x) for x in jax.tree.leaves(snap["m1"])]
    out["grad_gap"] = check.leaf_norm_gap(m_got, m_ref)
    m_norm = np.array([np.linalg.norm(x) for x in m_ref])
    moved = m_norm >= 1e-3 * np.median(m_norm)
    d_got = [f64(a) - f64(b) for a, b in zip(tr(snap["params"]),
                                             tr(snap["params0"]))]
    d_ref = [f64(a) - f64(b) for a, b in zip(tr(ref["params"]),
                                             tr(ref["params0"]))]
    out["param_change_gap"] = check.leaf_norm_gap(d_got, d_ref, moved)
    worst = {k: names[check.worst_leaf(g, w, keep)] for k, g, w, keep in
             (("grad_gap", m_got, m_ref, None),
              ("param_change_gap", d_got, d_ref, moved))}
    print(f"[bench] worst leaves: {worst}; leaves left out of the change "
          f"(reference first moment under 1e-3 of the median): "
          f"{[n for n, k in zip(names, moved) if not k]}", file=sys.stderr)
    return out

"""Entry ``route``: route a request stream through the simulator.

The timed program is one jitted chunk: ``vmap`` over the cell's envs of a
``lax.scan`` over ``chunk_steps`` routing decisions, each
``features.build_obs`` -> the HAN/SAC actor (``routers.sac_policy``,
sampled actions as in the training collect) -> ``env.step``, the body of
``training.evaluate``, carrying env state and keys from chunk to chunk.
Each env routes with a router of its own, whose weights are made on the
device from the seed: the load that an untrained router's weights put on
the experts then averages over the envs, rather than one draw setting it
for every env, so that seeds do alike work.

Set-up compiles the chunk and runs ``warmup_steps`` decisions per env so
that the queues reach steady occupancy.  The window then runs chunks back
to back, one in flight while the host waits on the previous one.  Every
``reset_chunks`` chunks the envs return to the state that set-up left
(the sampling keys run on), so the window covers one fixed range of
simulated time however fast the chip steps it: a run that gets further in
its seconds repeats that range more often, rather than reaching other
phases of a diurnal arrival process, whose steps cost more or less.

The check replays sampled chunks of the window through the plain
reference from the chunk's input state, with the actions the program
took: the gap of each action below the reference's best sampled logit,
the integer queue state and the float state after the chunk.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import advance_drive, check, counts, reference, system


class Entry:
    def __init__(self, cfg: dict, traffic: dict):
        from repro.core import features, routers, sac
        from repro.env import env

        self.cfg, self.traffic = cfg, traffic
        self.env_cfg, self.pool, self.sac_cfg = system.program_configs(
            cfg, traffic)
        self.n_envs = traffic["n_envs"]
        self.k = traffic["chunk_steps"]
        env_cfg, pool, sac_cfg = self.env_cfg, self.pool, self.sac_cfg
        fmt, n_envs, k = cfg["obs_fmt"], self.n_envs, self.k

        streams = jax.random.split(
            jax.random.PRNGKey(traffic["arrival_seed"]), n_envs)

        def init(key):
            # every seed routes the same set of arrival and request streams
            # (one per env, drawn from the mix's arrival_seed), dealt to the
            # envs in another order, so that seeds do the same work; the
            # seed makes the routers' weights and their sampling keys
            k_params, k_env, k_pol = jax.random.split(key, 3)
            params = jax.vmap(lambda kk: sac.init_params(kk, sac_cfg))(
                jax.random.split(k_params, n_envs))
            states = jax.vmap(lambda kk: env.reset(env_cfg, pool, kk))(
                jax.random.permutation(k_env, streams))
            return params, states, jax.random.split(k_pol, n_envs)

        def chunk(params, states, keys):
            def one(params, state, key):
                policy = routers.sac_policy("qos", sac_cfg, params,
                                            greedy=False, obs_fmt=fmt)

                def body(carry, _):
                    state, key = carry
                    key, k_act = jax.random.split(key)
                    obs = features.build_obs(env_cfg, pool, state, fmt=fmt)
                    a, _ = policy.act({}, state, obs, k_act)
                    state, r, _ = env.step(env_cfg, pool, state, a)
                    return (state, key), (a, r)

                (state, key), (acts, rews) = jax.lax.scan(
                    body, (state, key), None, length=k)
                return state, key, acts, rews

            return jax.vmap(one)(params, states, keys)

        self._init = jax.jit(init)
        self._chunk = jax.jit(chunk)

    # -- run ---------------------------------------------------------------

    def setup(self, seed: int) -> None:
        self.seed = seed
        self.params, self.states, self.keys = self._init(
            jax.random.PRNGKey(seed))
        n_warm = -(-self.traffic["warmup_steps"] // self.k)
        for _ in range(n_warm):
            self.states, self.keys, _, rews = self._chunk(
                self.params, self.states, self.keys)
        jax.block_until_ready(rews)
        self.warm_steps = self.steps = n_warm * self.k
        self.warm_states, self.since_reset = self.states, 0
        # one sampled chunk early in the window, drawn from the seed, and
        # the window's last chunk are checked
        self.sample_at = int(np.random.default_rng(seed).integers(0, 4))

    def window(self, seconds: float) -> dict:
        ann = jax.profiler.TraceAnnotation
        rews, samples, prev, chunks = [], {}, None, 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            if self.since_reset == self.traffic["reset_chunks"]:
                self.states, self.since_reset = self.warm_states, 0
                self.steps = self.warm_steps
            inputs = (self.states, self.keys)
            with ann("bench.route.dispatch"):
                out = self._chunk(self.params, *inputs)
            if chunks == self.sample_at:
                samples["sampled"] = (inputs, out)
            last = (inputs, out)
            self.states, self.keys = out[0], out[1]
            self.steps += self.k
            self.since_reset += 1
            rews.append(out[3])
            with ann("bench.route.wait"):
                if prev is not None:
                    prev.block_until_ready()
            prev = out[3]
            chunks += 1
            if time.perf_counter() >= end:
                break
        with ann("bench.route.wait"):
            prev.block_until_ready()
        wall = time.perf_counter() - t0
        samples["last"] = last
        self.samples = samples
        decisions = chunks * self.n_envs * self.k
        bad = int(sum(np.sum(~np.isfinite(np.asarray(r))) for r in rews))
        return {"wall_s": wall, "chunks": chunks, "decisions": decisions,
                "attempted": decisions, "failed": bad}

    def traced_stretch(self, seconds: float) -> None:
        """More chunks, run as the window runs them, for the profiler; the
        window's checked chunks stay as they were."""
        samples = self.samples
        self.window(seconds)
        self.samples = samples

    def trace_prepare(self) -> None:
        """Compile and warm the engine drive (``advance_drive``) at this
        cell's fleet, rate and envs, before the profiler starts."""
        cfg, t = self.cfg, self.traffic
        drive, arrays, empty = advance_drive.make_drive(self.env_cfg,
                                                        self.pool)
        rate = system.arrival_config(cfg, t)["rate"]
        k1, k2 = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(self.seed), 1))
        stream = lambda k: advance_drive.make_stream(
            k, self.n_envs, t["advance_windows"], cfg["n_experts"], rate)
        carry = jax.block_until_ready(
            drive(arrays, empty(self.n_envs), stream(k1)))
        self._drive = (drive, arrays, carry, stream(k2))

    def trace_extras(self) -> dict:
        """One traced call of the engine drive, from steady queues."""
        drive, arrays, carry, stream = self._drive
        with jax.profiler.TraceAnnotation("bench.advance"):
            jax.block_until_ready(drive(arrays, carry, stream))
        cfg = self.cfg
        shape = (cfg["n_experts"], cfg["run_cap"], cfg["wait_cap"], self.n_envs)
        return {"advance_program": "bench_advance_drive",
                "advance_calls": self.traffic["advance_windows"],
                "advance_bytes": counts.advance_bytes(*shape),
                "advance_ops": counts.advance_ops(*shape)}

    def release(self) -> None:
        """Drop the program's live state before the reference runs."""
        self.params = self.states = self.keys = self.warm_states = None

    def episode_counts(self) -> dict:
        """The program's own counters, summed over envs."""
        from repro.env import env
        m = jax.vmap(env.episode_metrics)(self.states)
        return {k: float(jnp.sum(m[k])) for k in
                ("routed", "completed", "dropped")} | {
            "violations": float(jnp.sum(self.states["stats"]["viol"])),
            "sim_seconds": float(jnp.mean(self.states["clock"])),
            # each step routes one arrival: the realised arrival rate of the
            # program's own arrival process, per env
            "arrivals_per_sim_s": float(jnp.mean(
                self.steps / self.states["clock"]))}

    # -- check ---------------------------------------------------------------

    def _replay(self, name: str):
        ft = jnp.float32 if name == "ref" else jnp.bfloat16
        return reference.built(
            ("route", json.dumps(self.cfg), json.dumps(self.traffic), name),
            lambda: make_replay(reference.Sim(self.cfg, self.traffic, ft)))

    def check(self, control: bool = False) -> dict:
        """Numbers compared with the reference; with ``control`` also the
        control's (the reference in bfloat16 in the program's place)."""
        names = ("ref", "ctl") if control else ("ref",)
        k_params = jax.random.split(jax.random.PRNGKey(self.seed), 3)[0]
        out = {}
        for which in self.samples.values():
            (s_in, keys_in), (s_out, keys_out, acts, rews) = which
            res = {name: self._replay(name)(k_params, s_in, keys_in, acts)
                   for name in names}
            ref = res["ref"]
            got = jax.vmap(self._replay("ref").sim.from_program)(s_out)
            mism, err = check.state_numbers(jax.device_get(got),
                                            jax.device_get(ref["state"]))
            mism += int(np.sum(np.asarray(keys_out) != np.asarray(ref["key"])))
            err = max(err, check.scaled_err(rews, ref["reward"]))
            _worst(out, "action_gap", float(jnp.max(ref["gap"])))
            _worst(out, "queue_mismatch", mism)
            _worst(out, "state_err", err)
            if control:
                ctl = res["ctl"]
                pert = ref["pert"]
                first = jnp.take_along_axis(pert, ctl["first"][..., None],
                                            -1)[..., 0]
                _worst(out, "control.action_gap",
                       float(jnp.max(jnp.max(pert, -1) - first)))
                c_state = jax.tree.map(lambda x: x.astype(jnp.float32)
                                       if x.dtype == jnp.bfloat16 else x,
                                       ctl["state"])
                mism, err = check.state_numbers(
                    jax.device_get(c_state), jax.device_get(ref["state"]))
                err = max(err, check.scaled_err(
                    ctl["reward"].astype(jnp.float32), ref["reward"]))
                _worst(out, "control.queue_mismatch", mism)
                _worst(out, "control.state_err", err)
        return out

    def counts(self) -> dict:
        cfg = self.cfg
        rc, wc = self.env_cfg.run_caps, self.env_cfg.wait_caps
        n = cfg["n_experts"]
        run_rows = sum(rc) if rc else n * cfg["run_cap"]
        wait_rows = sum(wc) if wc else n * cfg["wait_cap"]
        return {"policy_flops_per_decision": counts.policy_forward_flops(
            n, run_rows, wait_rows, cfg["han"], cfg["sac"]["hidden"],
            n + 1)}


def _worst(out: dict, name: str, value) -> None:
    out[name] = max(out.get(name, value), value)


def make_replay(sim):
    """The reference over one chunk of every env, with the program's
    actions, from the program's input state (jitted)."""
    def run(k_params, states, keys, acts):
        params = jax.vmap(sim.init_params)(
            jax.random.split(k_params, acts.shape[0]))

        def one(params, s, key, acts):
            s = sim.from_program(s)

            def body(carry, a):
                s, key = carry
                key, k_act = jax.random.split(key)
                lg = sim.logits(params, sim.obs(s)).astype(jnp.float32)
                pert = lg + jax.random.gumbel(k_act, lg.shape, jnp.float32)
                gap = jnp.max(pert) - pert[a]
                s, r = sim.step(s, a)
                return (s, key), (gap, jnp.argmax(pert), pert, r)

            (s, key), (gap, first, pert, r) = jax.lax.scan(
                body, (s, key), acts)
            return {"state": s, "key": key, "gap": gap, "first": first,
                    "pert": pert, "reward": r}

        return jax.vmap(one)(params, states, keys, acts)

    fn = jax.jit(run)
    fn.sim = sim
    return fn

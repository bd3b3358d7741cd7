"""The router's and the served model's layers are named in the compiled
program.

Each name of ``spans.SCOPES`` has to reach the ``op_name`` metadata of some
instruction of the optimized HLO, in the routing chunk (built as the
benchmark's route entry builds it), in the training iteration, or in the
decode step of a served DeepSeek-V2-kind model, so that a trace joined
with those names splits device time by layer.  Under the
Pallas backend the lockstep loop runs inside one kernel, so no instruction
is a trip of it.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_config
from repro.core import features, han, routers, sac, spans, training
from repro.env import env, serve_engine
from repro.models import model

N = 3


def configs(backend):
    env_cfg = env.EnvConfig(n_experts=N, run_cap=2, wait_cap=2,
                            engine_backend=backend)
    sac_cfg = sac.SACConfig(n_actions=N + 1, hidden=8,
                            han=han.HANConfig(hidden=8, heads=2, layers=1))
    return env_cfg, env.make_env_pool(env_cfg), sac_cfg


def op_names(compiled_text):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def has(scope, op_name):
    """``scope`` is a whole segment of the path, bare or inside a
    transform's wrapper such as ``vmap(...)``."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)",
                     op_name) is not None


def route_chunk(env_cfg, pool, sac_cfg, n_envs=2, k=3):
    """A vmap over envs, each with its own router, of a scan over routing
    decisions: ``build_obs`` -> the SAC actor -> ``env.step``."""
    def chunk(params, states, keys):
        def one(params, state, key):
            policy = routers.sac_policy("qos", sac_cfg, params, greedy=False)

            def body(carry, _):
                state, key = carry
                key, k_act = jax.random.split(key)
                obs = features.build_obs(env_cfg, pool, state)
                a, _ = policy.act({}, state, obs, k_act)
                state, r, _ = env.step(env_cfg, pool, state, a)
                return (state, key), r

            (state, key), rews = jax.lax.scan(body, (state, key), None,
                                              length=k)
            return state, key, rews

        return jax.vmap(one)(params, states, keys)

    def init(key):
        ks = jax.random.split(key, n_envs)
        params = jax.vmap(lambda kk: sac.init_params(kk, sac_cfg))(ks)
        states = jax.vmap(lambda kk: env.reset(env_cfg, pool, kk))(ks)
        return params, states, ks

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return jax.jit(chunk).lower(*shapes).compile().as_text()


def train_iteration(env_cfg, pool, sac_cfg):
    tc = training.TrainConfig(n_envs=2, collect_steps=2, updates_per_iter=2,
                              batch_size=4, buffer_capacity=16,
                              warmup_transitions=4, iterations=2)
    made = {}

    def init(key):
        params, opt, opt_state, env_states, buf = training.init_train_state(
            env_cfg, sac_cfg, tc, pool, key)
        made["opt"] = opt
        return params, opt_state, env_states, buf

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    iteration = training.make_iteration(env_cfg, sac_cfg, tc, pool,
                                        made["opt"])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return iteration.lower(*shapes, key, step).compile().as_text()


def serve_decode():
    """The decode step of an ``ExpertServer`` over a small latent-attention
    model with shared and routed experts."""
    cfg = reduce_config(get_config("deepseek-v2-lite"))
    _, decode = serve_engine.make_steps(cfg, 32)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    cache = jax.eval_shape(lambda: model.init_cache(cfg, 2, 32))
    tokens = jax.ShapeDtypeStruct((2,), jnp.int32)
    return decode.lower(params, cache, tokens).compile().as_text()


@pytest.fixture(scope="module")
def decode_names():
    return op_names(serve_decode())


@pytest.fixture(scope="module", params=["xla", "pallas"])
def compiled(request, decode_names):
    env_cfg, pool, sac_cfg = configs(request.param)
    return request.param, (op_names(route_chunk(env_cfg, pool, sac_cfg))
                           + op_names(train_iteration(env_cfg, pool,
                                                      sac_cfg))
                           + decode_names)


def test_every_scope_reaches_the_compiled_program(compiled):
    backend, names = compiled
    expect = set(spans.SCOPES)
    if backend == "pallas":
        # the kernel runs the whole loop: no instruction is one trip of it
        expect.discard(spans.TRIP)
        assert not any(has(spans.TRIP, n) for n in names)
    missing = [s for s in sorted(expect) if not any(has(s, n) for n in names)]
    assert not missing, missing


def test_trip_sits_under_advance(compiled):
    backend, names = compiled
    trips = [n for n in names if has(spans.TRIP, n)]
    assert bool(trips) == (backend == "xla")
    for n in trips:
        adv = re.search(rf"(^|[/(]){re.escape(spans.ADVANCE)}[/)]", n)
        assert adv is not None and adv.start() < n.index(spans.TRIP), n


def test_serving_scopes_sit_under_decode(decode_names):
    # whole paths (a reduction's body names its ops relative to the call)
    for scope in (spans.MLA, spans.ROUTED, spans.SHARED):
        inside = [n for n in decode_names
                  if has(scope, n) and n.startswith("jit(")]
        assert inside, scope
        assert all(has(spans.DECODE, n) for n in inside), scope


def test_scopes_are_named_once():
    assert len(set(spans.SCOPES)) == len(spans.SCOPES) == 13
    assert all(re.fullmatch(r"[a-z]+\.[a-z]+", s) for s in spans.SCOPES)
